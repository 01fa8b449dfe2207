"""SpMM on ME-BCRS: C (M, N) = A_sparse (M, K) @ B_dense (K, N).

Three execution paths:

  * ``blocked`` (default, XLA): the swap-and-transpose window GEMM expressed
    in jnp — gather B rows once (contiguous, the TPU analogue of the paper's
    coalesced access), per-K-block partial products, segment-sum over
    windows.  jit/pjit/shard_map friendly; this path backs the dry-run and
    the distributed models.
  * ``pallas``: the TPU kernel (kernels/spmm_pallas.py), gather-free grouped
    window-GEMM — dense rows are DMA'd HBM→VMEM inside the kernel from the
    original B operand (no staging buffer), double-buffered, with the
    zero-init and output cast fused into the epilogue (DESIGN.md §3).
    Validated in interpret mode on CPU; compiles to Mosaic on TPU
    (``interpret=None`` auto-detects).
  * ``coo_segment``: element-wise scatter-add SpMM — the "CUDA-core class"
    baseline (Sputnik / RoDe / cuSPARSE row algorithms reduce to this data
    flow on TPU); also serves as an independent oracle.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from . import dispatch as _dispatch
from . import validate as _validate
from .format import MEBCRS, BlockedMEBCRS, block_format, to_coo

__all__ = ["spmm", "spmm_blocked", "spmm_coo_segment", "spmm_dense_ref"]


def spmm_dense_ref(a_dense: jax.Array, b: jax.Array) -> jax.Array:
    """Dense oracle (the "cuSPARSE-class" dense baseline is simply XLA dot)."""
    return jnp.dot(a_dense, b, preferred_element_type=jnp.float32).astype(b.dtype)


@partial(jax.jit, static_argnames=("out_rows",))
def _spmm_blocked_impl(blocked: BlockedMEBCRS, b: jax.Array, out_rows: int):
    v = blocked.vector_size
    k_blk = blocked.k_blk
    nb = blocked.num_blocks
    w = blocked.num_windows

    bgath = jnp.take(b, blocked.cols, axis=0)            # (NB*K_BLK, N) contiguous gather
    vals = blocked.vals.reshape(nb, k_blk, v)            # Aᵀ blocks (k × n of the MMA)
    gb = bgath.reshape(nb, k_blk, -1)                    # Bᵀ side (m × k after swap)
    # Swap-and-transpose contraction: C_wᵀ = Σ_blocks B_gᵀ @ A_wᵀ.  We keep C
    # un-transposed in memory; the contraction over the vector index t is
    # identical mathematics (see DESIGN.md §2).
    partial_c = jnp.einsum(
        "bkv,bkn->bvn", vals, gb, preferred_element_type=jnp.float32
    )                                                     # (NB, V, N)
    c_win = jax.ops.segment_sum(partial_c, blocked.block_win, num_segments=w)
    c = c_win.reshape(w * v, -1)[:out_rows]
    return c.astype(b.dtype)


def spmm_blocked(fmt, b: jax.Array, k_blk: int = 8) -> jax.Array:
    """XLA swap-and-transpose SpMM: ``C (M, N) = A @ B`` over the blocked
    view (``fmt`` may be canonical :class:`MEBCRS` or already blocked).
    Returns ``(M, N)`` in ``b``'s dtype; fp32 accumulation."""
    blocked = fmt if isinstance(fmt, BlockedMEBCRS) else block_format(fmt, k_blk)
    blocked, b = _precision_blocked(blocked, b, None)  # dequantize int8 formats
    return _spmm_blocked_impl(blocked, b, blocked.shape[0])


@partial(jax.jit, static_argnames=("num_rows",))
def spmm_coo_segment(rows, cols, vals, b, num_rows: int):
    """Element-wise scatter-add SpMM (CUDA-core-class baseline / oracle)."""
    contrib = vals[:, None] * jnp.take(b, cols, axis=0)
    return jax.ops.segment_sum(contrib, rows, num_segments=num_rows).astype(b.dtype)


def spmm(fmt: MEBCRS, b: jax.Array, impl: str = "blocked", k_blk: int = 8,
         interpret: bool | None = None, n_blk: int | None = None,
         split_blk: int | None = None, schedule=None, mesh=None, part=None,
         n_batches: int | None = None, precision: str | None = None,
         check: str | None = None, strict: bool | None = None,
         guard_nonfinite: bool = False) -> jax.Array:
    """SpMM dispatch through the unified registry (:mod:`repro.core.dispatch`).

    ``impl`` names a registered implementation (``dispatch.impls("spmm")``
    lists them: blocked / pallas / pallas_balanced / pallas_staged /
    pallas_noncoalesced / coo_segment / the sharded impls).
    ``interpret=None`` auto-detects: the Pallas paths compile to Mosaic on
    a TPU backend and fall back to interpret mode elsewhere (resolved in
    :mod:`repro.kernels.ops`); pass ``True``/``False`` to force a mode.
    An explicit ``n_blk`` overrides the column tile of the Pallas paths.
    ``split_blk``/``schedule`` parameterize the block-parallel
    ``pallas_balanced`` grid (DESIGN.md §11).
    ``precision`` selects the mixed-precision path (DESIGN.md §13:
    ``"fp32"``/``"bf16"``/``"int8"``; ``None`` = operand dtypes as given)
    and is capability-checked against the impl's registry entry.

    Robustness knobs (DESIGN.md §15): ``check`` audits ``fmt`` and guards
    ``b`` before dispatch (``None`` → ambient
    :func:`repro.core.validate.check_level`, default ``"none"`` — the
    hot path stays bitwise-identical).  ``strict``/``guard_nonfinite``
    route through :func:`repro.core.dispatch.robust_dispatch`:
    ``strict=False`` degrades down the capability ladder on kernel
    failure (one :class:`~repro.core.dispatch.FallbackWarning` + call-log
    record), ``strict=True`` re-raises the impl's own error, and
    ``guard_nonfinite=True`` re-runs a bf16/int8 forward at fp32 when the
    narrow path yields NaN/Inf.  ``strict=None`` (default) keeps the
    plain non-degrading dispatch.
    """
    level = _validate.effective_check(check, fmt.values
                                     if hasattr(fmt, "values")
                                     else fmt.vals, b)
    if level != "none":
        _validate.validate(fmt, check=level)
        _validate.guard_operand(b, "b")
    kwargs = {"k_blk": k_blk, "interpret": interpret}
    if n_blk is not None:
        kwargs["n_blk"] = n_blk
    if split_blk is not None:
        kwargs["split_blk"] = split_blk
    if schedule is not None:
        kwargs["schedule"] = schedule
    if mesh is not None:
        kwargs["mesh"] = mesh
    if part is not None:
        kwargs["part"] = part
    if n_batches is not None:
        kwargs["n_batches"] = n_batches
    if precision is not None:
        if strict is None:
            _dispatch.require("spmm", impl, precision=precision)
        kwargs["precision"] = precision
    if strict is None and not guard_nonfinite:
        return _dispatch.dispatch("spmm", impl, fmt, b, **kwargs)
    # guard_nonfinite without an explicit strict keeps legacy error
    # behavior (no silent degradation) — only the fp32 rescue is added.
    strict_eff = bool(strict) if strict is not None else True
    return _dispatch.robust_dispatch("spmm", impl, fmt, b,
                                     strict=strict_eff,
                                     guard_nonfinite=guard_nonfinite,
                                     **kwargs)


# ---------------------------------------------------------------------------
# Registry adapters — uniform (fmt_or_blocked, b, *, k_blk, n_blk, interpret)
# signature so every layer resolves impls identically.
# ---------------------------------------------------------------------------


def _precision_blocked(blocked: BlockedMEBCRS, b: jax.Array,
                       precision: str | None):
    """XLA-oracle precision transform mirroring the kernels' policy.

    bf16 narrows both operands (the fp32-accumulating einsum is the
    oracle for the Pallas bf16 path); int8 quantizes the values per
    K-block and *dequantizes in fp32* — arithmetically the kernels'
    ``scale · dot(q, b)`` with the scale folded in, so this is the
    reference the tolerance ladder compares the in-VMEM-dequantizing
    kernel against.  A format already carrying int8 values + scales is
    dequantized regardless of ``precision`` (auto-detect, as in the
    kernels)."""
    from .quantize import (dequantize_block_values, quantize_block_values,
                           validate_precision)

    validate_precision(precision)
    vals = blocked.vals
    if blocked.scales is not None and vals.dtype == jnp.int8:
        vals = dequantize_block_values(vals, blocked.scales)
    elif precision == "int8":
        q, scales = quantize_block_values(vals, blocked.k_blk)
        vals = dequantize_block_values(q, scales)
    if precision in ("bf16", "int8"):
        b = b.astype(jnp.bfloat16)
        if precision == "bf16":
            vals = vals.astype(jnp.bfloat16)
    elif precision == "fp32":
        vals = vals.astype(jnp.float32)
        b = b.astype(jnp.float32)
    if vals is not blocked.vals:
        blocked = dataclasses.replace(blocked, vals=vals, scales=None)
    return blocked, b


def _spmm_blocked_adapter(fmt, b, *, k_blk: int = 8, n_blk: int | None = None,
                          interpret: bool | None = None,
                          precision: str | None = None):
    del n_blk, interpret  # XLA path: no column tiling / interpret mode
    blocked = fmt if isinstance(fmt, BlockedMEBCRS) else block_format(fmt, k_blk)
    blocked, b = _precision_blocked(blocked, b, precision)
    return _spmm_blocked_impl(blocked, b, blocked.shape[0])


def _spmm_coo_adapter(fmt, b, *, k_blk: int = 8, n_blk: int | None = None,
                      interpret: bool | None = None):
    """CUDA-core-class oracle via host-side COO conversion (not traceable)."""
    del k_blk, n_blk, interpret
    rows, cols, vals = to_coo(fmt)
    return spmm_coo_segment(jnp.asarray(rows, jnp.int32),
                            jnp.asarray(cols, jnp.int32),
                            jnp.asarray(vals), b, num_rows=fmt.shape[0])


_dispatch.register("spmm", "blocked", _spmm_blocked_adapter,
                   differentiable=True, batched=True,
                   precisions=("fp32", "bf16", "int8"))
_dispatch.register("spmm", "coo_segment", _spmm_coo_adapter)
