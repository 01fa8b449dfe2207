"""SDDMM on ME-BCRS: C_sparse = mask ∘ (Q @ Kᵀ) sampled at A's pattern.

In attention GNNs (AGNN/GAT) Q = K = node features; the sparse output feeds
the subsequent SpMM (paper §3.4), so the result is returned *in ME-BCRS
layout* — values (NNZV, V), vector-major — ready to be consumed by
:func:`repro.core.spmm.spmm` with no re-translation.  This reproduces the
paper's "output splitting for subsequent SpMM" at format level (the GPU
version needs Algorithm 1's per-thread offset arithmetic; on TPU the
vector-major layout already matches, one of the places the swap-and-
transpose co-design is *cheaper* on TPU than GPU).
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from . import dispatch as _dispatch
from . import validate as _validate
from .format import MEBCRS, BlockedMEBCRS, block_format, to_coo

__all__ = ["sddmm", "sddmm_blocked", "sddmm_dense_ref", "sddmm_coo",
           "attention"]


def sddmm_dense_ref(a_mask_dense: jax.Array, q: jax.Array, k: jax.Array) -> jax.Array:
    """Dense oracle: (Q @ Kᵀ) ∘ mask, full (M, Mc) output."""
    scores = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
    return (scores * (a_mask_dense != 0)).astype(q.dtype)


@jax.jit
def _sddmm_blocked_impl(blocked: BlockedMEBCRS, q: jax.Array, k: jax.Array):
    v = blocked.vector_size
    k_blk = blocked.k_blk
    nb = blocked.num_blocks
    w = blocked.num_windows

    # Pad Q rows up to W*V (last window residue).
    qpad = jnp.zeros((w * v, q.shape[1]), q.dtype).at[: q.shape[0]].set(q)
    qwin = qpad.reshape(w, v, -1)                       # (W, V, F)
    kg = jnp.take(k, blocked.cols, axis=0)              # (NB*K_BLK, F) gather
    kg = kg.reshape(nb, k_blk, -1)
    qg = jnp.take(qwin, blocked.block_win, axis=0)      # (NB, V, F)
    scores = jnp.einsum(
        "bkf,bvf->bkv", kg, qg, preferred_element_type=jnp.float32
    ).reshape(nb * k_blk, v)
    return (scores * blocked.mask).astype(q.dtype)


def sddmm_blocked(fmt, q: jax.Array, k: jax.Array, k_blk: int = 8):
    """Returns values (NNZP, V) aligned with the blocked view's layout."""
    blocked = fmt if isinstance(fmt, BlockedMEBCRS) else block_format(fmt, k_blk)
    return _sddmm_blocked_impl(blocked, q, k)


@partial(jax.jit)
def sddmm_coo(rows, cols, q, k):
    """Edge-wise SDDMM (CUDA-core-class baseline): e_ij = <Q_i, K_j>."""
    return jnp.sum(jnp.take(q, rows, axis=0) * jnp.take(k, cols, axis=0), axis=-1)


def sddmm(fmt, q: jax.Array, k: jax.Array, impl: str = "blocked",
          k_blk: int = 8, interpret: bool | None = None,
          f_blk: int | None = None, split_blk: int | None = None,
          schedule=None, mesh=None, part=None, n_batches: int | None = None,
          precision: str | None = None,
          check: str | None = None, strict: bool | None = None,
          guard_nonfinite: bool = False):
    """SDDMM dispatch through the unified registry → blocked-layout values.

    ``impl`` names a registered implementation (``dispatch.impls("sddmm")``:
    blocked / pallas / pallas_balanced / coo / the sharded impls).
    ``interpret=None`` auto-detects (compile on TPU, interpret elsewhere —
    resolved in :mod:`repro.kernels.ops`).  ``split_blk``/``schedule``
    parameterize the schedule-driven ``pallas_balanced`` grid (DESIGN.md
    §11).

    ``precision`` selects the mixed-precision path (DESIGN.md §13:
    ``"fp32"``/``"bf16"``; SDDMM has no int8 level) and is
    capability-checked against the impl's registry entry.

    Compose with SpMM by replacing ``blocked.vals`` (see
    :func:`with_values`).

    Robustness knobs (DESIGN.md §15) mirror :func:`repro.core.spmm.spmm`:
    ``check`` audits ``fmt`` and guards ``q``/``k`` before dispatch,
    ``strict=False`` degrades down the capability ladder on kernel
    failure, ``strict=True`` re-raises, ``guard_nonfinite=True`` re-runs
    a bf16 forward at fp32 on NaN/Inf.  ``strict=None`` (default) keeps
    the plain non-degrading dispatch.
    """
    level = _validate.effective_check(check, fmt.values
                                     if hasattr(fmt, "values")
                                     else fmt.vals, q, k)
    if level != "none":
        _validate.validate(fmt, check=level)
        _validate.guard_operand(q, "q")
        _validate.guard_operand(k, "k")
    kwargs = {"k_blk": k_blk, "interpret": interpret}
    if f_blk is not None:
        kwargs["f_blk"] = f_blk
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
            _dispatch.require("sddmm", impl, precision=precision)
        kwargs["precision"] = precision
    if strict is None and not guard_nonfinite:
        return _dispatch.dispatch("sddmm", impl, fmt, q, k, **kwargs)
    strict_eff = bool(strict) if strict is not None else True
    return _dispatch.robust_dispatch("sddmm", impl, fmt, q, k,
                                     strict=strict_eff,
                                     guard_nonfinite=guard_nonfinite,
                                     **kwargs)


def attention(fmt, q: jax.Array, k: jax.Array, v: jax.Array,
              impl: str = "blocked", *, scale=None, k_blk: int = 8,
              interpret: bool | None = None, split_blk: int | None = None,
              schedule=None, mesh=None, part=None,
              n_batches: int | None = None, n_blk: int | None = None,
              f_blk: int | None = None, precision: str | None = None,
              check: str | None = None, strict: bool | None = None,
              guard_nonfinite: bool = False):
    """Sparse attention dispatch through the unified registry.

    ``impl`` names a registered implementation
    (``dispatch.impls("attention")``: blocked / pallas_fused_attn /
    pallas_staged / pallas_balanced / the sharded impls);
    ``"blocked"`` is the pure-XLA staged pipeline — the terminal rung of
    the fallback ladder.  The robustness knobs (DESIGN.md §15) mirror
    :func:`repro.core.spmm.spmm`: ``check`` audits ``fmt`` and guards
    ``q``/``k``/``v`` before dispatch, ``strict=False`` degrades down the
    capability ladder on kernel failure, ``strict=True`` re-raises, and
    ``strict=None`` (default) keeps the plain non-degrading dispatch.
    """
    level = _validate.effective_check(check, fmt.values
                                     if hasattr(fmt, "values")
                                     else fmt.vals, q, k, v)
    if level != "none":
        _validate.validate(fmt, check=level)
        _validate.guard_operand(q, "q")
        _validate.guard_operand(k, "k")
        _validate.guard_operand(v, "v")
    kwargs = {"k_blk": k_blk, "interpret": interpret}
    if scale is not None:
        kwargs["scale"] = scale
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
    if n_blk is not None:
        kwargs["n_blk"] = n_blk
    if f_blk is not None:
        kwargs["f_blk"] = f_blk
    if precision is not None:
        if strict is None:
            _dispatch.require("attention", impl, precision=precision)
        kwargs["precision"] = precision
    if strict is None and not guard_nonfinite:
        return _dispatch.dispatch("attention", impl, fmt, q, k, v, **kwargs)
    strict_eff = bool(strict) if strict is not None else True
    return _dispatch.robust_dispatch("attention", impl, fmt, q, k, v,
                                     strict=strict_eff,
                                     guard_nonfinite=guard_nonfinite,
                                     **kwargs)


# ---------------------------------------------------------------------------
# Registry adapters — uniform (fmt_or_blocked, q, k, *, k_blk, f_blk,
# interpret) signature.
# ---------------------------------------------------------------------------


def _sddmm_blocked_adapter(fmt, q, k, *, k_blk: int = 8,
                           f_blk: int | None = None,
                           interpret: bool | None = None,
                           precision: str | None = None):
    del f_blk, interpret
    from .quantize import cast_precision

    q, k = cast_precision(precision, q, k)
    blocked = fmt if isinstance(fmt, BlockedMEBCRS) else block_format(fmt, k_blk)
    return _sddmm_blocked_impl(blocked, q, k)


def _sddmm_coo_adapter(fmt, q, k, *, k_blk: int = 8, f_blk: int | None = None,
                       interpret: bool | None = None):
    """Edge-wise oracle via host-side COO conversion → (NNZ,) edge values."""
    del k_blk, f_blk, interpret
    rows, cols, _ = to_coo(fmt)
    return sddmm_coo(jnp.asarray(rows, jnp.int32),
                     jnp.asarray(cols, jnp.int32), q, k)


def _attention_blocked_adapter(fmt, q, k, v, *, scale=None, k_blk: int = 8,
                               interpret: bool | None = None,
                               precision: str | None = None):
    """Pure-XLA staged attention: blocked SDDMM → sparse softmax → blocked
    SpMM.  The terminal rung of the attention fallback ladder — it shares
    no code with the Pallas kernels, so a Mosaic/VMEM failure anywhere in
    the fused paths still leaves a working (if slower) attention.
    """
    import math

    from .quantize import cast_precision
    from .softmax import sparse_softmax

    del interpret
    q, k, v = cast_precision(precision, q, k, v)
    blocked = fmt if isinstance(fmt, BlockedMEBCRS) else block_format(fmt, k_blk)
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])

    def one_head(qh, kh, vh):
        from .spmm import _spmm_blocked_impl

        scores = _sddmm_blocked_impl(blocked, qh, kh)
        probs = sparse_softmax(blocked, scores * scale)
        probed = dataclasses.replace(blocked, vals=probs.astype(vh.dtype),
                                     scales=None)
        return _spmm_blocked_impl(probed, vh, blocked.shape[0])

    if q.ndim == 2:
        return one_head(q, k, v)
    return jnp.stack([one_head(q[i], k[i], v[i]) for i in range(q.shape[0])])


_dispatch.register("sddmm", "blocked", _sddmm_blocked_adapter,
                   differentiable=True, batched=True,
                   precisions=("fp32", "bf16"))
_dispatch.register("sddmm", "coo", _sddmm_coo_adapter)
_dispatch.register("attention", "blocked", _attention_blocked_adapter,
                   differentiable=True, batched=True,
                   precisions=("fp32", "bf16"))


def with_values(blocked: BlockedMEBCRS, new_vals: jax.Array) -> BlockedMEBCRS:
    """Rebind values (e.g. SDDMM output → SpMM input), keeping the pattern.

    Any per-K-block quantization ``scales`` are dropped — they describe the
    *old* values; re-quantize via
    :func:`repro.core.quantize.quantize_format` if needed."""
    return dataclasses.replace(blocked, vals=new_vals, scales=None)
