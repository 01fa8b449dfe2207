"""Jit'd public wrappers around the Pallas kernels.

``interpret`` semantics (shared by every wrapper and by the ``core``
dispatch layer, which threads ``interpret=None`` straight through):

  * ``None`` (default) — compile to Mosaic when the default JAX backend
    is a TPU; interpret mode (the kernel bodies run as plain JAX, for
    correctness validation) when it is the CPU.  Any other backend raises:
    the kernels are never silently interpreted next to an accelerator.
  * ``True`` / ``False`` — force interpret / compiled mode explicitly.

The ``*_tuned`` wrappers consult the :mod:`repro.kernels.autotune`
subsystem to pick ``(k_blk, n_blk)`` per matrix-stats bucket (persistent
on-disk cache), then run the fused gather-free kernels.
"""

from __future__ import annotations

import jax

from repro.core import dispatch as _dispatch

from .attention_pallas import (
    attention_hbm_bytes,
    attention_pallas,
    attention_pallas_balanced,
    attention_pallas_staged,
)
from .sddmm_pallas import (
    sddmm_hbm_bytes,
    sddmm_pallas,
    sddmm_pallas_balanced,
)
from .spmm_pallas import (
    spmm_hbm_bytes,
    spmm_pallas,
    spmm_pallas_balanced,
    spmm_pallas_noncoalesced,
    spmm_pallas_staged,
)

__all__ = [
    "spmm",
    "sddmm",
    "spmm_balanced",
    "sddmm_balanced",
    "attention",
    "attention_balanced",
    "attention_staged",
    "spmm_noncoalesced",
    "spmm_staged",
    "spmm_tuned",
    "spmm_tuned_plan",
    "sddmm_tuned",
    "attention_tuned",
    "spmm_hbm_bytes",
    "sddmm_hbm_bytes",
    "attention_hbm_bytes",
]


def _resolve_interpret(interpret: bool | None) -> bool:
    if interpret is not None:
        return interpret
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(
            f"the Pallas kernels compile for TPU only and interpret on the "
            f"CPU; backend {backend!r} is neither (pass interpret=True to "
            f"run them in interpret mode anyway)")
    return backend == "cpu"


def spmm(blocked, b_dense, *, n_blk: int = 128, interpret: bool | None = None,
         precision: str | None = None):
    """Fused gather-free SpMM (dense rows DMA'd in-kernel)."""
    return spmm_pallas(blocked, b_dense, n_blk=n_blk,
                       interpret=_resolve_interpret(interpret),
                       precision=precision)


def spmm_noncoalesced(blocked, b_dense, *, n_blk: int = 128,
                      interpret: bool | None = None,
                      precision: str | None = None):
    """Serialized-DMA ablation of :func:`spmm` (paper Fig. 15)."""
    return spmm_pallas_noncoalesced(blocked, b_dense, n_blk=n_blk,
                                    interpret=_resolve_interpret(interpret),
                                    precision=precision)


def spmm_staged(blocked, b_dense, *, n_blk: int = 128,
                interpret: bool | None = None,
                precision: str | None = None):
    """Legacy staged-gather SpMM baseline (HBM staging buffer)."""
    return spmm_pallas_staged(blocked, b_dense, n_blk=n_blk,
                              interpret=_resolve_interpret(interpret),
                              precision=precision)


def sddmm(blocked, q, k, *, f_blk: int = 128, interpret: bool | None = None,
          precision: str | None = None):
    """Fused gather-free SDDMM (K rows DMA'd in-kernel)."""
    return sddmm_pallas(blocked, q, k, f_blk=f_blk,
                        interpret=_resolve_interpret(interpret),
                        precision=precision)


def spmm_balanced(blocked, b_dense, *, schedule=None, split_blk: int = 1,
                  n_blk: int = 128, interpret: bool | None = None,
                  precision: str | None = None):
    """Block-parallel load-balanced SpMM (uniform-segment grid, §11)."""
    return spmm_pallas_balanced(blocked, b_dense, schedule=schedule,
                                split_blk=split_blk, n_blk=n_blk,
                                interpret=_resolve_interpret(interpret),
                                precision=precision)


def sddmm_balanced(blocked, q, k, *, schedule=None, split_blk: int = 1,
                   f_blk: int = 128, interpret: bool | None = None,
                   precision: str | None = None):
    """Schedule-driven SDDMM (block-indirect grid, zeros for empty)."""
    return sddmm_pallas_balanced(blocked, q, k, schedule=schedule,
                                 split_blk=split_blk, f_blk=f_blk,
                                 interpret=_resolve_interpret(interpret),
                                 precision=precision)


def attention_balanced(blocked, q, k, v, *, schedule=None,
                       split_blk: int = 1, scale=None,
                       interpret: bool | None = None,
                       precision: str | None = None):
    """Load-balanced fused sparse attention (segment-aware online softmax)."""
    return attention_pallas_balanced(blocked, q, k, v, schedule=schedule,
                                     split_blk=split_blk, scale=scale,
                                     interpret=_resolve_interpret(interpret),
                                     precision=precision)


def attention(blocked, q, k, v, *, scale=None, interpret: bool | None = None,
              precision: str | None = None):
    """Single-pass fused sparse attention (SDDMM→softmax→SpMM megakernel)."""
    return attention_pallas(blocked, q, k, v, scale=scale,
                            interpret=_resolve_interpret(interpret),
                            precision=precision)


def attention_staged(blocked, q, k, v, *, scale=None, n_blk: int = 128,
                     f_blk: int = 128, interpret: bool | None = None,
                     precision: str | None = None):
    """3-dispatch sparse-attention baseline (scores round-trip HBM)."""
    return attention_pallas_staged(blocked, q, k, v, scale=scale,
                                   n_blk=n_blk, f_blk=f_blk,
                                   interpret=_resolve_interpret(interpret),
                                   precision=precision)


def attention_tuned(fmt, q, k, v, *, scale=None, interpret: bool | None = None,
                    cache=None, k_blks=None, precision: str | None = None,
                    precisions=None):
    """Autotuned fused attention: sweep/cache ``(k_blk, split_blk)``, then
    run the winning megakernel (window-parallel or block-parallel).

    ``fmt`` must be the canonical :class:`~repro.core.format.MEBCRS` (the
    tuner re-blocks it per candidate ``k_blk``).  ``precision`` pins one
    precision level; ``precisions`` hands the tuner a set to sweep (the
    winner's dtype rides in ``cfg.precision``).  With neither, operands
    run at their native dtypes, exactly as before the precision axis.
    """
    from repro.core.format import block_format

    from . import autotune

    interpret = _resolve_interpret(interpret)
    kwargs = {} if k_blks is None else {"k_blks": k_blks}
    if precisions is None and precision is not None:
        precisions = (precision,)
    if precisions is not None:
        kwargs["precisions"] = tuple(precisions)
    cfg = autotune.tune_attention(fmt, q, k, v, interpret=interpret,
                                  cache=cache, **kwargs)
    run_prec = cfg.precision if precisions is not None else None
    blocked = block_format(fmt, cfg.k_blk)
    if cfg.split_blk:
        return attention_pallas_balanced(blocked, q, k, v, scale=scale,
                                         split_blk=cfg.split_blk,
                                         interpret=interpret,
                                         precision=run_prec)
    return attention_pallas(blocked, q, k, v, scale=scale,
                            interpret=interpret, precision=run_prec)


def spmm_tuned_plan(fmt, b_dense, *, interpret: bool | None = None,
                    cache=None, k_blks=None, n_blks=None, precisions=None):
    """Resolve the tuned execution plan: ``(cfg, blocked)``.

    This is the single tune → re-block sequence behind :func:`spmm_tuned`;
    benchmarks use it too, so they measure exactly the path users run.
    ``precisions`` (e.g. ``("fp32", "bf16")``) adds the dtype axis to the
    sweep; the winner lands in ``cfg.precision``.
    """
    from repro.core.format import block_format

    from . import autotune

    interpret = _resolve_interpret(interpret)
    kwargs = {}
    if k_blks is not None:
        kwargs["k_blks"] = k_blks
    if n_blks is not None:
        kwargs["n_blks"] = n_blks
    if precisions is not None:
        kwargs["precisions"] = tuple(precisions)
    cfg = autotune.tune_spmm(fmt, b_dense, interpret=interpret, cache=cache,
                             **kwargs)
    return cfg, block_format(fmt, cfg.k_blk)


def spmm_tuned(fmt, b_dense, *, interpret: bool | None = None, cache=None,
               k_blks=None, n_blks=None, precision: str | None = None,
               precisions=None):
    """Autotuned SpMM: sweep/cache ``(k_blk, n_blk, split_blk)``, then run
    the winner — the window-parallel fused kernel, or the block-parallel
    balanced kernel when the sweep preferred a split (skewed matrices;
    the skew bucket keys the cache).

    ``fmt`` must be the canonical :class:`~repro.core.format.MEBCRS` (the
    tuner re-blocks it per candidate ``k_blk``).  A batched ``(H, K, N)``
    operand runs the batched grid — the same path the sweep timed.
    """
    if precisions is None and precision is not None:
        precisions = (precision,)
    cfg, blocked = spmm_tuned_plan(fmt, b_dense, interpret=interpret,
                                   cache=cache, k_blks=k_blks, n_blks=n_blks,
                                   precisions=precisions)
    run_prec = cfg.precision if precisions is not None else None
    if cfg.split_blk:
        return spmm_pallas_balanced(blocked, b_dense,
                                    split_blk=cfg.split_blk, n_blk=cfg.n_blk,
                                    interpret=_resolve_interpret(interpret),
                                    precision=run_prec)
    return spmm_pallas(blocked, b_dense, n_blk=cfg.n_blk,
               interpret=_resolve_interpret(interpret), precision=run_prec)


def sddmm_tuned(fmt, q, k, *, interpret: bool | None = None, cache=None,
                k_blks=None, f_blks=None, precision: str | None = None,
                precisions=None):
    """Autotuned SDDMM: sweep/cache (k_blk, f_blk), then run the fused kernel.

    Because the blocked value layout depends on the tuned ``k_blk``, this
    returns the full :class:`~repro.core.format.BlockedMEBCRS` with the
    sampled scores bound as its values (pattern + scores), ready to feed
    the subsequent SpMM directly.
    """
    from repro.core.format import block_format
    from repro.core.sddmm import with_values

    from . import autotune

    interpret = _resolve_interpret(interpret)
    kwargs = {}
    if k_blks is not None:
        kwargs["k_blks"] = k_blks
    if f_blks is not None:
        kwargs["f_blks"] = f_blks
    if precisions is None and precision is not None:
        precisions = (precision,)
    if precisions is not None:
        kwargs["precisions"] = tuple(precisions)
    cfg = autotune.tune_sddmm(fmt, q, k, interpret=interpret, cache=cache,
                              **kwargs)
    run_prec = cfg.precision if precisions is not None else None
    blocked = block_format(fmt, cfg.k_blk)
    vals = sddmm_pallas(blocked, q, k, f_blk=cfg.n_blk, interpret=interpret,
                        precision=run_prec)
    return with_values(blocked, vals)


# ---------------------------------------------------------------------------
# Registry adapters (repro.core.dispatch) — uniform signatures shared with
# the XLA adapters in core/spmm.py / core/sddmm.py.  The Pallas paths are
# marked ``differentiable``: their gradients run through the custom_vjp
# wrappers in repro.core.autodiff (backward = dispatched sparse ops on the
# cached transposed format), not through tracing the kernel bodies.
# ---------------------------------------------------------------------------


def _ensure_blocked(fmt, k_blk: int):
    from repro.core.format import BlockedMEBCRS, block_format

    return fmt if isinstance(fmt, BlockedMEBCRS) else block_format(fmt, k_blk)


def _require_canonical(fmt, impl: str):
    from repro.core.format import BlockedMEBCRS

    if isinstance(fmt, BlockedMEBCRS):
        raise ValueError(f"impl={impl!r} needs the canonical MEBCRS "
                         "(the autotuner re-blocks it per k_blk candidate)")
    return fmt


def _spmm_pallas_adapter(fmt, b, *, k_blk=8, n_blk=128, interpret=None,
                         precision=None):
    return spmm(_ensure_blocked(fmt, k_blk), b, n_blk=n_blk,
                interpret=interpret, precision=precision)


def _spmm_staged_adapter(fmt, b, *, k_blk=8, n_blk=128, interpret=None,
                         precision=None):
    return spmm_staged(_ensure_blocked(fmt, k_blk), b, n_blk=n_blk,
                       interpret=interpret, precision=precision)


def _spmm_noncoalesced_adapter(fmt, b, *, k_blk=8, n_blk=128, interpret=None,
                               precision=None):
    return spmm_noncoalesced(_ensure_blocked(fmt, k_blk), b, n_blk=n_blk,
                             interpret=interpret, precision=precision)


def _spmm_tuned_adapter(fmt, b, *, k_blk=8, n_blk=None, interpret=None,
                        precision=None):
    del k_blk, n_blk  # the tuner picks both
    return spmm_tuned(_require_canonical(fmt, "pallas_tuned"), b,
                      interpret=interpret, precision=precision)


def _sddmm_pallas_adapter(fmt, q, k, *, k_blk=8, f_blk=128, interpret=None,
                          precision=None):
    return sddmm(_ensure_blocked(fmt, k_blk), q, k, f_blk=f_blk,
                 interpret=interpret, precision=precision)


def _sddmm_tuned_adapter(fmt, q, k, *, k_blk=8, f_blk=None, interpret=None,
                         precision=None):
    del k_blk, f_blk
    return sddmm_tuned(_require_canonical(fmt, "pallas_tuned"), q, k,
                       interpret=interpret, precision=precision)


def _spmm_balanced_adapter(fmt, b, *, k_blk=8, n_blk=128, split_blk=1,
                           schedule=None, interpret=None, precision=None):
    return spmm_balanced(_ensure_blocked(fmt, k_blk), b, schedule=schedule,
                         split_blk=split_blk, n_blk=n_blk,
                         interpret=interpret, precision=precision)


def _sddmm_balanced_adapter(fmt, q, k, *, k_blk=8, f_blk=128, split_blk=1,
                            schedule=None, interpret=None, precision=None):
    return sddmm_balanced(_ensure_blocked(fmt, k_blk), q, k,
                          schedule=schedule, split_blk=split_blk,
                          f_blk=f_blk, interpret=interpret,
                          precision=precision)


def _attention_balanced_adapter(fmt, q, k, v, *, scale=None, k_blk=8,
                                split_blk=1, schedule=None, interpret=None,
                                precision=None):
    return attention_balanced(_ensure_blocked(fmt, k_blk), q, k, v,
                              schedule=schedule, split_blk=split_blk,
                              scale=scale, interpret=interpret,
                              precision=precision)


def _attention_fused_adapter(fmt, q, k, v, *, scale=None, k_blk=8,
                             interpret=None, precision=None):
    return attention(_ensure_blocked(fmt, k_blk), q, k, v, scale=scale,
                     interpret=interpret, precision=precision)


def _attention_staged_adapter(fmt, q, k, v, *, scale=None, k_blk=8,
                              n_blk=128, f_blk=128, interpret=None,
                              precision=None):
    return attention_staged(_ensure_blocked(fmt, k_blk), q, k, v,
                            scale=scale, n_blk=n_blk, f_blk=f_blk,
                            interpret=interpret, precision=precision)


def _attention_tuned_adapter(fmt, q, k, v, *, scale=None, k_blk=8,
                             interpret=None, precision=None):
    del k_blk
    return attention_tuned(_require_canonical(fmt, "pallas_fused_attn_tuned"),
                           q, k, v, scale=scale, interpret=interpret,
                           precision=precision)


_dispatch.register("spmm", "pallas", _spmm_pallas_adapter, differentiable=True,
                   precisions=("fp32", "bf16", "int8"))
# "pallas_batched" is the same kernel as "pallas" (H is a grid axis of
# every kernel); the name stays for callers that ask for a batched impl.
_dispatch.register("spmm", "pallas_batched", _spmm_pallas_adapter,
                   differentiable=True, batched=True,
                   precisions=("fp32", "bf16", "int8"))
# Block-parallel load-balanced impls (DESIGN.md §11): uniform-segment grids
# driven by a host-built Schedule; bitwise-equal to the window-parallel
# kernels, chosen for skewed matrices (autotuner sweeps split_blk per
# skew bucket).  The natively-batched grids serve all head counts.
_dispatch.register("spmm", "pallas_balanced", _spmm_balanced_adapter,
                   differentiable=True, batched=True, load_balanced=True,
                   precisions=("fp32", "bf16", "int8"))
_dispatch.register("sddmm", "pallas_balanced", _sddmm_balanced_adapter,
                   differentiable=True, batched=True, load_balanced=True,
                   precisions=("fp32", "bf16"))
_dispatch.register("attention", "pallas_balanced",
                   _attention_balanced_adapter, differentiable=True,
                   batched=True, load_balanced=True,
                   precisions=("fp32", "bf16"))
_dispatch.register("spmm", "pallas_tuned", _spmm_tuned_adapter,
                   differentiable=True, needs_canonical=True,
                   precisions=("fp32", "bf16", "int8"))
_dispatch.register("spmm", "pallas_staged", _spmm_staged_adapter,
                   precisions=("fp32", "bf16"))
_dispatch.register("spmm", "pallas_noncoalesced", _spmm_noncoalesced_adapter,
                   precisions=("fp32", "bf16", "int8"))
_dispatch.register("sddmm", "pallas", _sddmm_pallas_adapter,
                   differentiable=True, precisions=("fp32", "bf16"))
_dispatch.register("sddmm", "pallas_batched", _sddmm_pallas_adapter,
                   differentiable=True, batched=True,
                   precisions=("fp32", "bf16"))
_dispatch.register("sddmm", "pallas_tuned", _sddmm_tuned_adapter,
                   differentiable=True, needs_canonical=True,
                   returns_format=True, precisions=("fp32", "bf16"))
# Sparse attention is an op in its own right: the fused megakernel never
# materializes scores/probs in HBM (differentiable through
# repro.core.autodiff.attention_ad — FlashAttention-style recompute
# backward); the staged 3-dispatch pipeline is the measured baseline.
_dispatch.register("attention", "pallas_fused_attn", _attention_fused_adapter,
                   differentiable=True, batched=True,
                   precisions=("fp32", "bf16"))
_dispatch.register("attention", "pallas_staged", _attention_staged_adapter,
                   batched=True, precisions=("fp32", "bf16"))
# forward-only: the tuned sweep picks a k_blk independent of any ADPlan
# layout, so there is no custom_vjp rebinding path (train through
# attention_ad / impl="pallas_tuned" instead)
_dispatch.register("attention", "pallas_fused_attn_tuned",
                   _attention_tuned_adapter, batched=True,
                   needs_canonical=True, precisions=("fp32", "bf16"))
