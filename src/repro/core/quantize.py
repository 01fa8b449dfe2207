"""Shared block quantization + the precision axis of the kernel stack.

One absmax int8 quantizer serves two consumers that previously could not
share code:

  * gradient compression for the DP all-reduce
    (:mod:`repro.train.compression` — flat per-``block`` quantization of
    arbitrary tensors), and
  * per-K-block value scales on :class:`~repro.core.format.BlockedMEBCRS`
    (the tentpole of the mixed-precision kernel path): each K-block's
    ``(K_BLK, V)`` value tile stores int8 with one fp32 scale, and the
    kernels dequantize in-VMEM via the block's scale — the
    dequantization commutes with the contraction
    (``dot(s·q, b) = s·dot(q, b)``), so a single fp32 multiply per block
    restores the magnitude.

The quantizer is jit-able (no host round trip), so the int8 execution
paths can quantize *in trace* — e.g. the autodiff wrappers quantize the
fp32 master values on the forward pass while gradients flow
straight-through to the fp32 masters.

``PRECISIONS`` names the supported precision axis:

  ``fp32``   operands cast to float32 (bitwise-identical to the legacy
             fp32-only kernels for fp32 inputs)
  ``bf16``   dense operands and float sparse values cast to bfloat16
             before the kernel — the in-kernel accumulator stays fp32,
             the epilogue casts back
  ``int8``   sparse values quantized per K-block to int8 + fp32 scale
             (SpMM only — the dense operand rides at bf16); dense-operand
             int8 is not exposed because the per-row DMA granularity of
             the gather-free kernels has no per-block scale to attach

``precision=None`` everywhere means "run at the operand dtypes as given"
— the pre-existing behavior, kept as the default so no caller changes
meaning.

The Pallas kernels have no packed narrow layout: bf16 and int8 run in
interpret mode (rounded, then widened to fp32 words), and compiled kernels
refuse them (:func:`repro.kernels.layout.require_fp32`).  The XLA impls
(``blocked``) implement every level without Pallas.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = [
    "PRECISIONS",
    "precision_dtype",
    "validate_precision",
    "cast_precision",
    "quantize_blocked",
    "dequantize_blocked",
    "quantize_block_values",
    "dequantize_block_values",
    "quantize_format",
]

PRECISIONS: Tuple[str, ...] = ("fp32", "bf16", "int8")

_DENSE_DTYPE = {"fp32": jnp.float32, "bf16": jnp.bfloat16,
                "int8": jnp.bfloat16}


def validate_precision(precision: Optional[str]) -> Optional[str]:
    """``None`` (operand dtypes as given) or one of :data:`PRECISIONS`."""
    if precision is not None and precision not in PRECISIONS:
        raise ValueError(
            f"unknown precision {precision!r}; expected None or one of "
            f"{', '.join(PRECISIONS)}")
    return precision


def precision_dtype(precision: str):
    """Dense-operand dtype of a precision level (int8 rides dense at bf16)."""
    validate_precision(precision)
    if precision is None:
        raise ValueError("precision None has no fixed dtype (operand dtypes "
                         "as given)")
    return _DENSE_DTYPE[precision]


def cast_precision(precision: Optional[str], *operands):
    """Cast dense operands per the precision policy (``None``/fp32/bf16).

    The shared entry for ops whose narrow path is a plain operand cast
    (SDDMM, attention, and the XLA oracles): ``None`` returns the
    operands untouched; int8 is rejected here because it only applies to
    SpMM sparse values (per-K-block scales), not dense operands.
    """
    validate_precision(precision)
    if precision == "int8":
        raise ValueError("int8 applies to SpMM sparse values; SDDMM and "
                         "attention support precision 'fp32'/'bf16'")
    if precision is None:
        return operands
    tgt = jnp.float32 if precision == "fp32" else jnp.bfloat16
    return tuple(x.astype(tgt) for x in operands)


# ----------------------------------------------------------------- int8 ----


def quantize_blocked(x: jax.Array, block: int, scale=None):
    """Per-block int8 quantization of ``x`` (any shape), saturating.

    Flattens, zero-pads to a multiple of ``block``, and quantizes each
    ``block``-element group:

      scale = max(absmax, 1e-12) / 127     (default, per group)
      q     = clip(round(x / scale), -127, 127)  (int8)

    Returns ``(q (NBLK, block) int8, scale (NBLK,) fp32)``.  The absolute
    round-trip error is bounded by ``scale / 2`` per element.

    With the default absmax ``scale`` the clip can never engage (every
    ``|x/scale|`` ≤ 127 by construction).  An explicit ``scale`` — a
    scalar or per-group ``(NBLK,)`` array, the fixed-scale regime of
    calibrated/stale scales shared across steps or replicas — CAN
    overflow the int8 range; the quantizer then **saturates** at ±127
    (never integer wraparound) and records the number of clipped elements
    on the ``int8_clip`` runtime counter
    (:func:`repro.core.metrics.record_counter` — jit-safe, counts land at
    execution time).
    """
    flat = x.reshape(-1)
    pad = (-flat.shape[0]) % block
    xp = jnp.pad(flat, (0, pad)).reshape(-1, block)
    if scale is None:
        sc = jnp.maximum(jnp.max(jnp.abs(xp), axis=-1, keepdims=True),
                         1e-12) / 127.0
        q = jnp.clip(jnp.round(xp / sc), -127, 127).astype(jnp.int8)
    else:
        from .metrics import record_counter

        sc = jnp.asarray(scale, jnp.float32)
        sc = jnp.broadcast_to(sc.reshape(-1, 1) if sc.ndim else sc,
                              (xp.shape[0], 1))
        rounded = jnp.round(xp / sc)
        n_clip = jnp.sum(jnp.abs(rounded) > 127)
        record_counter("int8_clip", n_clip)
        q = jnp.clip(rounded, -127, 127).astype(jnp.int8)
    return q, sc[:, 0].astype(jnp.float32)


def dequantize_blocked(q: jax.Array, scale: jax.Array, shape) -> jax.Array:
    """Inverse of :func:`quantize_blocked`: ``(q, scale) → fp32 of ``shape``."""
    x = (q.astype(jnp.float32) * scale[:, None]).reshape(-1)
    size = 1
    for s in shape:
        size *= s
    return x[:size].reshape(shape)


def quantize_block_values(vals: jax.Array, k_blk: int, scales=None):
    """Quantize blocked ME-BCRS values ``(NNZP, V)`` per K-block.

    Each K-block owns ``k_blk`` consecutive vectors → one quantization
    group of ``k_blk * V`` elements.  Returns ``(q (NNZP, V) int8,
    scales (NB,) fp32)`` with ``NB = NNZP / k_blk`` — the scale array the
    kernels read per block.  Zero-padding vectors inside a K-block keep
    quantizing to exact 0, preserving ME-BCRS's branch-free residue
    handling at int8.  An explicit ``scales`` (scalar or ``(NB,)``) runs
    the saturating fixed-scale path of :func:`quantize_blocked`.
    """
    if vals.ndim != 2:
        raise ValueError(
            "per-K-block quantization expects 2-D values (NNZP, V); "
            f"got shape {vals.shape} — per-head quantized values are not "
            "supported (quantize before stacking heads)")
    q, out_scales = quantize_blocked(vals, k_blk * vals.shape[-1],
                                     scale=scales)
    return q.reshape(vals.shape), out_scales


def dequantize_block_values(q: jax.Array, scales: jax.Array) -> jax.Array:
    """Inverse of :func:`quantize_block_values` → fp32 ``(NNZP, V)``."""
    return dequantize_blocked(q.reshape(scales.shape[0], -1), scales, q.shape)


def quantize_format(blocked):
    """Attach per-K-block int8 values + fp32 scales to a blocked format.

    Returns a :class:`~repro.core.format.BlockedMEBCRS` whose ``vals`` are
    int8 and whose ``scales`` leaf carries the per-block dequantization
    scales; every Pallas SpMM path detects the pair and runs the
    in-VMEM-dequantizing kernel without further annotation.  jit-able.
    """
    import dataclasses

    q, scales = quantize_block_values(blocked.vals, blocked.k_blk)
    return dataclasses.replace(blocked, vals=q, scales=scales)
