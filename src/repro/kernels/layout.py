"""Kernel-side data layout shared by the Pallas SpMM / SDDMM / attention
kernels (DESIGN.md §3).

Mosaic only DMAs slices whose minor dimension is a whole number of
128-lane tiles, and a single row of a 16-bit array is half of each packed
32-bit word, so it cannot be copied on its own.  Per-nonzero metadata,
on the other hand, must not be scalar-prefetched: SMEM is 1 MiB on v5e,
so a graph with more than ~262k column ids would not fit.  Every kernel
therefore sees its operands in one layout:

  * **32-bit words only.**  Compiled, the kernels take fp32 operands and
    refuse narrow ones (:func:`require_fp32`): bf16 or int8 would have to
    be widened before the launch, which moves more bytes than fp32, not
    fewer.  Interpret mode keeps the narrow precisions (bf16 rounding,
    int8 values with per-block scales) for the numerics tests: the
    wrappers apply them and the kernels run on the widened words, which
    is bitwise an in-kernel upcast.
  * **Lane-dense sparse tiles.**  ``vals``/``mask`` ``(NNZP, V)`` are
    passed transposed, ``(V, L)`` with the vector index on lanes
    (:func:`vectors_on_lanes`); a K-block is a ``(V, k_blk)`` window of
    the 128-lane chunk(s) that hold it, rotated to lane 0 in VMEM.
  * **Dense operands padded to 128-lane column tiles** (:func:`lane_tile`).
  * **Metadata in HBM, read through a small SMEM cache**
    (:class:`SmemRows`): flat int32/fp32 tables stored as ``(R, 128)``
    rows; a kernel keeps a fixed window of rows in SMEM and refills it
    with one synchronous DMA when a lookup leaves the window, so SMEM use
    is fixed whatever the graph size.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128

# Rows of a metadata table one SMEM cache holds (16 x 128 int32 = 8 KiB).
CACHE_ROWS = 16


def require_fp32(interpret: bool, *operands, quantized: bool = False):
    """Refuse narrow operands (or int8-quantized values) of a compiled
    kernel: there is no packed 16-bit or 8-bit layout, so they could only
    run widened to fp32 words."""
    if interpret:
        return
    narrow = sorted({str(x.dtype) for x in operands
                     if x.dtype != jnp.float32} | ({"int8"} if quantized
                                                   else set()))
    if narrow:
        raise ValueError(
            f"compiled Pallas kernels take fp32 operands only, got "
            f"{', '.join(narrow)}: narrow precisions would run widened to "
            f"fp32 words, more traffic than fp32.  Use precision='fp32', "
            f"the 'blocked' impl, or interpret mode")


def lane_tile(requested: int, n: int) -> int:
    """Column tile for an ``n``-wide dense operand: ``requested`` capped at
    ``n`` and rounded up to whole 128-lane tiles.  A narrower operand is
    padded: HBM lays a row out over whole 128-lane tiles, and Mosaic DMAs
    only whole tiles of it."""
    t = min(requested, max(n, 1))
    return -(-t // LANES) * LANES


def sublanes(rows: int) -> int:
    """``rows`` rounded up to whole 8-row sublane tiles (VMEM buffers that
    receive single-row DMAs)."""
    return -(-rows // 8) * 8


def pad_cols(x: jax.Array, tile: int) -> jax.Array:
    """Zero-pad the minor dim of ``x`` to a multiple of ``tile``."""
    n = x.shape[-1]
    n_pad = -(-n // tile) * tile
    if n_pad == n:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, n_pad - n)])


def chunks_per_block(k_blk: int) -> int:
    """128-lane chunks one DMA must cover so that any K-block's vectors
    ``[b*k_blk, (b+1)*k_blk)`` lie inside them, wherever the block starts
    within its first chunk."""
    if LANES % k_blk == 0:
        return 1
    return -(-(LANES - 1 + k_blk) // LANES)


def vectors_on_lanes(x: jax.Array, k_blk: int) -> jax.Array:
    """``(..., NNZP, V)`` → fp32 ``(..., V, L)``: vector index on lanes,
    zero-padded so the chunk DMA of any block stays in bounds."""
    nnzp = x.shape[-2]
    lanes = (-(-nnzp // LANES) + chunks_per_block(k_blk) - 1) * LANES
    xt = jnp.swapaxes(x.astype(jnp.float32), -1, -2)
    return pad_cols(xt, lanes) if lanes > nnzp else xt


def table(x: jax.Array, rows_extra: int = CACHE_ROWS) -> jax.Array:
    """Flat metadata → ``(R, 128)`` rows, zero-padded with ``rows_extra``
    rows so that a cache refill starting at any valid row stays in
    bounds."""
    flat = x.reshape(-1)
    rows = -(-flat.shape[0] // LANES) + rows_extra
    return jnp.pad(flat, (0, rows * LANES - flat.shape[0])).reshape(rows,
                                                                     LANES)


def steps_table(steps: jax.Array) -> jax.Array:
    """Per-grid-step metadata ``(S, 5)`` ``[lo, len, first, last, win]``
    → a table with 8 int32 slots per step (16 steps per row, so a step's
    slots never straddle rows)."""
    return table(jnp.pad(steps.astype(jnp.int32), ((0, 0), (0, 3))))


def window_steps(win_ptr: jax.Array) -> jax.Array:
    """The window-parallel grid as steps: one per window, owning that
    window's whole K-block range."""
    w = win_ptr.shape[0] - 1
    ones = jnp.ones((w,), jnp.int32)
    return jnp.stack([win_ptr[:-1], win_ptr[1:] - win_ptr[:-1], ones, ones,
                      jnp.arange(w, dtype=jnp.int32)], axis=1)


def schedule_steps(seg_win: jax.Array, seg_meta: jax.Array) -> jax.Array:
    """A :class:`~repro.core.format.Schedule`'s segments as steps."""
    return jnp.concatenate([seg_meta, seg_win[:, None]], axis=1)


def smem_cache(dtype=jnp.int32, rows: int = CACHE_ROWS):
    """Scratch shapes of one :class:`SmemRows`: the rows and the base."""
    return [pltpu.SMEM((rows, LANES), dtype), pltpu.SMEM((1,), jnp.int32)]


class SmemRows:
    """Read-through SMEM window over an HBM ``(R, 128)`` metadata table.

    :meth:`ensure` makes flat indices ``[first, last]`` resident, refilling
    ``rows`` rows from the table with one synchronous DMA only on a miss;
    indexing reads a resident flat index.  The kernel calls :meth:`reset`
    on its first grid step (scratch persists across the sequential grid).
    """

    def __init__(self, hbm, buf, base, sem):
        self.hbm, self.buf, self.base, self.sem = hbm, buf, base, sem
        self.rows = buf.shape[0]

    def reset(self):
        self.base[0] = -2 * self.rows - 1

    def ensure(self, first, last):
        r0 = first // LANES
        miss = jnp.logical_or(r0 < self.base[0],
                              last // LANES >= self.base[0] + self.rows)

        @pl.when(miss)
        def _refill():
            cp = pltpu.make_async_copy(self.hbm.at[pl.ds(r0, self.rows), :],
                                       self.buf, self.sem)
            cp.start()
            cp.wait()
            self.base[0] = r0

    def __getitem__(self, flat):
        i = flat - self.base[0] * LANES
        return self.buf[i // LANES, i % LANES]


def block_tile(chunk: jax.Array, start, k_blk: int) -> jax.Array:
    """The ``(V, k_blk)`` tile at lane ``start % 128`` of a loaded
    ``(V, c·128)`` chunk: rotated to lane 0 (exact data movement)."""
    width = chunk.shape[-1]
    off = jax.lax.rem(start, LANES)
    return pltpu.roll(chunk, jax.lax.rem(width - off, width), 1)[:, :k_blk]


def dot(a: jax.Array, b: jax.Array, contract=((1,), (0,))) -> jax.Array:
    """fp32 MXU contraction at full fp32 precision."""
    return jax.lax.dot_general(a, b, dimension_numbers=(contract, ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)
