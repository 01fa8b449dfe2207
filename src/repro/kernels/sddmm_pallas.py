"""Pallas TPU SDDMM kernel — sampled QKᵀ over the ME-BCRS pattern.

Paper §3.4 adapted to TPU: the output is produced directly in ME-BCRS
vector-major layout (values ``(K_BLK, V)`` per block), so it feeds the
subsequent SpMM with **zero** re-layout — the paper needs Algorithm 1's
per-thread offset arithmetic to split the 8×16 TC block C into SpMM-shaped
sub-blocks; on TPU the block layouts coincide by construction.

Gather-free (DESIGN.md §3): K stays in HBM (``memory_space=ANY``) and the
kernel DMAs the K_BLK rows each sparse block samples — at the feature tile
currently being contracted — into a double-buffered VMEM scratch, at the
column ids read through an SMEM cache of ``cols``.  This removes the ``(NB·K_BLK, F)`` staged
gather the previous pipeline materialized in HBM.  The sparsity mask and
the cast to the input dtype are fused into the final-feature-tile epilogue.

Grid ``(H, chunks, F / F_BLK)`` over chunks of 128 consecutive vectors
(the lane-dense output tile, :mod:`.layout`), the feature dimension
innermost: the chunk's fp32 accumulator stays resident in VMEM scratch
while the QKᵀ contraction walks the feature tiles.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .layout import (CACHE_ROWS, LANES, SmemRows, dot, lane_tile, pad_cols,
                     require_fp32, smem_cache, table, vectors_on_lanes)

__all__ = [
    "sddmm_pallas",
    "sddmm_pallas_balanced",
    "sddmm_hbm_bytes",
    "sddmm_launch_counts",
]


def _cast_precision(precision, *operands):
    """Apply the SDDMM/attention precision policy (DESIGN.md §13): cast the
    dense operands to the target dtype; the in-kernel accumulator stays
    fp32 regardless, and compiled kernels take fp32 only
    (:func:`.layout.require_fp32`).  ``int8`` is not offered here — the
    sampled-QKᵀ operands are dense rows with no per-block scale to attach
    (int8 lives on the SpMM value side)."""
    from repro.core.quantize import cast_precision

    return cast_precision(precision, *operands)


def _sddmm_kernel(chunk0_ref, cols_hbm, wp_hbm, bw_hbm, q_hbm, k_hbm,
                  mask_ref, o_ref, acc_ref, k_buf, q_buf, cols_buf, cols_base,
                  wp_buf, wp_base, bw_buf, bw_base, sems, *, k_blk: int,
                  nf: int, v: int, nnzp: int, num_windows: int, k_rows: int,
                  q_batched: bool, k_batched: bool):
    h = pl.program_id(0)
    c = pl.program_id(1)
    fi = pl.program_id(2)
    qh = h if q_batched else 0      # static: shared operands read slice 0
    kh = h if k_batched else 0
    t0 = (chunk0_ref[0] + c) * LANES
    cols = SmemRows(cols_hbm, cols_buf, cols_base, sems.at[2])
    win_ptr = SmemRows(wp_hbm, wp_buf, wp_base, sems.at[3])
    block_win = SmemRows(bw_hbm, bw_buf, bw_base, sems.at[3])

    @pl.when((h == 0) & (c == 0) & (fi == 0))
    def _reset():
        for cache in (cols, win_ptr, block_win):
            cache.reset()

    cols.ensure(t0, t0 + LANES - 1)

    def row_copy(r, tile_fi, slot, for_wait=False):
        """Single K-row DMA: the chunk's vector ``r`` at feature tile
        ``tile_fi`` (waits need only destination and semaphore)."""
        row = 0 if for_wait else (kh * nf + tile_fi) * k_rows + cols[t0 + r]
        return pltpu.make_async_copy(k_hbm.at[pl.ds(row, 1), :],
                                     k_buf.at[slot, pl.ds(r, 1)],
                                     sems.at[slot])

    def start_rows(tile_fi, slot):
        def body(r, carry):
            row_copy(r, tile_fi, slot).start()
            return carry
        jax.lax.fori_loop(0, LANES, body, 0)

    @pl.when(fi == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        start_rows(0, 0)

    slot = jax.lax.rem(fi, 2)

    @pl.when(fi + 1 < nf)
    def _prefetch_next():
        start_rows(fi + 1, 1 - slot)

    def wait_body(r, carry):
        row_copy(r, 0, slot, for_wait=True).wait()
        return carry

    jax.lax.fori_loop(0, LANES, wait_body, 0)

    # The chunk's vectors belong to the windows [w_lo, w_hi] (blocks of a
    # window are contiguous): contract each window's Q tile against all
    # 128 sampled K rows and keep the lanes that window owns.
    t_first = jnp.minimum(t0, nnzp - 1)
    t_last = jnp.minimum(t0 + LANES - 1, nnzp - 1)
    block_win.ensure(t_first // k_blk, t_last // k_blk)
    w_lo = block_win[t_first // k_blk]
    w_hi = block_win[t_last // k_blk]
    lane_t = t0 + jax.lax.broadcasted_iota(jnp.int32, (v, LANES), 1)
    krows = k_buf[slot]

    def window(w, carry):
        win_ptr.ensure(w, w + 1)
        lo_t = win_ptr[w] * k_blk
        hi_t = win_ptr[w + 1] * k_blk

        @pl.when(lo_t < hi_t)
        def _contract():
            row = ((qh * nf + fi) * num_windows + w) * v
            cp = pltpu.make_async_copy(q_hbm.at[pl.ds(row, v), :], q_buf,
                                       sems.at[4])
            cp.start()
            cp.wait()
            # (V, 128) = Q window tile (V, F_BLK) @ K rows (128, F_BLK)ᵀ
            scores = dot(q_buf[...], krows, ((1,), (1,)))
            owned = (lane_t >= lo_t) & (lane_t < hi_t)
            acc_ref[...] += jnp.where(owned, scores, 0.0)

        return carry

    jax.lax.fori_loop(w_lo, w_hi + 1, window, 0)

    @pl.when(fi == nf - 1)
    def _epilogue():
        # Fused epilogue: sample at the sparsity pattern in-kernel.
        o_ref[...] = (acc_ref[...] * mask_ref[...])[None]


@functools.partial(
    jax.jit, static_argnames=("k_blk", "f_blk", "num_chunks", "interpret",
                              "direction"))
def _sddmm_call(chunk0, cols, win_ptr, block_win, mask, q3, k3, *, k_blk,
                f_blk, num_chunks, interpret, direction=None):
    """Launch :func:`_sddmm_kernel` over ``num_chunks`` chunks of 128
    vectors starting at chunk ``chunk0`` (a traced ``(1,)`` int32).

    ``mask`` ``(NNZP, V)``; ``q3`` ``(1 | H, W·V, F)`` (window rows padded);
    ``k3`` ``(1 | H, Mc, F)``.  Returns fp32 ``(H, V, num_chunks·128)``:
    the sampled scores with the vector index on lanes.

    ``direction`` (``"fwd"``: the kernel samples A's pattern) stamps the
    kernel's ``kernel_metadata`` with it and :func:`sddmm_launch_counts`,
    for a launch whose chunks cover the whole pattern.  Launches over a
    chunk range (a device's share) pass none and carry no metadata.
    """
    require_fp32(interpret, q3, k3)
    nnzp, v = mask.shape
    hq, qrows, _ = q3.shape
    hk, k_rows, _ = k3.shape
    h = max(hq, hk)
    f_blk = lane_tile(f_blk, q3.shape[-1])
    q3 = pad_cols(q3.astype(jnp.float32), f_blk)
    k3 = pad_cols(k3.astype(jnp.float32), f_blk)
    nf = q3.shape[-1] // f_blk
    lanes = (-(-nnzp // LANES) + num_chunks) * LANES
    mask_t = pad_cols(vectors_on_lanes(mask, 1), lanes)
    # Column tiles stacked on rows, so every DMA copies whole rows.
    q_tiles = q3.reshape(hq, qrows, nf, f_blk).swapaxes(1, 2)
    k_tiles = k3.reshape(hk, k_rows, nf, f_blk).swapaxes(1, 2)
    kernel = functools.partial(
        _sddmm_kernel, k_blk=k_blk, nf=nf, v=v, nnzp=nnzp,
        num_windows=qrows // v, k_rows=k_rows, q_batched=hq > 1,
        k_batched=hk > 1)
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    metadata = None
    if direction is not None:
        counts = sddmm_launch_counts(
            nnzp=nnzp, num_windows=qrows // v, num_chunks=num_chunks,
            heads=h, f_pad=q3.shape[-1], f_blk=f_blk, v=v)
        metadata = {"op": "sddmm", "dir": direction,
                    **{k: str(c) for k, c in counts.items()}}
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(h, num_chunks, nf),
        in_specs=[any_spec] * 5 + [
            pl.BlockSpec((v, LANES), lambda hh, c, fi, c0: (0, c0[0] + c))],
        out_specs=pl.BlockSpec((1, v, LANES),
                               lambda hh, c, fi, c0: (hh, 0, c)),
        scratch_shapes=[
            pltpu.VMEM((v, LANES), jnp.float32),         # fp32 accumulator
            pltpu.VMEM((2, LANES, f_blk), jnp.float32),  # sampled K rows
            pltpu.VMEM((v, f_blk), jnp.float32),         # one window of Q
            *smem_cache(), *smem_cache(), *smem_cache(),  # cols, win_ptr, block_win
            pltpu.SemaphoreType.DMA((5,)),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((h, v, num_chunks * LANES),
                                       jnp.float32),
        interpret=interpret,
        metadata=metadata,
    )(chunk0, table(cols, num_chunks + CACHE_ROWS), table(win_ptr),
      table(block_win), q_tiles.reshape(-1, f_blk),
      k_tiles.reshape(-1, f_blk), mask_t)


def chunk_range(num_blocks: int, k_blk: int) -> int:
    """Chunks of 128 vectors that cover ``num_blocks`` contiguous K-blocks
    wherever the first one starts."""
    return -(-(LANES - 1 + num_blocks * k_blk) // LANES)


def _sddmm(blocked, q, k, *, f_blk, interpret, precision, chunk0=None,
           num_chunks=None):
    """Precision policy + launch, back in the blocked value layout:
    ``(NNZP, V)`` or ``(H, NNZP, V)`` in ``q``'s (narrowed) dtype.

    ``chunk0``/``num_chunks`` restrict the launch to a chunk range (a
    device's share of the blocks); vectors outside it come back zero.
    """
    q, k = _cast_precision(precision, q, k)
    qb, kb = q.ndim == 3, k.ndim == 3
    v = blocked.vector_size
    nnzp = blocked.cols.shape[0]
    q3 = q if qb else q[None]
    k3 = k if kb else k[None]
    qpad = jnp.zeros((q3.shape[0], blocked.num_windows * v, q.shape[-1]),
                     q.dtype).at[:, : q3.shape[1], :].set(q3)
    ranged = num_chunks is not None
    if not ranged:
        chunk0, num_chunks = jnp.zeros((1,), jnp.int32), -(-nnzp // LANES)
    out = _sddmm_call(chunk0, blocked.cols, blocked.win_ptr,
                      blocked.block_win, blocked.mask, qpad, k3,
                      k_blk=blocked.k_blk, f_blk=f_blk,
                      num_chunks=num_chunks, interpret=interpret,
                      direction=None if ranged else "fwd")
    out = jnp.swapaxes(out, 1, 2)                        # (H, C·128, V)
    if ranged:
        full = jnp.zeros((out.shape[0], nnzp + num_chunks * LANES, v),
                         out.dtype)
        out = jax.lax.dynamic_update_slice(full, out,
                                           (0, chunk0[0] * LANES, 0))
    out = out[:, :nnzp].astype(q.dtype)
    return out if (qb or kb) else out[0]


def sddmm_pallas(blocked, q: jax.Array, k: jax.Array, *, f_blk: int = 128,
                 interpret: bool = True,
                 precision: str | None = None) -> jax.Array:
    """Gather-free SDDMM over a :class:`BlockedMEBCRS` pattern.

    Returns blocked-layout values ``(NNZP, V)`` in ``q`` dtype, directly
    consumable by :func:`repro.core.sddmm.with_values` + SpMM.  The grid
    runs over chunks of 128 consecutive vectors: each chunk DMAs its
    sampled K rows in-kernel (no staged gather of K) and contracts them
    against the Q tile of every window it touches.  ``precision``
    ("fp32"/"bf16") rounds Q and K before the launch; accumulation stays
    fp32 in-kernel.  bf16 runs in interpret mode only.  ``q``/``k`` may
    be ``(M, F)``/``(Mc, F)`` or carry a leading per-head dim: one
    ``(H, chunks, F/F_BLK)`` grid serves every head, and at least one
    batched operand returns ``(H, NNZP, V)``, bitwise-equal to stacking H
    per-slice launches.
    """
    return _sddmm(blocked, q, k, f_blk=f_blk, interpret=interpret,
                  precision=precision)


def sddmm_pallas_balanced(blocked, q: jax.Array, k: jax.Array, *,
                          schedule=None, split_blk: int = 1,
                          f_blk: int = 128,
                          interpret: bool = True,
                          precision: str | None = None) -> jax.Array:
    """Schedule-driven SDDMM over a :class:`BlockedMEBCRS` pattern.

    SDDMM is block-parallel by nature: the chunk grid gives every step the
    same 128 sampled rows whatever the window skew, so the schedule only
    decides the degenerate case — an all-empty matrix has zero scheduled
    blocks and returns zeros without a kernel launch.  ``schedule`` is
    built from ``blocked`` with ``split_blk`` when omitted (host-side).
    Batching follows :func:`sddmm_pallas`; outputs are
    bitwise-equal to the window-parallel path.
    """
    if schedule is None:
        schedule = blocked.schedule(split_blk)
    if schedule.num_blocks == 0:
        q, k = _cast_precision(precision, q, k)
        qb, kb = q.ndim == 3, k.ndim == 3
        h = q.shape[0] if qb else (k.shape[0] if kb else 1)
        out = jnp.zeros((h,) + blocked.mask.shape, q.dtype)
        return out if (qb or kb) else out[0]
    return sddmm_pallas(blocked, q, k, f_blk=f_blk, interpret=interpret,
                        precision=precision)


def sddmm_hbm_bytes(blocked, f: int, *, f_blk: int = 128,
                    impl: str = "fused", value_bytes: int = 4) -> int:
    """Modeled HBM bytes moved by one SDDMM under ``impl``.

    ``fused``: each sampled K row is DMA'd exactly once (the feature tiles
    partition the row); Q window tiles are streamed per block; mask read
    once; output written once in its final dtype.

    ``staged``: the pre-fusion pipeline additionally read K and wrote /
    re-read the ``(NB·K_BLK, F)`` gather buffer, and wrote an fp32
    intermediate recast in a post-pass.

    These are structural counts of a narrow layout, not of the committed
    kernel (:mod:`.layout`), which moves fp32 words only, pads Q and K to
    128 lanes (4x the bytes at F=32), reads the mask as fp32 and writes
    the scores fp32.  Use them to compare paths, not as the bytes of a
    roofline share.
    """
    v = blocked.vector_size
    nnzp = int(blocked.cols.shape[0])
    nb = nnzp // blocked.k_blk
    f_blk = min(f_blk, max(f, 1))
    f_pad = -(-f // f_blk) * f_blk

    k_pass = nnzp * f_pad * value_bytes          # one sweep over sampled rows
    q_bytes = nb * v * f_pad * value_bytes       # Q window tile per block
    mask_bytes = nnzp * v                        # bool mask
    meta_bytes = 4 * nb + 4 * nnzp               # block_win + cols
    out_bytes = nnzp * v * value_bytes           # output written once

    if impl == "fused":
        return k_pass + q_bytes + mask_bytes + meta_bytes + out_bytes
    if impl == "staged":
        postpass = 2 * nnzp * v * 4
        return 3 * k_pass + q_bytes + mask_bytes + meta_bytes + out_bytes + postpass
    raise ValueError(f"unknown impl {impl!r}")


def sddmm_launch_counts(*, nnzp: int, num_windows: int, num_chunks: int,
                        heads: int, f_pad: int, f_blk: int,
                        v: int = 8) -> dict:
    """What one launch of the committed SDDMM kernel over the whole
    pattern starts, from its static shapes: ``grid_steps``, ``dmas``,
    ``dma_bytes`` and ``mxu_macs``.

    The grid runs ``heads × num_chunks × (f_pad / f_blk)`` steps.  Per
    head and chunk of 128 vectors the kernel DMAs the 128 sampled K rows
    at every feature tile (``f_blk × 4`` bytes each), and the pipeline
    writes back the chunk's ``(v, 128)`` output block and copies in its
    ``(v, 128)`` mask block, which it keeps across heads where the launch
    has one chunk.  Per head and feature tile, each window
    that meets a chunk costs one ``(v, f_blk)`` Q-tile DMA and
    ``v × 128 × f_blk`` MXU multiply-adds.  Which windows meet which
    chunk is data, not a shape: the count takes every window as nonempty
    and no window as ending on a chunk boundary, ``num_windows +
    num_chunks - 1`` meetings.  That bounds the Q tiles from above and
    is exact on such patterns; each empty window and each window end on
    an inner chunk boundary takes one meeting off.  The SMEM metadata
    refills are left out.
    """
    nf = f_pad // f_blk
    tiles = heads * nf * (num_windows + num_chunks - 1)
    chunks = heads * num_chunks
    blocks = chunks + (chunks if num_chunks > 1 else 1)   # outputs, masks
    return {
        "grid_steps": chunks * nf,
        "dmas": chunks * nf * LANES + blocks + tiles,
        "dma_bytes": 4 * (chunks * nf * LANES * f_blk + blocks * v * LANES
                          + tiles * v * f_blk),
        "mxu_macs": tiles * v * LANES * f_blk,
    }
