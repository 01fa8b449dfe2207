"""Pallas TPU SpMM kernels — grouped window-GEMM over blocked ME-BCRS.

This is the TPU realization of FlashSparse's swap-and-transpose SpMM
(paper §3.3), adapted per DESIGN.md §2–§3:

  * The sparse operand is stored **vector-major** (``vals (NNZP, V)`` =
    Aᵀ) and handed to the kernel with the vector index on lanes
    (:mod:`.layout`), so a K-block is a ``(V=8, K_BLK)`` tile and the
    window size V = 8 is the output tile's row count — the granularity
    the paper obtains by swapping MMA operands falls out of the layout.
  * **Gather-free** (DESIGN.md §3): the dense operand B stays in HBM
    (``memory_space=ANY``) and the kernel DMAs exactly the K_BLK dense rows
    each K-block needs into a double-buffered VMEM scratch
    (``pltpu.make_async_copy`` at the column ids read through an SMEM
    cache of ``cols``).
    Every dense row slice is a full-lane contiguous HBM→VMEM copy — the TPU
    analogue of the paper's coalesced thread mapping (§3.3, Fig. 7) — and B
    is read **once** per output column tile, with no ``(NB·K_BLK, N)``
    staging buffer in HBM.  The legacy staged-gather path survives as
    :func:`spmm_pallas_staged` (baseline for the Fig. 12-style traffic
    model, :func:`spmm_hbm_bytes`).
  * The grid runs over **output windows** with an inner loop over that
    window's K-blocks (the ``win_ptr`` ranges, as a step table), so every
    output tile is initialized exactly once, empty windows are written zero
    in-kernel, and the fp32 accumulator is cast to the output dtype in the
    epilogue — no ``_zero_unvisited`` / ``astype`` post-passes.
  * ME-BCRS's padding-free residue handling (§3.5) is unchanged: padding
    vectors inside the last K-block of a window carry zero values, so their
    MXU contribution vanishes — the paper's arithmetic elimination of the
    modulo residue, resolved without branches.

Grid: ``(N / N_BLK, W)`` with the window index innermost.  The accumulator
block is (V=8, N_BLK=128) fp32 — exactly one VREG tile.

:func:`spmm_pallas_balanced` (DESIGN.md §11) replaces the ragged
per-window inner loop with a **block-parallel** grid over uniform
schedule segments — same DMAs, same ascending-block fp32 accumulation
(bitwise-equal), but hub windows no longer serialize one grid cell.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .layout import (CACHE_ROWS, LANES, SmemRows, block_tile, chunks_per_block,
                     dot, lane_tile, pad_cols, require_fp32, schedule_steps,
                     smem_cache, steps_table, sublanes, table,
                     vectors_on_lanes, window_steps)

__all__ = [
    "spmm_pallas",
    "spmm_pallas_balanced",
    "spmm_pallas_noncoalesced",
    "spmm_pallas_staged",
    "spmm_hbm_bytes",
    "spmm_launch_counts",
]


# ---------------------------------------------------------------------------
# Precision policy (DESIGN.md §13).  Shared by every SpMM wrapper below:
# resolve the (vals, scales, quantized, B) quadruple a kernel launch needs.
# ---------------------------------------------------------------------------


def _apply_precision(blocked, b_dense, precision):
    """Apply the precision policy to one SpMM launch.

    Returns ``(vals, scales, quantized, b_dense)``:

      * ``precision=None`` — operands as given; a format carrying int8
        values + per-block ``scales`` selects the quantized kernel path.
      * ``"fp32"`` / ``"bf16"`` — cast the dense operand (and float
        values) to the target dtype; the in-kernel accumulator is fp32
        either way.
      * ``"int8"`` — quantize the values per K-block **in trace**
        (:func:`repro.core.quantize.quantize_block_values`) unless the
        format is already quantized; the dense operand rides at bf16.

    ``scales`` is always a concrete ``(NB,)`` fp32 array (ones when not
    quantized) so every kernel shares one operand signature; the
    static ``quantized`` flag gates the per-block multiply, keeping the
    unquantized path's arithmetic untouched (bitwise-identical).
    Compiled kernels take fp32 only and refuse the narrow results
    (:func:`.layout.require_fp32`); interpret mode runs them widened.
    """
    from repro.core.quantize import quantize_block_values, validate_precision

    validate_precision(precision)
    vals = blocked.vals
    scales = getattr(blocked, "scales", None)
    quantized = scales is not None and vals.dtype == jnp.int8
    if precision == "int8" and not quantized:
        vals, scales = quantize_block_values(vals, blocked.k_blk)
        quantized = True
    if precision in ("bf16", "int8"):
        b_dense = b_dense.astype(jnp.bfloat16)
        if not quantized:
            vals = vals.astype(jnp.bfloat16)
    elif precision == "fp32":
        b_dense = b_dense.astype(jnp.float32)
        if not quantized:
            vals = vals.astype(jnp.float32)
    if scales is None:
        scales = jnp.ones((blocked.num_blocks,), jnp.float32)
    return vals, jnp.asarray(scales, jnp.float32), quantized, b_dense


# ---------------------------------------------------------------------------
# The SpMM kernel.  Grid (H, N / N_BLK, S) over *steps*: step ``s`` owns the
# contiguous K-block range ``[lo, lo + len)`` of output window ``win`` and
# is the ``first`` and/or ``last`` step of that window.  The window-parallel
# grid has one step per window (:func:`layout.window_steps`); the
# load-balanced grid runs a Schedule's segments (DESIGN.md §11), so a hub
# window's work is spread over many steps instead of serializing one.
# Steps of one window are adjacent and ascend over its blocks, so the fp32
# accumulator (VMEM scratch, persistent across the sequential grid) sees
# the same additions in the same order on either grid: the window-parallel,
# batched and balanced paths are bitwise-equal by construction.
#
# Per K-block the step DMAs the block's (V, 128·c) value chunk and its
# K_BLK dense rows of B at the column ids read through the SMEM cache —
# double-buffered, block ``blk + 1`` in flight while ``blk`` contracts on
# the MXU.  Empty windows are zero-length steps: no DMA, just the zero
# store.  Heads share the metadata; a shared operand is a (1, ...) slice
# every head reads, never an H-fold broadcast in HBM.
# ---------------------------------------------------------------------------


def _spmm_kernel(steps_hbm, cols_hbm, scales_hbm, vals_hbm, b_hbm, o_hbm,
                 acc_ref, vals_buf, b_buf, steps_buf, steps_base, cols_buf,
                 cols_base, sc_buf, sc_base, sems, *, k_blk: int, n_blk: int,
                 v: int, k_rows: int, num_windows: int, vals_batched: bool,
                 b_batched: bool,
                 quantized: bool, double_buffer: bool):
    h = pl.program_id(0)
    j = pl.program_id(1)
    s = pl.program_id(2)
    # Operands are 2-D with heads (and B's column tiles) stacked on rows,
    # so every dense-row DMA copies a whole row; a shared operand is one
    # slice every head reads (static choice).
    vrow = h * v if vals_batched else 0
    brow = ((h if b_batched else 0) * pl.num_programs(1) + j) * k_rows
    steps = SmemRows(steps_hbm, steps_buf, steps_base, sems.at[0, 2])
    cols = SmemRows(cols_hbm, cols_buf, cols_base, sems.at[1, 2])
    scales = SmemRows(scales_hbm, sc_buf, sc_base, sems.at[0, 3])

    @pl.when((h == 0) & (j == 0) & (s == 0))
    def _reset():
        for cache in (steps, cols, scales):
            cache.reset()

    steps.ensure(8 * s, 8 * s + 4)
    lo = steps[8 * s]
    hi = lo + steps[8 * s + 1]
    first = steps[8 * s + 2]
    last = steps[8 * s + 3]
    win = steps[8 * s + 4]
    width = vals_buf.shape[-1]

    def copies(blk, slot, for_wait=False):
        """DMA descriptors of K-block ``blk`` into slot ``slot``: the value
        chunk plus K_BLK single dense rows of B.  Waits need only the
        destination and semaphore, so their sources are left at 0."""
        base = blk * k_blk
        chunk = 0 if for_wait else (base // LANES) * LANES
        cps = [pltpu.make_async_copy(
            vals_hbm.at[pl.ds(vrow, v), pl.ds(chunk, width)],
            vals_buf.at[slot], sems.at[slot, 0])]
        for r in range(k_blk):
            row = 0 if for_wait else brow + cols[base + r]
            cps.append(pltpu.make_async_copy(
                b_hbm.at[pl.ds(row, 1), :],
                b_buf.at[slot, pl.ds(r, 1)], sems.at[slot, 1]))
        return cps

    def start(blk, slot):
        cols.ensure(blk * k_blk, blk * k_blk + k_blk - 1)
        for cp in copies(blk, slot):
            cp.start()

    def accumulate(blk, slot):
        # (V, N_BLK) += vals tile (V, K_BLK) @ dense rows (K_BLK, N_BLK)
        contrib = dot(block_tile(vals_buf[slot], blk * k_blk, k_blk),
                      b_buf[slot, pl.ds(0, k_blk), :])
        if quantized:
            # In-VMEM dequantization: the per-block scale commutes with the
            # contraction, so one fp32 multiply restores a whole int8
            # K-block tile (DESIGN.md §13).
            scales.ensure(blk, blk)
            contrib = contrib * scales[blk]
        acc_ref[...] += contrib

    @pl.when(first == 1)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    if double_buffer:
        @pl.when(lo < hi)
        def _warmup():
            start(lo, 0)

        def body(blk, carry):
            slot = jax.lax.rem(blk - lo, 2)

            @pl.when(blk + 1 < hi)
            def _prefetch_next():
                start(blk + 1, 1 - slot)

            for cp in copies(blk, slot, for_wait=True):
                cp.wait()
            accumulate(blk, slot)
            return carry
    else:
        # Serialized variant (the "non-coalesced" ablation): each dense row
        # is fetched and waited on individually, with no overlap between
        # DMA and compute — the structural analogue of the strided-access
        # penalty the paper's direct thread mapping suffers (Fig. 15).
        def body(blk, carry):
            cols.ensure(blk * k_blk, blk * k_blk + k_blk - 1)
            for cp in copies(blk, 0):
                cp.start()
                cp.wait()
            accumulate(blk, 0)
            return carry

    jax.lax.fori_loop(lo, hi, body, 0)

    @pl.when(last == 1)
    def _store():
        cp = pltpu.make_async_copy(
            acc_ref,
            o_hbm.at[pl.ds((h * num_windows + win) * v, v),
                     pl.ds(j * n_blk, n_blk)],
            sems.at[1, 3])
        cp.start()
        cp.wait()


@functools.partial(
    jax.jit,
    static_argnames=("num_windows", "k_blk", "n_blk", "interpret",
                     "quantized", "double_buffer", "direction"),
)
def _spmm_call(steps, cols, scales, vals3, b3, *, num_windows, k_blk, n_blk,
               interpret, quantized=False, double_buffer=True,
               direction=None):
    """Launch :func:`_spmm_kernel`.

    ``steps`` ``(S, 5)`` int32 ``[lo, len, first, last, win]``
    (:func:`layout.window_steps` / :func:`layout.schedule_steps`);
    ``cols`` ``(NNZP,)``; ``scales`` ``(NB,)`` (read only when
    ``quantized``); ``vals3`` ``(1 | H, NNZP, V)``; ``b3``
    ``(1 | H, K, N)``.  Returns fp32 ``(H, num_windows·V, N_pad)``; rows of
    windows no step stores are left unwritten.

    ``direction`` (``"fwd"`` on A, ``"bwd"`` on Aᵀ) stamps the kernel's
    ``kernel_metadata`` with it and :func:`spmm_launch_counts`, which
    hold when the steps run every K-block once and store every window
    once.  Sharded launches, whose steps cover one device's share, pass
    none and carry no metadata.
    """
    require_fp32(interpret, vals3, b3, quantized=quantized)
    hv, _, v = vals3.shape
    hb, k_rows, _ = b3.shape
    h = max(hv, hb)
    n_blk = lane_tile(n_blk, b3.shape[-1])
    b3 = pad_cols(b3.astype(jnp.float32), n_blk)
    n_pad = b3.shape[-1]
    b_tiles = b3.reshape(hb, k_rows, n_pad // n_blk, n_blk).swapaxes(1, 2)
    vals_t = vectors_on_lanes(vals3, k_blk)
    width = chunks_per_block(k_blk) * LANES
    ns = steps.shape[0]
    cols_rows = max(CACHE_ROWS, width // LANES + 1)
    kernel = functools.partial(
        _spmm_kernel, k_blk=k_blk, n_blk=n_blk, v=v, k_rows=k_rows,
        num_windows=num_windows, vals_batched=hv > 1,
        b_batched=hb > 1, quantized=quantized, double_buffer=double_buffer)
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    metadata = None
    if direction is not None:
        if direction not in ("fwd", "bwd"):
            raise ValueError(f"direction must be 'fwd' or 'bwd', got "
                             f"{direction!r}")
        counts = spmm_launch_counts(
            nnzp=cols.shape[0], num_windows=num_windows, num_steps=ns,
            heads=h, n_pad=n_pad, k_blk=k_blk, n_blk=n_blk, v=v)
        metadata = {"op": "spmm", "dir": direction,
                    **{k: str(c) for k, c in counts.items()}}
    return pl.pallas_call(
        kernel,
        grid=(h, n_pad // n_blk, ns),
        in_specs=[any_spec] * 5,
        out_specs=any_spec,
        out_shape=jax.ShapeDtypeStruct((h * num_windows * v, n_pad),
                                       jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((v, n_blk), jnp.float32),           # fp32 accumulator
            pltpu.VMEM((2, v, width), jnp.float32),        # value chunks
            pltpu.VMEM((2, sublanes(k_blk), n_blk), jnp.float32),  # B rows
            *smem_cache(),                                 # step table
            *smem_cache(rows=cols_rows),                    # cols
            *smem_cache(jnp.float32),                      # block scales
            pltpu.SemaphoreType.DMA((2, 4)),
        ],
        interpret=interpret,
        metadata=metadata,
    )(steps_table(steps), table(cols, cols_rows), table(scales),
      vals_t.reshape(hv * v, -1), b_tiles.reshape(-1, n_blk)
      ).reshape(h, num_windows * v, -1)


def _spmm(blocked, b_dense, steps, *, n_blk, interpret, precision,
          num_windows=None, double_buffer=True, direction=None):
    """Precision policy + launch + unpad: ``(M, N)`` or ``(H, M, N)`` in
    the (narrowed) dense operand's dtype."""
    vals, scales, quantized, b_dense = _apply_precision(
        blocked, b_dense, precision)
    vb, bb = vals.ndim == 3, b_dense.ndim == 3
    out = _spmm_call(
        steps, blocked.cols, scales, vals if vb else vals[None],
        b_dense if bb else b_dense[None],
        num_windows=num_windows or blocked.num_windows, k_blk=blocked.k_blk,
        n_blk=n_blk, interpret=interpret, quantized=quantized,
        double_buffer=double_buffer, direction=direction)
    out = out[:, :blocked.shape[0], :b_dense.shape[-1]].astype(b_dense.dtype)
    return out if (vb or bb) else out[0]


def spmm_pallas(blocked, b_dense: jax.Array, *, n_blk: int = 128,
                interpret: bool = True, precision: str | None = None,
                direction: str | None = None) -> jax.Array:
    """Gather-free SpMM over a :class:`BlockedMEBCRS`, one grid step per
    output window.  Returns ``(M, N)`` in ``b`` dtype.  Dense rows are
    DMA'd HBM→VMEM inside the kernel (double-buffered); no staging buffer
    is materialized.  ``precision`` selects the mixed-precision path
    (DESIGN.md §13): ``"bf16"`` rounds the operands to bf16 with fp32
    accumulation; ``"int8"`` additionally quantizes the values per
    K-block, dequantizing in VMEM with the per-block scales.  Both run in
    interpret mode only: compiled, the kernel takes fp32.  Either
    operand may carry a leading head dim: ``blocked.vals`` ``(NNZP, V)``
    or ``(H, NNZP, V)`` (per-head, e.g. attention probabilities),
    ``b_dense`` ``(K, N)`` or ``(H, K, N)``; one ``(H, N/N_BLK, W)`` grid
    serves every head, and at least one batched operand returns
    ``(H, M, N)``, bitwise-equal to stacking H per-slice launches.
    ``"int8"`` requires shared (2-D) pattern values.  ``direction``
    (``"fwd"`` / ``"bwd"``) names the launch on the trace
    (:func:`_spmm_call`)."""
    return _spmm(blocked, b_dense, window_steps(blocked.win_ptr),
                 n_blk=n_blk, interpret=interpret, precision=precision,
                 direction=direction)


def spmm_pallas_noncoalesced(blocked, b_dense: jax.Array, *, n_blk: int = 128,
                             interpret: bool = True,
                             precision: str | None = None) -> jax.Array:
    """Ablation variant (paper Fig. 15): serialized per-row DMA with no
    double buffering.  Bitwise-identical results to :func:`spmm_pallas`
    (same accumulation order); only the copy scheduling differs."""
    return _spmm(blocked, b_dense, window_steps(blocked.win_ptr),
                 n_blk=n_blk, interpret=interpret, precision=precision,
                 double_buffer=False)


def spmm_pallas_balanced(blocked, b_dense: jax.Array, *, schedule=None,
                         split_blk: int = 1, n_blk: int = 128,
                         interpret: bool = True,
                         precision: str | None = None,
                         direction: str | None = None) -> jax.Array:
    """Block-parallel load-balanced SpMM over a :class:`BlockedMEBCRS`.

    ``schedule`` is the precomputed :class:`~repro.core.format.Schedule`;
    omitted, it is built (and memoized) from ``blocked`` with ``split_blk``
    — host-side, so pass it explicitly when calling under ``jit``
    (``ADPlan`` does).  Operand batching follows
    :func:`spmm_pallas`: ``blocked.vals`` may be ``(NNZP, V)`` or
    ``(H, NNZP, V)``, ``b_dense`` ``(K, N)`` or ``(H, K, N)``; unbatched
    in → unbatched out.  Results are **bitwise-equal** to
    :func:`spmm_pallas` (same per-block contraction in the same ascending
    order); only the work-to-grid mapping differs.  ``direction`` as in
    :func:`spmm_pallas`.
    """
    if schedule is None:
        schedule = blocked.schedule(split_blk)
    return _spmm(blocked, b_dense,
                 schedule_steps(schedule.seg_win, schedule.seg_meta),
                 n_blk=n_blk, interpret=interpret, precision=precision,
                 direction=direction)


def spmm_pallas_staged(blocked, b_dense: jax.Array, *, n_blk: int = 128,
                       interpret: bool = True,
                       precision: str | None = None) -> jax.Array:
    """Legacy staged-gather SpMM: materializes ``bgath = B[cols]`` in HBM
    (an ``avg_vectors_per_row ×`` blow-up of B) and then runs the window
    kernel over the staged rows in order.  Kept as the baseline the fused
    path is measured against; bitwise-equal to it.  ``precision``
    supports ``"fp32"``/``"bf16"`` (no ``"int8"``: the baseline predates
    the per-block scales)."""
    from repro.core.quantize import validate_precision

    validate_precision(precision)
    if precision == "int8":
        raise ValueError("spmm_pallas_staged has no int8 path (no per-block "
                         "scales in the staged baseline); use the fused "
                         "or balanced impls")
    vals = blocked.vals
    if precision is not None:
        tgt = jnp.float32 if precision == "fp32" else jnp.bfloat16
        b_dense = b_dense.astype(tgt)
        vals = vals.astype(tgt)
    nnzp = blocked.cols.shape[0]
    bgath = jnp.take(b_dense, blocked.cols, axis=0)  # staged gather in HBM
    staged = dataclasses.replace(
        blocked, vals=vals, cols=jnp.arange(nnzp, dtype=jnp.int32),
        scales=None)
    return _spmm(staged, bgath, window_steps(blocked.win_ptr), n_blk=n_blk,
                 interpret=interpret, precision=None)


# ---------------------------------------------------------------------------
# Modeled HBM traffic (bytes moved per SpMM) — the Fig. 12-style cost model
# extended to the execution paths above, for a narrow layout: dense and
# output elements at ``value_bytes``, values at ``k_blk × V`` per block.
# The committed kernels move more (see the docstring).
# ---------------------------------------------------------------------------


def spmm_hbm_bytes(blocked, n: int, *, n_blk: int = 128,
                   impl: str = "fused", value_bytes: int = 4,
                   vals_value_bytes: int | None = None,
                   schedule=None) -> int:
    """Modeled HBM bytes moved by one SpMM under ``impl``.

    ``value_bytes`` is the element size of the dense operand and output
    (4 for fp32, 2 for bf16 — callers derive it from the dtype, see
    :func:`benchmarks.common.dtype_bytes`); ``vals_value_bytes`` is the
    sparse-value element size when it differs (int8 values: 1, plus the
    4-byte per-K-block scale the quantized kernels read).
    Defaults to ``value_bytes``.

    ``fused`` / ``noncoalesced``: each needed dense row is DMA'd from B
    exactly once per output column tile; vals tiles are re-read per column
    tile; the output is written once in its final dtype.

    ``balanced``: identical data movement to ``fused`` (same DMAs, same
    single output store per window — the schedule only re-maps work to
    grid cells) plus the segment metadata (``seg_win`` +
    ``seg_meta``, 20 bytes per segment).  Pass the ``schedule`` (defaults
    to ``blocked.schedule(1)``).  The *latency* difference the schedule
    exists for is modeled separately — see
    :func:`benchmarks.common.balance_cost`.

    ``staged``: additionally reads B and writes the ``(NB·K_BLK, N)``
    gather buffer, then re-reads it inside the kernel — three full passes
    over the gathered dense rows instead of one.

    These are the paper-style (Fig. 12) structural counts of a narrow
    layout, not of the committed kernels (:mod:`.layout`), which move
    more: each K-block's values arrive as a whole 128-lane chunk
    (``128 / k_blk`` times the value bytes), dense operands are padded to
    128 lanes (:func:`.layout.lane_tile`), and every word is 4 bytes
    whatever the precision.  Use them to compare paths, not as the bytes
    of a roofline share; :func:`spmm_launch_counts` counts what the
    committed kernel starts.
    """
    v = blocked.vector_size
    nnzp = int(blocked.cols.shape[0])
    w = blocked.num_windows
    nb = blocked.num_blocks
    n_blk = min(n_blk, max(n, 1))
    n_pad = -(-n // n_blk) * n_blk
    nj = n_pad // n_blk
    vvb = value_bytes if vals_value_bytes is None else vals_value_bytes

    dense_pass = nnzp * n_pad * value_bytes      # one sweep over needed rows
    vals_bytes = nj * nnzp * v * vvb             # vals re-read per column tile
    meta_bytes = 4 * (w + 1) + 4 * nnzp          # win_ptr/block_win + cols
    if vvb != value_bytes:
        meta_bytes += 4 * nb                     # per-K-block dequant scales
    out_bytes = w * v * n_pad * value_bytes      # output written once

    if impl in ("fused", "noncoalesced"):
        return dense_pass + vals_bytes + meta_bytes + out_bytes
    if impl == "balanced":
        sched = schedule if schedule is not None else blocked.schedule(1)
        sched_bytes = 20 * sched.num_segments   # seg_win (4) + seg_meta (16)
        return dense_pass + vals_bytes + meta_bytes + out_bytes + sched_bytes
    if impl == "staged":
        # gather read + gather write + kernel re-read of bgath, plus the
        # fp32 intermediate re-read/rewritten by the zero/cast post-pass.
        postpass = 2 * w * v * n_pad * 4
        return 3 * dense_pass + vals_bytes + meta_bytes + out_bytes + postpass
    raise ValueError(f"unknown impl {impl!r}")


def spmm_launch_counts(*, nnzp: int, num_windows: int, num_steps: int,
                       heads: int, n_pad: int, k_blk: int, n_blk: int,
                       v: int = 8) -> dict:
    """What one launch of the committed SpMM kernel starts, from its
    static shapes: ``grid_steps``, ``dmas``, ``dma_bytes`` and
    ``mxu_macs``.

    Per head and per ``n_blk`` column tile of the ``n_pad``-wide dense
    operand, each of the ``nnzp / k_blk`` K-blocks starts one value-chunk
    DMA of ``v × width × 4`` bytes (``width`` the 128-lane chunks that
    hold any block, :func:`.layout.chunks_per_block`) and ``k_blk``
    dense-row DMAs of ``n_blk × 4`` bytes, and contracts ``v × k_blk ×
    n_blk`` multiply-adds on the MXU; each of the ``num_windows`` windows
    is stored once, ``v × n_blk × 4`` bytes.  This holds for step tables
    that run every K-block once and store every window once
    (:func:`.layout.window_steps`, a whole Schedule's
    :func:`.layout.schedule_steps`).  The SMEM metadata refills
    (:class:`.layout.SmemRows`) are left out.  :func:`spmm_hbm_bytes`
    remains the narrow-layout model for comparing paths.
    """
    nb = nnzp // k_blk
    tiles = heads * (n_pad // n_blk)
    width = chunks_per_block(k_blk) * LANES
    return {
        "grid_steps": tiles * num_steps,
        "dmas": tiles * (nb * (1 + k_blk) + num_windows),
        "dma_bytes": 4 * tiles * (nb * (v * width + k_blk * n_blk)
                                  + num_windows * v * n_blk),
        "mxu_macs": tiles * nb * v * k_blk * n_blk,
    }
