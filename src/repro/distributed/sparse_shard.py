"""Multi-device sharded sparse ops: ``shard_map`` over partitioned Schedules.

FlashSparse's kernels are single-accelerator; this module is the scale
lever on top (DESIGN.md §12).  The block-parallel :class:`Schedule`
(§11) already expresses the matrix as uniform, independently-executable
segments — exactly the unit to partition across a device mesh, the same
balanced-work-partitioning insight cuTeSpMM / Acc-SpMM apply at the
warp/SM level, lifted to the mesh level:

  * :func:`partition_schedule` splits a Schedule's segment list into
    ``num_devices`` **contiguous ranges**, cut where the cumulative
    per-segment cost (the :func:`segment_costs` model, shared with
    ``benchmarks.common.balance_cost``) crosses each device's fair
    share — so inter-device skew is handled the same way §11 handled
    inter-cell skew.  With ``window_split=True`` a cut may fall inside
    a hub window (each side accumulates a partial sum, recombined by
    the ``psum``); with ``window_split=False`` cuts snap to window
    boundaries (required by the attention megakernel, whose online-
    softmax statistics cannot cross devices).
  * :func:`spmm_sharded` / :func:`sddmm_sharded` /
    :func:`attention_sharded` wrap one **local** ``pallas_balanced``
    launch per device in ``shard_map``: row-segment data parallelism
    over the ``"data"`` axis (sparse pattern replicated, dense operand
    replicated or all-gathered — the GNN-baseline sharding style), and
    head parallelism over the ``"model"`` axis reusing the batched
    ``(H, ...)`` grids (2-D SpMM splits output columns, 2-D SDDMM
    splits the contracted feature dim with a ``psum`` over model).

Why row parallelism needs **no halo exchange**: every output row lives
in exactly one V-row window, and a window's work is exactly its segment
range — so each device's local launch produces a row-disjoint slice of
the output (plus zeros elsewhere, masked NaN-safe), and a single
``psum`` over ``"data"`` reassembles the full output *exactly*
(``x + 0`` is exact in fp32; only windows split across devices change
the fp32 summation grouping).

Everything here is testable on CPU via
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` with
interpret-mode kernels; see ``tests/test_sparse_shard.py``.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import dispatch as _dispatch
from repro.core.format import BlockedMEBCRS, Schedule, block_format

__all__ = [
    "ShardedSchedule",
    "partition_schedule",
    "sharded_schedule",
    "segment_costs",
    "device_balance",
    "batch_costs",
    "spmm_sharded",
    "sddmm_sharded",
    "attention_sharded",
]


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class ShardedSchedule:
    """Per-device partition of a :class:`~repro.core.format.Schedule`.

    All arrays are **stacked per-device** (leading dim ``num_devices``) so
    a ``shard_map`` in_spec of ``P("data")`` hands each device exactly its
    own slice; pad entries keep the stacked shapes uniform:

      seg_win  (D, NSL)    int32  local segments → *global* window id; pad
                                  entries point at the **dummy window**
                                  ``num_windows`` (its rows are sliced off
                                  after the kernel)
      seg_meta (D, NSL, 4) int32  [first block, block count, seg_first,
                                  seg_last] with the first/last flags
                                  **recomputed per device** (a window split
                                  across devices re-inits its accumulator
                                  on each side; the partials recombine in
                                  the psum); pad entries are store-only
                                  zero segments ``[0, 0, 1, 1]``
      blk_id   (D, NBL)    int32  local scheduled K-blocks (global ids),
                                  padded with a repeat of the device's
                                  first block (harmless double store) —
                                  the device's SDDMM block range
      row_own  (D, M)      bool   output rows this device produces (≥ 1
                                  local segment of the row's window);
                                  non-owned rows are zeroed NaN-safe
                                  before the psum
      blk_own  (D, NNZP)   bool   value rows (blocks × K_BLK) this device
                                  produces — the SDDMM ownership mask

    **Segment-batch sub-partition** (the ``pallas_sharded_overlap``
    pipeline, DESIGN.md §14): each device's contiguous segment range is
    further cut into ``n_batches`` contiguous batches by the same
    :func:`segment_costs` model, so the ring can circulate batch ``i``'s
    compact partial while batch ``i+1`` computes:

      bseg_win (D, NB, NSLB)    per-batch segment windows (pad → dummy)
      bseg_meta(D, NB, NSLB, 4) per-batch metadata, first/last flags
                                recomputed **per batch** (a window
                                straddling a batch cut stores one partial
                                per batch; the ring's scatter-adds
                                recombine them, like the psum did across
                                devices)
      brow_idx (D, NB, R)  int32 global output rows of the batch's
                                windows — the compact ring buffer's
                                row map; pad entries are ``m`` (their
                                buffer rows are zero-masked)
      bblk_id  (D, NB, NBLB)    per-batch SDDMM block range
      bval_idx (D, NB, RV) int32 global value rows of the batch's blocks
                                (pad ``nnzp``, zero-masked)

    Aux (static): ``num_devices``, ``num_windows``, ``split_blk``,
    ``window_split``, ``num_blocks``, ``n_batches``.  A pytree — pass it
    through ``jit``/``grad``/``shard_map`` like the format itself.
    """

    seg_win: jax.Array
    seg_meta: jax.Array
    blk_id: jax.Array
    row_own: jax.Array
    blk_own: jax.Array
    num_devices: int
    num_windows: int
    split_blk: int
    window_split: bool
    num_blocks: int
    bseg_win: Optional[jax.Array] = None
    bseg_meta: Optional[jax.Array] = None
    brow_idx: Optional[jax.Array] = None
    bblk_id: Optional[jax.Array] = None
    bval_idx: Optional[jax.Array] = None
    n_batches: int = 1

    def tree_flatten(self):
        leaves = (self.seg_win, self.seg_meta, self.blk_id, self.row_own,
                  self.blk_own, self.bseg_win, self.bseg_meta, self.brow_idx,
                  self.bblk_id, self.bval_idx)
        aux = (self.num_devices, self.num_windows, self.split_blk,
               self.window_split, self.num_blocks, self.n_batches)
        return leaves, aux

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        (sw, sm, bi, ro, bo, bsw, bsm, bri, bbi, bvi) = leaves
        (d, w, sb, ws, nb, nbat) = aux
        return cls(seg_win=sw, seg_meta=sm, blk_id=bi, row_own=ro,
                   blk_own=bo, num_devices=d, num_windows=w, split_blk=sb,
                   window_split=ws, num_blocks=nb, bseg_win=bsw,
                   bseg_meta=bsm, brow_idx=bri, bblk_id=bbi, bval_idx=bvi,
                   n_batches=nbat)


# Fixed per-grid-cell issue overhead of the §11 cost model (bytes-
# equivalent).  benchmarks.common.balance_cost consumes segment_costs
# below for its balanced-cell vector, so the partitioner and the bench
# share one implementation (documented in docs/benchmarks.md).
_FIXED_CELL_BYTES = 512


def segment_costs(blocked: BlockedMEBCRS, schedule: Schedule, *,
                  n_blk: int = 128, value_bytes: int = 4,
                  fixed_cell_bytes: int = _FIXED_CELL_BYTES) -> np.ndarray:
    """Per-segment cost (bytes-equivalent) under the §11 cell model.

    One grid cell per segment: a fixed issue overhead, the DMA bytes of
    its K-blocks (vals tile + the K_BLK dense rows), and the output-tile
    store charged to the window's final segment.  This is the single
    source of the ``impl="balanced"`` cell vector —
    ``benchmarks.common.balance_cost`` calls it — so the partitioner
    balances exactly the quantity the benchmarks report.
    """
    v = blocked.vector_size
    k_blk = blocked.k_blk
    meta = np.asarray(schedule.seg_meta).astype(np.int64)
    block_bytes = k_blk * (v + n_blk) * value_bytes
    store_bytes = v * n_blk * value_bytes
    return (fixed_cell_bytes + meta[:, 1] * block_bytes
            + meta[:, 3] * store_bytes).astype(np.float64)


def _allowed_cuts(seg_win: np.ndarray, window_split: bool) -> np.ndarray:
    """Legal cut positions (segment indices incl. 0 and NS): everywhere,
    or window starts only when ``window_split`` is off."""
    ns = seg_win.size
    if window_split:
        return np.arange(ns + 1)
    starts = np.flatnonzero(np.diff(seg_win) != 0) + 1
    return np.concatenate([[0], starts, [ns]])


def _cut_points(costs: np.ndarray, num_devices: int,
                allowed: np.ndarray) -> np.ndarray:
    """Contiguous cuts (D+1 monotone segment indices) balancing ``costs``.

    Greedy fair-share: cut ``i`` lands on the ``allowed`` boundary whose
    cost prefix is nearest ``i/D`` of the total.  ``allowed`` must contain
    0 and ``len(costs)``.
    """
    ns = costs.size
    prefix = np.concatenate([[0.0], np.cumsum(costs)])
    total = prefix[-1]
    cuts = [0]
    for i in range(1, num_devices):
        target = total * i / num_devices
        pa = prefix[allowed]
        j = int(np.searchsorted(pa, target))
        cands = [c for c in (j - 1, j) if 0 <= c < allowed.size]
        best = min(cands, key=lambda c: abs(pa[c] - target))
        cuts.append(max(int(allowed[best]), cuts[-1]))
    cuts.append(ns)
    return np.asarray(cuts, np.int64)


def _run_flags(seg_win: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Window-run first/last flags recomputed for a local segment range."""
    n_loc = seg_win.size
    run_first = np.ones(n_loc, bool)
    run_first[1:] = seg_win[1:] != seg_win[:-1]
    run_last = np.ones(n_loc, bool)
    run_last[:-1] = seg_win[:-1] != seg_win[1:]
    return run_first, run_last


def _range_blocks(seg_meta: np.ndarray) -> Tuple[int, int]:
    """[blk_lo, blk_hi) global block range of a local segment slice."""
    lens = seg_meta[:, 1]
    real = lens > 0
    if real.any():
        return (int(seg_meta[:, 0][real].min()),
                int((seg_meta[:, 0] + lens)[real].max()))
    return 0, 0


def _range_rows(seg_win: np.ndarray, v: int, m: int) -> np.ndarray:
    """Global output rows (< m) of the windows a segment slice touches."""
    owned = np.unique(seg_win)
    rows = (owned[:, None] * v + np.arange(v)).reshape(-1)
    return rows[rows < m]


def partition_schedule(blocked: BlockedMEBCRS,
                       schedule: Optional[Schedule] = None,
                       num_devices: int = 1, *, split_blk: int = 1,
                       window_split: bool = True,
                       n_blk: int = 128,
                       n_batches: int = 1,
                       check: Optional[str] = None) -> ShardedSchedule:
    """Split a Schedule into ``num_devices`` balanced contiguous ranges.

    Host-side numpy like :func:`~repro.core.format.build_schedule` — call
    outside ``jit`` (or let :func:`sharded_schedule` memoize it on the
    blocked instance).  ``window_split=False`` restricts cuts to window
    boundaries — mandatory for :func:`attention_sharded` (online-softmax
    statistics cannot cross devices), optional elsewhere (hub windows
    larger than a device's fair share then pin the balance).

    ``n_batches`` sub-splits each device's range into that many
    contiguous *segment batches* by the same cost model (the
    ``pallas_sharded_overlap`` pipeline unit; batch cuts inherit the
    ``window_split`` rule, so attention batches stay window-aligned).
    When devices (or batches) outnumber non-empty segments, the surplus
    ranges come out **store-only**: their slots hold only dummy-window /
    zero-length pad entries, so the local launch stores zeros and the
    reassembly (psum or ring) is a no-op for them — no failure, no
    silent replication of real work.
    """
    from repro.core import validate as _validate

    level = _validate.resolve_check(check)
    if num_devices < 1:
        raise ValueError(f"num_devices must be >= 1, got {num_devices}")
    if n_batches < 1:
        raise ValueError(f"n_batches must be >= 1, got {n_batches}")
    if schedule is None:
        schedule = blocked.schedule(split_blk)
    _validate.validate_blocked(blocked, check=level)
    _validate.validate_schedule(schedule, blocked=blocked, check=level)
    w = blocked.num_windows
    v = blocked.vector_size
    k_blk = blocked.k_blk
    m = blocked.shape[0]
    nnzp = int(np.asarray(blocked.cols).shape[0])
    seg_win = np.asarray(schedule.seg_win).astype(np.int64)
    seg_meta = np.asarray(schedule.seg_meta).astype(np.int64)
    d = num_devices
    nb = n_batches

    costs = segment_costs(blocked, schedule, n_blk=n_blk)
    cuts = _cut_points(costs, d, _allowed_cuts(seg_win, window_split))

    counts = np.diff(cuts)
    nsl = max(int(counts.max()) if counts.size else 0, 1)
    sw = np.full((d, nsl), w, np.int32)               # pad → dummy window
    sm = np.zeros((d, nsl, 4), np.int32)
    sm[:, :, 2] = 1                                    # pad: store-only zero
    sm[:, :, 3] = 1
    row_own = np.zeros((d, m), bool)
    blk_own = np.zeros((d, nnzp), bool)
    blk_ranges = []
    # Per-(device, batch) segment sub-ranges: same greedy fair-share cut
    # applied to the device's own cost slice (shared model — the batches
    # the overlap pipeline executes are the batches the makespan model
    # prices).
    bat_ranges = [[None] * nb for _ in range(d)]
    for dev in range(d):
        lo, hi = int(cuts[dev]), int(cuts[dev + 1])
        n_loc = hi - lo
        if n_loc:
            sw[dev, :n_loc] = seg_win[lo:hi]
            sm[dev, :n_loc] = seg_meta[lo:hi]
            # Recompute window-run boundaries locally: a straddled
            # window's first local segment must re-init the accumulator
            # and its last must store the partial (psum recombines).
            run_first, run_last = _run_flags(seg_win[lo:hi])
            sm[dev, :n_loc, 2] = run_first.astype(np.int32)
            sm[dev, :n_loc, 3] = run_last.astype(np.int32)
            row_own[dev, _range_rows(seg_win[lo:hi], v, m)] = True
            blk_lo, blk_hi = _range_blocks(seg_meta[lo:hi])
        else:
            blk_lo = blk_hi = 0
        blk_ranges.append((blk_lo, blk_hi))
        blk_own[dev, blk_lo * k_blk: blk_hi * k_blk] = True
        bcuts = lo + _cut_points(
            costs[lo:hi], nb, _allowed_cuts(seg_win[lo:hi], window_split))
        for b in range(nb):
            bat_ranges[dev][b] = (int(bcuts[b]), int(bcuts[b + 1]))

    nbl = max((hi - lo for lo, hi in blk_ranges), default=0)

    def block_grid(shape, ranges):
        bid = np.zeros(shape, np.int32)
        if shape[-1] == 0:                  # no scheduled blocks at all
            return bid
        flat_id = bid.reshape(-1, shape[-1])
        for i, (lo, hi) in enumerate(ranges):
            n_loc = hi - lo
            flat_id[i, :] = lo if n_loc else 0   # pad: recompute own block
            if n_loc:
                flat_id[i, :n_loc] = np.arange(lo, hi, dtype=np.int32)
        return bid

    bid = block_grid((d, nbl), blk_ranges)

    # ---- segment-batch arrays ------------------------------------------
    bat_counts = np.asarray([[hi - lo for lo, hi in row] for row in bat_ranges],
                            np.int64)
    nslb = max(int(bat_counts.max()) if bat_counts.size else 0, 1)
    bsw = np.full((d, nb, nslb), w, np.int32)
    bsm = np.zeros((d, nb, nslb, 4), np.int32)
    bsm[:, :, :, 2] = 1
    bsm[:, :, :, 3] = 1
    bat_blk_ranges = []
    row_lists = []
    for dev in range(d):
        for b in range(nb):
            lo, hi = bat_ranges[dev][b]
            n_loc = hi - lo
            if n_loc:
                bsw[dev, b, :n_loc] = seg_win[lo:hi]
                bsm[dev, b, :n_loc] = seg_meta[lo:hi]
                run_first, run_last = _run_flags(seg_win[lo:hi])
                bsm[dev, b, :n_loc, 2] = run_first.astype(np.int32)
                bsm[dev, b, :n_loc, 3] = run_last.astype(np.int32)
                rows = _range_rows(seg_win[lo:hi], v, m)
                blk_lo, blk_hi = _range_blocks(seg_meta[lo:hi])
            else:
                rows = np.zeros(0, np.int64)
                blk_lo = blk_hi = 0
            row_lists.append(rows)
            bat_blk_ranges.append((blk_lo, blk_hi))

    r_max = max((r.size for r in row_lists), default=0) or 1
    bri = np.full((d, nb, r_max), m, np.int32)        # pad → zero-masked
    flat_bri = bri.reshape(d * nb, r_max)
    for i, rows in enumerate(row_lists):
        flat_bri[i, :rows.size] = rows
    nblb = max((hi - lo for lo, hi in bat_blk_ranges), default=0) or 1
    bbi = block_grid((d, nb, nblb), bat_blk_ranges)
    rv_max = max((hi - lo for lo, hi in bat_blk_ranges), default=0) * k_blk or 1
    bvi = np.full((d, nb, rv_max), nnzp, np.int32)    # pad → zero-masked
    flat_bvi = bvi.reshape(d * nb, rv_max)
    for i, (lo, hi) in enumerate(bat_blk_ranges):
        n_v = (hi - lo) * k_blk
        flat_bvi[i, :n_v] = np.arange(lo * k_blk, hi * k_blk, dtype=np.int32)

    return _validate.validate_sharded(ShardedSchedule(
        seg_win=jnp.asarray(sw), seg_meta=jnp.asarray(sm),
        blk_id=jnp.asarray(bid), row_own=jnp.asarray(row_own), blk_own=jnp.asarray(blk_own),
        num_devices=d, num_windows=w, split_blk=schedule.split_blk,
        window_split=window_split, num_blocks=schedule.num_blocks,
        bseg_win=jnp.asarray(bsw), bseg_meta=jnp.asarray(bsm),
        brow_idx=jnp.asarray(bri), bblk_id=jnp.asarray(bbi),
        bval_idx=jnp.asarray(bvi),
        n_batches=nb), blocked=blocked, check=level)


def sharded_schedule(blocked: BlockedMEBCRS, num_devices: int, *,
                     split_blk: int = 1, window_split: bool = True,
                     n_blk: int = 128, n_batches: int = 1,
                     schedule: Optional[Schedule] = None) -> ShardedSchedule:
    """Memoized :func:`partition_schedule` (per ``(split_blk, D,
    window_split, n_blk)``), host-side like ``BlockedMEBCRS.schedule``.

    ``n_blk`` is the dense-tile width the cost model charges per cell —
    pass the tile the kernel will actually run so the cuts balance the
    executed cost.  An explicitly supplied ``schedule`` bypasses the
    memo entirely (the cache key cannot see it, and a custom schedule
    must never be served a partition built from the default one, or
    vice versa).
    """
    if schedule is not None:
        return partition_schedule(blocked, schedule, num_devices,
                                  split_blk=split_blk,
                                  window_split=window_split, n_blk=n_blk,
                                  n_batches=n_batches)
    memo = getattr(blocked, "_shard_plans", None)
    if memo is None:
        memo = {}
        object.__setattr__(blocked, "_shard_plans", memo)
    key = (split_blk, num_devices, window_split, n_blk, n_batches)
    if key not in memo:
        memo[key] = partition_schedule(blocked, None, num_devices,
                                       split_blk=split_blk,
                                       window_split=window_split,
                                       n_blk=n_blk, n_batches=n_batches)
    return memo[key]


def device_balance(blocked: BlockedMEBCRS, num_devices: int, *,
                   schedule: Optional[Schedule] = None, split_blk: int = 1,
                   window_split: bool = True, n_blk: int = 128) -> dict:
    """Per-device cost totals of the partition the sharded ops would run.

    Returns ``{"costs": [per-device cost], "max_over_mean": float}`` —
    the inter-device skew statistic BENCH_spmm.json records and CI floors
    at ≤ 1.25 on the skewed suite at 8 devices (the partitioner must
    *balance*, not just split).
    """
    if schedule is None:
        schedule = blocked.schedule(split_blk)
    costs = segment_costs(blocked, schedule, n_blk=n_blk)
    seg_win = np.asarray(schedule.seg_win)
    cuts = _cut_points(costs, num_devices,
                       _allowed_cuts(seg_win, window_split))
    per_dev = [float(costs[cuts[i]:cuts[i + 1]].sum())
               for i in range(num_devices)]
    mean = float(np.mean(per_dev)) if per_dev else 0.0
    return {"costs": per_dev,
            "max_over_mean": (max(per_dev) / mean) if mean > 0 else 1.0}


def batch_costs(blocked: BlockedMEBCRS, num_devices: int, n_batches: int, *,
                schedule: Optional[Schedule] = None, split_blk: int = 1,
                window_split: bool = True, n_blk: int = 128) -> dict:
    """Per-(device, batch) cost/row statistics of the overlap partition.

    Reapplies exactly the cuts :func:`partition_schedule` uses (device
    cuts, then per-device batch sub-cuts, same :func:`segment_costs`
    model) and returns host-side numpy:

      ``costs``  (D, NB) float  bytes-equivalent compute cost per batch
      ``rows``   (D, NB) int    output rows the batch's windows own —
                                what the ring buffer for that batch
                                carries (``benchmarks.common.
                                overlap_makespan`` prices the hops from
                                this)

    Shared-model invariant: ``costs.sum(axis=1)`` equals
    :func:`device_balance`'s per-device totals.
    """
    if schedule is None:
        schedule = blocked.schedule(split_blk)
    costs = segment_costs(blocked, schedule, n_blk=n_blk)
    seg_win = np.asarray(schedule.seg_win)
    v = blocked.vector_size
    m = blocked.shape[0]
    cuts = _cut_points(costs, num_devices,
                       _allowed_cuts(seg_win, window_split))
    c = np.zeros((num_devices, n_batches), np.float64)
    r = np.zeros((num_devices, n_batches), np.int64)
    for dev in range(num_devices):
        lo, hi = int(cuts[dev]), int(cuts[dev + 1])
        bcuts = lo + _cut_points(
            costs[lo:hi], n_batches,
            _allowed_cuts(seg_win[lo:hi], window_split))
        for b in range(n_batches):
            blo, bhi = int(bcuts[b]), int(bcuts[b + 1])
            c[dev, b] = float(costs[blo:bhi].sum())
            if bhi > blo:
                r[dev, b] = _range_rows(seg_win[blo:bhi], v, m).size
    return {"costs": c, "rows": r}


# ---------------------------------------------------------------------------
# shard_map entry points
# ---------------------------------------------------------------------------


def _resolve_mesh(mesh: Optional[Mesh]) -> Mesh:
    if mesh is None:
        from .ctx import current_mesh

        mesh = current_mesh()
    if mesh is None:
        raise ValueError(
            "sharded sparse ops need a mesh with a 'data' axis: pass "
            "mesh=..., enter `with activation_mesh(mesh):`, or build one "
            "with repro.launch.mesh.make_host_mesh(data, model)")
    if "data" not in mesh.shape:
        raise ValueError(f"mesh must have a 'data' axis, got {mesh.axis_names}")
    return mesh


def _interp(interpret):
    from repro.kernels.ops import _resolve_interpret

    return _resolve_interpret(interpret)


def _model_axis(mesh: Mesh) -> Tuple[Optional[str], int]:
    if "model" in mesh.shape and mesh.shape["model"] > 1:
        return "model", mesh.shape["model"]
    return None, 1


def _check_part(part: ShardedSchedule, mesh: Mesh, *, window_aligned=False):
    ndev = mesh.shape["data"]
    if part.num_devices != ndev:
        raise ValueError(f"partition built for {part.num_devices} devices, "
                         f"mesh 'data' axis has {ndev}")
    if window_aligned and part.window_split:
        raise ValueError("attention_sharded needs a window-aligned "
                         "partition (window_split=False): online-softmax "
                         "statistics cannot cross devices")


def spmm_sharded(fmt, b: jax.Array, *, mesh: Optional[Mesh] = None,
                 part: Optional[ShardedSchedule] = None,
                 schedule: Optional[Schedule] = None, split_blk: int = 1,
                 k_blk: int = 8, n_blk: int = 128,
                 interpret: Optional[bool] = None,
                 precision: Optional[str] = None) -> jax.Array:
    """Multi-device SpMM: one local balanced launch per device + psum.

    ``fmt``: canonical :class:`~repro.core.format.MEBCRS` or
    :class:`BlockedMEBCRS` (values may carry a leading head dim);
    ``b``: ``(K, N)`` or ``(H, K, N)``.  Row segments are partitioned
    over the ``"data"`` axis by :func:`partition_schedule`; the
    ``"model"`` axis carries heads (3-D operands) or output columns
    (2-D) when divisible, degrading to replication otherwise.  The
    output is replicated over ``"data"`` (the psum *is* the row
    all-gather a GNN layer needs before the next aggregation).  Exact
    fp32 parity with the single-device ``pallas_balanced`` path, up to
    summation grouping on windows split across devices.  ``precision``
    follows the kernel-wide policy (DESIGN.md §13): ``"bf16"`` narrows
    the operands before the shard_map, ``"int8"`` quantizes the sparse
    values per K-block (scales replicate — a few bytes per block).
    """
    from repro.kernels.layout import schedule_steps
    from repro.kernels.spmm_pallas import _apply_precision, _spmm_call

    blocked = fmt if isinstance(fmt, BlockedMEBCRS) else block_format(fmt, k_blk)
    mesh = _resolve_mesh(mesh)
    if part is None:
        part = sharded_schedule(blocked, mesh.shape["data"],
                                split_blk=split_blk, n_blk=n_blk,
                                schedule=schedule)
    _check_part(part, mesh)
    interpret = _interp(interpret)

    vals, scales, quantized, b = _apply_precision(blocked, b, precision)
    vb, bb = vals.ndim == 3, b.ndim == 3
    h = vals.shape[0] if vb else (b.shape[0] if bb else 1)
    m, _ = blocked.shape
    n = b.shape[-1]
    w = part.num_windows
    model_ax, tp = _model_axis(mesh)
    if model_ax and (vb or bb) and h % tp == 0:
        mode = "heads"
    elif model_ax and not (vb or bb) and n % tp == 0:
        mode = "cols"
    else:
        mode, model_ax = "none", None

    def local(sw, sm, own, vals_l, b_l):
        sw, sm, own = sw[0], sm[0], own[0]
        b3 = b_l if bb else b_l[None]
        out = _spmm_call(
            schedule_steps(sw, sm), blocked.cols, scales,
            vals_l if vb else vals_l[None], b3, num_windows=w + 1,
            k_blk=blocked.k_blk, n_blk=n_blk, interpret=interpret,
            quantized=quantized)
        out = out[:, :m, :b3.shape[-1]].astype(b3.dtype)
        out = jnp.where(own[None, :, None], out, 0.0)   # NaN-safe zero fill
        out = jax.lax.psum(out, "data")
        return out if (vb or bb) else out[0]

    b_spec = (P(model_ax) if (mode == "heads" and bb)
              else (P(None, model_ax) if mode == "cols" else P()))
    v_spec = P(model_ax) if (mode == "heads" and vb) else P()
    if vb or bb:
        out_spec = P(model_ax) if mode == "heads" else P()
    else:
        out_spec = P(None, model_ax) if mode == "cols" else P()
    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(P("data"), P("data"), P("data"), v_spec,
                                 b_spec),
                       out_specs=out_spec, check_vma=False)
    return fn(part.seg_win, part.seg_meta, part.row_own, vals, b)


def sddmm_sharded(fmt, q: jax.Array, k: jax.Array, *,
                  mesh: Optional[Mesh] = None,
                  part: Optional[ShardedSchedule] = None,
                  schedule: Optional[Schedule] = None, split_blk: int = 1,
                  k_blk: int = 8, f_blk: int = 128,
                  interpret: Optional[bool] = None,
                  precision: Optional[str] = None) -> jax.Array:
    """Multi-device SDDMM → blocked-layout values ``(NNZP, V)``.

    K-blocks are uniquely owned by segments, so the block-indirect grid
    partitions with **no** cross-device accumulation over ``"data"``
    (each block's value is written by exactly one device; the psum only
    reassembles).  Heads take the ``"model"`` axis for 3-D operands; for
    2-D operands the *contracted* feature dim F splits over ``"model"``
    — each device contracts its F slice and the psum over both axes sums
    the partial products (TP-style).  Degrades to replication when the
    dim does not divide.
    """
    from repro.kernels.layout import LANES
    from repro.kernels.sddmm_pallas import _cast_precision, _sddmm, chunk_range

    q, k = _cast_precision(precision, q, k)
    blocked = fmt if isinstance(fmt, BlockedMEBCRS) else block_format(fmt, k_blk)
    mesh = _resolve_mesh(mesh)
    if part is None:
        part = sharded_schedule(blocked, mesh.shape["data"],
                                split_blk=split_blk, n_blk=f_blk,
                                schedule=schedule)
    _check_part(part, mesh)
    interpret = _interp(interpret)

    qb, kb = q.ndim == 3, k.ndim == 3
    h = q.shape[0] if qb else (k.shape[0] if kb else 1)
    v = blocked.vector_size
    nb = blocked.num_blocks
    f = q.shape[-1]
    if part.num_blocks == 0:                     # all-empty pattern
        out = jnp.zeros((h, nb * blocked.k_blk, v), q.dtype)
        return out if (qb or kb) else out[0]
    num_chunks = chunk_range(part.blk_id.shape[-1], blocked.k_blk)
    model_ax, tp = _model_axis(mesh)
    if model_ax and (qb or kb) and h % tp == 0:
        mode = "heads"
    elif model_ax and not (qb or kb) and f % tp == 0:
        mode = "feat"
    else:
        mode, model_ax = "none", None
    psum_axes = ("data", model_ax) if mode == "feat" else ("data",)

    def local(bid, own, q_l, k_l):
        bid, own = bid[0], own[0]
        # The device's blocks are the contiguous range starting at bid[0]:
        # launch over the 128-vector chunks that cover it.
        out = _sddmm(blocked, q_l, k_l, f_blk=f_blk, interpret=interpret,
                     precision=None, chunk0=bid[:1] * blocked.k_blk // LANES,
                     num_chunks=num_chunks)
        out = out if (qb or kb) else out[None]
        out = jnp.where(own[None, :, None], out, 0.0)
        out = jax.lax.psum(out, psum_axes)
        return out if (qb or kb) else out[0]

    q_spec = (P(model_ax) if (mode == "heads" and qb)
              else (P(None, model_ax) if mode == "feat" else P()))
    k_spec = (P(model_ax) if (mode == "heads" and kb)
              else (P(None, model_ax) if mode == "feat" else P()))
    out_spec = P(model_ax) if mode == "heads" else P()
    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(P("data"), P("data"), q_spec, k_spec),
                       out_specs=out_spec, check_vma=False)
    return fn(part.blk_id, part.blk_own, q, k)


def attention_sharded(fmt, q: jax.Array, k: jax.Array, v: jax.Array, *,
                      mesh: Optional[Mesh] = None,
                      part: Optional[ShardedSchedule] = None,
                      schedule: Optional[Schedule] = None,
                      split_blk: int = 1, k_blk: int = 8, scale=None,
                      interpret: Optional[bool] = None,
                      precision: Optional[str] = None) -> jax.Array:
    """Multi-device single-pass fused sparse attention.

    Row windows partition over ``"data"`` on a **window-aligned**
    partition (a window's online-softmax statistics live in one device's
    VMEM scratch and cannot straddle); heads take the ``"model"`` axis
    (3-D operands, head count divisible), otherwise the model axis
    replicates.  Output replicated over ``"data"`` via psum, same
    no-halo argument as :func:`spmm_sharded`.  ``scale`` may be a traced
    scalar (folded into Q before the shard_map, so it stays
    differentiable through :func:`repro.core.autodiff.attention_ad`'s
    recompute backward).
    """
    import math

    from repro.kernels.attention_pallas import _attn_call
    from repro.kernels.layout import schedule_steps
    from repro.kernels.sddmm_pallas import _cast_precision

    q, k, v = _cast_precision(precision, q, k, v)
    blocked = fmt if isinstance(fmt, BlockedMEBCRS) else block_format(fmt, k_blk)
    mesh = _resolve_mesh(mesh)
    if part is None:
        part = sharded_schedule(blocked, mesh.shape["data"],
                                split_blk=split_blk, window_split=False,
                                schedule=schedule)
    _check_part(part, mesh, window_aligned=True)
    interpret = _interp(interpret)

    qb, kb, vb = q.ndim == 3, k.ndim == 3, v.ndim == 3
    batched = qb or kb or vb
    h = next((x.shape[0] for x, f in ((q, qb), (k, kb), (v, vb)) if f), 1)
    vsz = blocked.vector_size
    w = part.num_windows
    m, _ = blocked.shape
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    qs = (q.astype(jnp.float32) * scale).astype(q.dtype)
    model_ax, tp = _model_axis(mesh)
    mode = "heads" if (model_ax and batched and h % tp == 0) else "none"
    if mode == "none":
        model_ax = None

    def local(sw, sm, own, q_l, k_l, v_l):
        sw, sm, own = sw[0], sm[0], own[0]
        q3 = q_l if qb else q_l[None]
        k3 = k_l if kb else k_l[None]
        v3 = v_l if vb else v_l[None]
        qpad = jnp.zeros((q3.shape[0], (w + 1) * vsz, q.shape[-1]), q.dtype
                         ).at[:, : q3.shape[1], :].set(q3)
        out = _attn_call(
            schedule_steps(sw, sm), blocked.cols, blocked.mask, qpad, k3, v3,
            num_windows=w + 1, k_blk=blocked.k_blk, interpret=interpret)
        out = out[:, :m, :v3.shape[-1]].astype(v3.dtype)
        out = jnp.where(own[None, :, None], out, 0.0)
        out = jax.lax.psum(out, "data")
        return out if batched else out[0]

    def spec(is_b):
        return P(model_ax) if (mode == "heads" and is_b) else P()

    out_spec = (P(model_ax) if mode == "heads" else P()) if batched else P()
    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(P("data"), P("data"), P("data"), spec(qb),
                                 spec(kb), spec(vb)),
                       out_specs=out_spec, check_vma=False)
    return fn(part.seg_win, part.seg_meta, part.row_own, qs, k, v)


# ---------------------------------------------------------------------------
# Registry adapters — impl "pallas_sharded" (multi_device capability flag).
# Signatures follow the other Pallas adapters plus (mesh, part) kwargs; the
# autodiff layer passes the ADPlan's per-direction partitions explicitly.
# ---------------------------------------------------------------------------


def _spmm_sharded_adapter(fmt, b, *, k_blk=8, n_blk=128, split_blk=1,
                          schedule=None, mesh=None, part=None,
                          interpret=None, precision=None):
    return spmm_sharded(fmt, b, mesh=mesh, part=part, schedule=schedule,
                        split_blk=split_blk, k_blk=k_blk, n_blk=n_blk,
                        interpret=interpret, precision=precision)


def _sddmm_sharded_adapter(fmt, q, k, *, k_blk=8, f_blk=128, split_blk=1,
                           schedule=None, mesh=None, part=None,
                           interpret=None, precision=None):
    return sddmm_sharded(fmt, q, k, mesh=mesh, part=part, schedule=schedule,
                         split_blk=split_blk, k_blk=k_blk, f_blk=f_blk,
                         interpret=interpret, precision=precision)


def _attention_sharded_adapter(fmt, q, k, v, *, scale=None, k_blk=8,
                               split_blk=1, schedule=None, mesh=None,
                               part=None, interpret=None, precision=None):
    return attention_sharded(fmt, q, k, v, mesh=mesh, part=part,
                             schedule=schedule, split_blk=split_blk,
                             k_blk=k_blk, scale=scale, interpret=interpret,
                             precision=precision)


_dispatch.register("spmm", "pallas_sharded", _spmm_sharded_adapter,
                   differentiable=True, batched=True, load_balanced=True,
                   multi_device=True, precisions=("fp32", "bf16", "int8"))
_dispatch.register("sddmm", "pallas_sharded", _sddmm_sharded_adapter,
                   differentiable=True, batched=True, load_balanced=True,
                   multi_device=True, precisions=("fp32", "bf16"))
_dispatch.register("attention", "pallas_sharded", _attention_sharded_adapter,
                   differentiable=True, batched=True, load_balanced=True,
                   multi_device=True, precisions=("fp32", "bf16"))
