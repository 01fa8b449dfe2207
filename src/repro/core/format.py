"""ME-BCRS: memory-efficient block-compressed row storage (FlashSparse §3.5).

The sparse matrix A (M, K) is partitioned into row *windows* of V rows
(V = 8 is FlashSparse's minimal granularity; V = 16 reproduces the
TC-GNN / DTC-SpMM baseline).  Within a window, any column holding at least
one nonzero is a *nonzero vector*.  ME-BCRS stores only nonzero vectors —
no zero-vector padding — using three arrays:

  row_pointers   (W + 1,) int32   start of each window in column_indices
  column_indices (NNZV,)  int32   column id of each nonzero vector
  values         (NNZV, V)        the V elements of each vector

``values`` is **vector-major**: ``values[t]`` is the t-th nonzero vector,
i.e. the storage *is* Aᵀ restricted to nonzero vectors.  This is the TPU
realization of the paper's swap-and-transpose strategy: the window GEMM
``C_w = A_w @ B_g`` is executed as a contraction over the vector index with
the sparse operand logically transposed (``C_wᵀ = B_gᵀ @ A_wᵀ``), so the
window size V sits on the minor, sublane-aligned dimension of every tile
and V = 8 costs nothing on the MXU.

``mask`` records which elements of each nonzero vector are true nonzeros of
A — needed by SDDMM (sampled write-back) and by the redundancy metrics.

A *blocked* view (:class:`BlockedMEBCRS`) pads each window's vector count to
a multiple of ``K_BLK`` for the grouped window-GEMM (XLA and Pallas paths).
Padding lives only in the blocked view; the canonical format stays
padding-free, exactly like the paper (the kernel reconstructs the residue
arithmetically — here via the ``block_win`` metadata).
"""

from __future__ import annotations

import dataclasses
import warnings
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .metrics import span

__all__ = [
    "MEBCRS",
    "BlockedMEBCRS",
    "Schedule",
    "from_dense",
    "from_coo",
    "to_dense",
    "to_coo",
    "block_format",
    "build_schedule",
    "window_skew",
    "memory_footprint_me_bcrs",
    "memory_footprint_sr_bcrs",
]


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class MEBCRS:
    """Padding-free ME-BCRS sparse matrix (FlashSparse §3.5)."""

    row_pointers: jax.Array    # (W + 1,) int32
    column_indices: jax.Array  # (NNZV,) int32
    values: jax.Array          # (NNZV, V) — vector-major (= Aᵀ layout)
    mask: jax.Array            # (NNZV, V) bool — true-nonzero positions
    shape: Tuple[int, int]     # (M, K) of the dense matrix
    vector_size: int           # V

    @property
    def num_windows(self) -> int:
        return int(self.row_pointers.shape[0]) - 1

    @property
    def nnzv(self) -> int:
        return int(self.values.shape[0])

    @property
    def nnz(self) -> int:
        return int(np.asarray(jnp.sum(self.mask)))

    def tree_flatten(self):
        leaves = (self.row_pointers, self.column_indices, self.values, self.mask)
        return leaves, (self.shape, self.vector_size)

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        shape, v = aux
        return cls(*leaves, shape=shape, vector_size=v)

    def transpose(self) -> "MEBCRS":
        """ME-BCRS of Aᵀ (host-side precompute, memoized on the instance).

        The backward duality (DESIGN.md §9) turns SpMM/SDDMM gradients
        into sparse ops *on Aᵀ* — dB = AᵀG is a transpose-SpMM — so the
        transposed format is a one-time format-translation cost, exactly
        like the forward CSR→ME-BCRS conversion, paid per adjacency and
        reused every training step.  Requires concrete (non-tracer)
        arrays: call it (or :func:`repro.core.autodiff.ad_plan`) outside
        ``jit``, like ``block_format``.
        """
        cached = getattr(self, "_transpose_cache", None)
        if cached is not None:
            return cached
        rows, cols, vals = to_coo(self)
        m, k = self.shape
        out = from_coo(cols, rows, vals, (k, m), vector_size=self.vector_size,
                       dtype=self.values.dtype)
        object.__setattr__(self, "_transpose_cache", out)
        return out


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class BlockedMEBCRS:
    """Blocked execution view: windows padded to multiples of K_BLK vectors.

    Flat arrays over NB = sum_w ceil(nnzv_w / K_BLK) K-blocks:
      vals      (NB * K_BLK, V)   zero-padded vector values
      cols      (NB * K_BLK,)     column ids (0 for padding — vals are 0)
      mask      (NB * K_BLK, V)   element mask (False for padding)
      block_win (NB,) int32       output window of each K-block
      win_ptr   (W + 1,) int32    K-block range of each window: window ``w``
                                  owns blocks ``[win_ptr[w], win_ptr[w+1])``
    Consecutive K-blocks of one window are adjacent, so a sequential kernel
    can accumulate into one resident output tile (revisiting pattern).
    ``block_win`` is the scatter view (segment-sum paths); ``win_ptr`` is the
    gather view driving the fused Pallas kernels' per-window inner loop.
    For the degenerate all-empty matrix a single dummy zero block exists so
    the *legacy* kernels always have a non-empty array to index, but no
    window owns it (``win_ptr[-1] == 0``), so ``win_ptr[-1] <= num_blocks``
    with equality in every non-empty case.  The block-parallel
    :class:`Schedule` (DESIGN.md §11) never schedules the dummy block — an
    all-empty matrix yields a valid zero-block schedule whose segments are
    all zero-length, and the balanced kernels write zeros in-kernel instead
    of relying on the dummy block's zero values.
    """

    vals: jax.Array
    cols: jax.Array
    mask: jax.Array
    block_win: jax.Array
    win_ptr: jax.Array
    shape: Tuple[int, int]
    vector_size: int
    k_blk: int
    # Optional per-K-block dequantization scales (NB,) fp32: set (alongside
    # int8 ``vals``) by :func:`repro.core.quantize.quantize_format`; the
    # Pallas SpMM kernels read them per block and dequantize in-VMEM
    # (DESIGN.md §13).  ``None`` on every unquantized format.
    scales: Optional[jax.Array] = None

    @property
    def num_blocks(self) -> int:
        return int(self.block_win.shape[0])

    @property
    def num_windows(self) -> int:
        return -(-self.shape[0] // self.vector_size)

    def tree_flatten(self):
        leaves = (self.vals, self.cols, self.mask, self.block_win,
                  self.win_ptr, self.scales)
        return leaves, (self.shape, self.vector_size, self.k_blk)

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        shape, v, k = aux
        return cls(*leaves[:5], shape=shape, vector_size=v, k_blk=k,
                   scales=leaves[5])

    def schedule(self, split_blk: int = 1) -> "Schedule":
        """Block-parallel execution :class:`Schedule` (memoized per
        ``split_blk``).  Host-side precompute like :func:`block_format` —
        requires concrete (non-tracer) arrays, call outside ``jit``."""
        memo = getattr(self, "_schedules", None)
        if memo is None:
            memo = {}
            object.__setattr__(self, "_schedules", memo)
        if split_blk not in memo:
            memo[split_blk] = build_schedule(self, split_blk)
        return memo[split_blk]


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class Schedule:
    """Block-parallel, load-balanced execution schedule (DESIGN.md §11).

    The window-parallel Pallas grids give each output window one grid cell
    with a ragged inner loop over its K-blocks: a power-law degree
    distribution leaves most cells near-idle while hub windows dominate
    wall-clock.  A schedule re-maps the work onto **uniform segments** of at
    most ``split_blk`` K-blocks:

      seg_win  (NS,)   int32  output window of each segment
      seg_meta (NS, 4) int32  per segment: [first K-block, K-block count,
                              is-first-segment-of-window,
                              is-last-segment-of-window]

    Segments of one window are contiguous and emitted in ascending block
    order, so on a sequential Pallas grid consecutive cells of one window
    revisit the same resident output block: the balanced kernels zero their
    accumulator on ``seg_first``, add one block's contraction per step in
    the same ascending order as the window-parallel kernels (bitwise-equal
    fp32 accumulation), and run the masked epilogue on ``seg_last``.

    Empty windows contribute a single **zero-length** segment (count 0,
    first = last = 1): no DMA and no MXU work are scheduled, only the zero
    store any correct kernel must emit — this is how the degenerate
    all-empty matrix becomes a *valid zero-block schedule* whose kernels
    return zeros without touching the legacy dummy block.
    """

    seg_win: jax.Array
    seg_meta: jax.Array
    split_blk: int            # max K-blocks per segment (0 = unsplit)
    num_blocks: int           # total scheduled K-blocks (0 iff all-empty)

    @property
    def num_segments(self) -> int:
        return int(self.seg_win.shape[0])

    def tree_flatten(self):
        leaves = (self.seg_win, self.seg_meta)
        return leaves, (self.split_blk, self.num_blocks)

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        split_blk, num_blocks = aux
        return cls(*leaves, split_blk=split_blk, num_blocks=num_blocks)


def build_schedule(blocked: BlockedMEBCRS, split_blk: int = 1,
                   check: Optional[str] = None) -> Schedule:
    """Split windows into ≤ ``split_blk``-block segments and elide all work
    for empty windows (they keep one zero-length store-only segment).

    ``split_blk = 0`` disables splitting — one segment per window, the
    window-parallel work assignment expressed in schedule form.  Host-side
    numpy, like :func:`block_format`.  ``check`` audits both the input
    blocked view and the built schedule (``None`` → ambient level,
    DESIGN.md §15).
    """
    from . import validate as _validate

    level = _validate.resolve_check(check)
    _validate.validate_blocked(blocked, check=level)
    if split_blk < 0:
        raise ValueError(f"split_blk must be >= 0, got {split_blk}")
    wp = np.asarray(blocked.win_ptr).astype(np.int64)
    w = blocked.num_windows
    counts = np.diff(wp)

    # Vectorized segmentation (host precompute runs at every plan build,
    # for A and Aᵀ — keep it O(W) numpy, not a Python loop).
    step = np.maximum(counts, 1) if split_blk == 0 \
        else np.full(w, split_blk, np.int64)
    nseg = np.maximum(-(-counts // step), 1)   # empty windows keep one seg
    seg_win = np.repeat(np.arange(w, dtype=np.int64), nseg)
    idx = np.arange(seg_win.size) - np.repeat(np.cumsum(nseg) - nseg, nseg)
    seg_lo = wp[seg_win] + idx * step[seg_win]
    seg_len = np.clip(counts[seg_win] - idx * step[seg_win], 0,
                      step[seg_win])
    seg_lo = np.where(seg_len > 0, seg_lo, 0)  # empty: store-only segment
    seg_first = (idx == 0).astype(np.int64)
    seg_last = (idx == nseg[seg_win] - 1).astype(np.int64)

    seg_meta = np.stack([seg_lo, seg_len, seg_first, seg_last],
                        axis=1).astype(np.int32)

    return _validate.validate_schedule(Schedule(
        seg_win=jnp.asarray(seg_win.astype(np.int32)),
        seg_meta=jnp.asarray(seg_meta),
        split_blk=split_blk,
        num_blocks=int(wp[-1]),
    ), blocked=blocked, check=level)


def window_skew(fmt) -> float:
    """p99 / mean of the per-window nonzero-vector counts (≥ 1.0).

    The balanced-vs-plain statistic (DESIGN.md §11): near 1 for uniform
    matrices, large for power-law / hub-row matrices where a handful of
    windows own most K-blocks — the regime where the block-parallel
    schedule beats the window-parallel grid.  Accepts the canonical
    :class:`MEBCRS` (``row_pointers``) or a :class:`BlockedMEBCRS`
    (``win_ptr``; blocks-per-window is vectors-per-window / k_blk, so the
    ratio statistic agrees between the two up to padding).
    """
    ptr = fmt.win_ptr if isinstance(fmt, BlockedMEBCRS) else fmt.row_pointers
    counts = np.diff(np.asarray(ptr)).astype(np.float64)
    mean = counts.mean() if counts.size else 0.0
    if mean <= 0:
        return 1.0
    return float(max(np.percentile(counts, 99) / mean, 1.0))


# ---------------------------------------------------------------------------
# Construction (host-side numpy: format translation is a preprocessing step,
# mirroring the paper's CUDA-side CSR→ME-BCRS converter).
# ---------------------------------------------------------------------------


@span("fs.from_coo")
def from_coo(
    rows: np.ndarray,
    cols: np.ndarray,
    vals: np.ndarray,
    shape: Tuple[int, int],
    vector_size: int = 8,
    dtype=jnp.float32,
    *,
    duplicates: str = "sum",
    check: Optional[str] = None,
) -> MEBCRS:
    """Build ME-BCRS from COO triplets.

    ``duplicates`` controls repeated ``(row, col)`` coordinates:
    ``"sum"`` coalesces them (the sparse-algebra convention; under
    ``check="full"`` a :class:`~repro.core.validate.ValidationWarning`
    reports how many were merged), ``"error"`` raises a named
    :class:`~repro.core.validate.ValidationError` — the right setting when
    the triplets come from an external producer where duplicates signal a
    corrupted stream rather than an incremental build.  ``check`` follows
    :func:`repro.core.validate.resolve_check` (``None`` → ambient level);
    the constructed format is audited before it is returned.  The build
    runs under the host span ``fs.from_coo`` (:func:`.metrics.span`).
    """
    from . import validate as _validate

    if duplicates not in ("sum", "error"):
        raise ValueError(f"duplicates must be 'sum' or 'error', "
                         f"got {duplicates!r}")
    level = _validate.resolve_check(check)
    m, k = shape
    v = vector_size
    rows = np.asarray(rows, dtype=np.int64)
    cols = np.asarray(cols, dtype=np.int64)
    vals = np.asarray(vals)
    if rows.size and (rows.min() < 0 or cols.min() < 0
                      or rows.max() >= m or cols.max() >= k):
        raise _validate.ValidationError(
            "coo-in-bounds", f"COO indices out of bounds for shape {shape}")
    if rows.size and (duplicates == "error" or level == "full"):
        elem_key = rows * k + cols
        n_dup = elem_key.size - np.unique(elem_key).size
        if n_dup:
            if duplicates == "error":
                raise _validate.ValidationError(
                    "duplicate-coords",
                    f"{n_dup} duplicate COO coordinate(s)")
            warnings.warn(_validate.ValidationWarning(
                f"[duplicate-coords] coalesced {n_dup} duplicate COO "
                f"coordinate(s) by summation"), stacklevel=2)

    w = -(-m // v)
    win = rows // v
    r_in_win = rows % v

    # Sort by (window, column) and coalesce duplicates into vectors.
    vec_key = win * k + cols
    order = np.argsort(vec_key, kind="stable")
    vec_key_s = vec_key[order]
    uniq_keys, vec_of_elem = np.unique(vec_key_s, return_inverse=True)
    nnzv = uniq_keys.shape[0]

    values = np.zeros((nnzv, v), dtype=np.float64)
    maskf = np.zeros((nnzv, v), dtype=bool)
    np.add.at(values, (vec_of_elem, r_in_win[order]), vals[order])
    maskf[vec_of_elem, r_in_win[order]] = True

    vec_win = (uniq_keys // k).astype(np.int32)
    vec_col = (uniq_keys % k).astype(np.int32)
    row_pointers = np.zeros(w + 1, dtype=np.int32)
    np.add.at(row_pointers, vec_win + 1, 1)
    row_pointers = np.cumsum(row_pointers, dtype=np.int32)

    return _validate.validate_format(MEBCRS(
        row_pointers=jnp.asarray(row_pointers),
        column_indices=jnp.asarray(vec_col),
        values=jnp.asarray(values, dtype=dtype),
        mask=jnp.asarray(maskf),
        shape=(m, k),
        vector_size=v,
    ), check=level)


def from_dense(a: np.ndarray, vector_size: int = 8, dtype=None) -> MEBCRS:
    """Build ME-BCRS from a dense matrix."""
    a = np.asarray(a)
    rows, cols = np.nonzero(a)
    dtype = dtype or jnp.asarray(a).dtype
    return from_coo(rows, cols, a[rows, cols], a.shape, vector_size, dtype=dtype)


def to_dense(fmt: MEBCRS) -> jax.Array:
    """Reconstruct the dense matrix (oracle for round-trip tests)."""
    m, k = fmt.shape
    v = fmt.vector_size
    w = fmt.num_windows
    rp = np.asarray(fmt.row_pointers)
    # window id of each vector, via the CSR pointer expansion
    win_of_vec = np.repeat(np.arange(w, dtype=np.int64), np.diff(rp))
    out = np.zeros((w * v, k), dtype=np.asarray(fmt.values).dtype)
    vals = np.asarray(fmt.values) * np.asarray(fmt.mask)
    ci = np.asarray(fmt.column_indices)
    for t in range(vals.shape[0]):
        out[win_of_vec[t] * v : (win_of_vec[t] + 1) * v, ci[t]] += vals[t]
    return jnp.asarray(out[:m])


def to_coo(fmt) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """True-nonzero COO triplets ``(rows, cols, vals)`` of a format.

    Accepts the canonical :class:`MEBCRS` or a :class:`BlockedMEBCRS`
    (padding entries carry ``mask=False`` and are dropped).  Host-side
    numpy — a format-translation step, not jit-traceable.
    """
    v = fmt.vector_size
    if isinstance(fmt, BlockedMEBCRS):
        mask = np.asarray(fmt.mask)
        t_idx, r_idx = np.nonzero(mask)
        win = np.asarray(fmt.block_win)[t_idx // fmt.k_blk]
        rows = win.astype(np.int64) * v + r_idx
        cols = np.asarray(fmt.cols)[t_idx].astype(np.int64)
        vals = np.asarray(fmt.vals)[t_idx, r_idx]
        return rows, cols, vals
    rp = np.asarray(fmt.row_pointers)
    win_of_vec = np.repeat(np.arange(fmt.num_windows, dtype=np.int64),
                           np.diff(rp))
    mask = np.asarray(fmt.mask)
    t_idx, r_idx = np.nonzero(mask)
    rows = win_of_vec[t_idx] * v + r_idx
    cols = np.asarray(fmt.column_indices)[t_idx].astype(np.int64)
    vals = np.asarray(fmt.values)[t_idx, r_idx]
    return rows, cols, vals


def block_format(fmt: MEBCRS, k_blk: int = 8,
                 check: Optional[str] = None) -> BlockedMEBCRS:
    """Pad each window's vectors to a multiple of ``k_blk`` → blocked view.

    This is where the paper's "last TC block residue" lives: padding columns
    get value 0 / mask False / column 0, so their MMA contribution vanishes
    (same arithmetic-elimination trick as the paper's modulo residue test,
    but resolved at format-translation time so the kernel's metadata
    reads stay branch-free).  ``check`` audits the input format and the blocked
    view (``None`` → ambient level, DESIGN.md §15).
    """
    from . import validate as _validate

    level = _validate.resolve_check(check)
    _validate.validate_format(fmt, check=level)
    if not (isinstance(k_blk, int) and 1 <= k_blk <= 4096):
        raise _validate.ValidationError(
            "block-config", f"k_blk={k_blk!r} outside the sane range "
            "[1, 4096]")
    rp = np.asarray(fmt.row_pointers)
    counts = np.diff(rp)
    w = fmt.num_windows
    v = fmt.vector_size
    nblk_per_win = -(-counts // k_blk)
    nblk_per_win = np.maximum(nblk_per_win, 0)
    nb = max(int(nblk_per_win.sum()), 1)  # >=1 so kernels always have a block
    nnzp = nb * k_blk

    vals = np.zeros((nnzp, v), dtype=np.asarray(fmt.values).dtype)
    cols = np.zeros((nnzp,), dtype=np.int32)
    mask = np.zeros((nnzp, v), dtype=bool)
    block_win = np.zeros((nb,), dtype=np.int32)

    src_vals = np.asarray(fmt.values)
    src_cols = np.asarray(fmt.column_indices)
    src_mask = np.asarray(fmt.mask)

    dst = 0
    blk = 0
    for wi in range(w):
        cnt = int(counts[wi])
        s = int(rp[wi])
        if cnt:
            vals[dst : dst + cnt] = src_vals[s : s + cnt]
            cols[dst : dst + cnt] = src_cols[s : s + cnt]
            mask[dst : dst + cnt] = src_mask[s : s + cnt]
        nblk = int(nblk_per_win[wi])
        block_win[blk : blk + nblk] = wi
        dst += nblk * k_blk
        blk += nblk
    if blk == 0:  # all-empty matrix: one dummy block on window 0
        block_win[0] = 0

    # Per-window K-block ranges for the fused kernels' inner loop.  The
    # all-empty dummy block is deliberately outside every range (its vals
    # are zero anyway, but the fused kernels then skip it entirely).
    win_ptr = np.zeros((w + 1,), dtype=np.int32)
    win_ptr[1:] = np.cumsum(nblk_per_win)

    return _validate.validate_blocked(BlockedMEBCRS(
        vals=jnp.asarray(vals),
        cols=jnp.asarray(cols),
        mask=jnp.asarray(mask),
        block_win=jnp.asarray(block_win),
        win_ptr=jnp.asarray(win_ptr),
        shape=fmt.shape,
        vector_size=v,
        k_blk=k_blk,
    ), check=level)


# ---------------------------------------------------------------------------
# Memory footprint accounting (paper Table 7)
# ---------------------------------------------------------------------------


def memory_footprint_me_bcrs(fmt: MEBCRS, value_bytes: int = 2) -> int:
    """Bytes of the padding-free ME-BCRS format (W row pointers)."""
    w = fmt.num_windows
    nnzv = fmt.nnzv
    return 4 * w + 4 * nnzv + value_bytes * nnzv * fmt.vector_size


def memory_footprint_sr_bcrs(fmt: MEBCRS, k: int = 8, value_bytes: int = 2) -> int:
    """Bytes of the zero-padding SR-BCRS scheme [Li et al., SC'22].

    Each window is padded to a multiple of ``k`` vectors and 2·W row
    pointers are stored (start of window + start of padding), per §3.5.
    """
    counts = np.diff(np.asarray(fmt.row_pointers))
    padded = (-(-counts // k) * k).sum()
    w = fmt.num_windows
    return 4 * 2 * w + 4 * int(padded) + value_bytes * int(padded) * fmt.vector_size
