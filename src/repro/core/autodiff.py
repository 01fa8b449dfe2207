"""Differentiable sparse ops: custom_vjp SpMM/SDDMM duality (DESIGN.md §9).

The backward pass of each sparse operator is *made of the sparse operators
we already optimized* — the classic duality:

  SpMM   C = A⟨vals⟩ @ B        dB    = Aᵀ @ G                (transpose-SpMM)
                                 dVals = mask ⊙ SDDMM(G, B)
  SDDMM  S = mask ⊙ (Q Kᵀ)      dQ    = A⟨g⟩ @ K             (SpMM)
                                 dK    = Aᵀ⟨g⟩ @ Q            (transpose-SpMM)

so ``jax.grad`` through a model that aggregates with the fused Pallas
kernels executes *the same* gather-free kernels backward — on Aᵀ for the
transpose-SpMMs — instead of falling back to a dense or scatter-add path.

Aᵀ cannot be re-blocked inside a traced function (the blocked layout's
shapes are data-dependent), so the transposed format is a host-side
precompute: :func:`ad_plan` builds an :class:`ADPlan` carrying

  * ``fwd``  — A as a :class:`BlockedMEBCRS` (the forward layout),
  * ``bwd``  — Aᵀ blocked (the transpose-SpMM layout; ``MEBCRS.transpose``
    is memoized on the canonical format instance),
  * the **re-layout map** that re-lays ``fwd``-layout values into
    ``bwd`` layout, so value rebinding (the live ``vals`` residual for
    dB, the upstream cotangent for dK) is a gather of one value per
    Aᵀ vector, not one per padded slot (:meth:`ADPlan.transpose_vals`),

plus the tile parameters each direction runs with.

The re-layout map is built per *vector* of Aᵀ (one row of a ``V×V`` tile
of A, ``V`` slots).  Level ``j`` holds, for every vector, the index of
its ``j``-th real entry in the ``fwd`` values read slot-major, ``(V,
NNZP)`` as the kernels lay them out (``vec_idx``, int32), and that
entry's slot (``vec_row``, int8; ``V`` marks a vector with no ``j``-th
entry).  Level 0 is always kept; level ``j`` only while at least 1/8 of
the non-empty vectors have more than ``j`` entries, so no level gathers
mostly padding and a dense-tile pattern keeps at most ``V`` levels (one
gather per slot, as a per-slot map would).  Entries past the kept levels
are the **overflow**: one compact gather ``ovf_src`` and a unique,
sorted scatter to the flat ``bwd`` slots ``ovf_dst``.  Graphs with about
one nonzero per tile (Amazon, DD) keep one level and overflow a few
dozen entries.  The plan build records the counters
``fs.ad_plan.relayout_levels`` (levels kept) and
``fs.ad_plan.relayout_overflow`` (entries left to the scatter).

The plan is a pytree: pass it through ``jit``/``grad``/``shard_map``
like the format itself.

All wrappers accept a leading batch dim on the dense operands and/or the
bound values (per-head sparse attention).  The Pallas paths execute the
**native batched grids** — ``(H, N/N_BLK, W)`` SpMM, ``(H, NB, F/F_BLK)``
SDDMM — one kernel launch for any head count, forward and both backward
duality ops, with the sparse metadata shared across heads (the
per-slice one-grid-per-head loop they used to run is gone).  XLA impls
flagged ``batched`` in the registry are ``jax.vmap``-ed; anything else
falls back to an unrolled per-slice loop.

:func:`attention_ad` goes one step further for the SDDMM → sparse softmax
→ SpMM composition: its forward is the single-pass fused megakernel
(``kernels/attention_pallas.py``) whose scores never touch HBM, and its
backward recomputes through the staged differentiable composition
(FlashAttention-style), so the gradient still runs the dispatched
transpose-SpMM/SDDMM duality.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import dispatch as _dispatch
from .format import MEBCRS, BlockedMEBCRS, Schedule, block_format
from .metrics import op_tag, record_counter, span
from .sddmm import with_values
from .softmax import sparse_softmax

__all__ = ["ADPlan", "ad_plan", "spmm_ad", "sddmm_ad", "attention_ad"]


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class ADPlan:
    """Execution plan for differentiable SpMM/SDDMM on one sparse pattern."""

    fwd: BlockedMEBCRS    # A, forward layout
    bwd: BlockedMEBCRS    # Aᵀ, transpose-SpMM layout (vals = re-laid A vals)
    # Re-layout map (module docstring): level after level, per Aᵀ vector
    vec_idx: jax.Array    # (L·NNZP_T,) int32 slot-major index into fwd vals
    vec_row: jax.Array    # (L·NNZP_T,) int8 slot of that entry, V = none
    ovf_src: jax.Array    # (n_ovf,) int32 the same, for entries past level L
    ovf_dst: jax.Array    # (n_ovf,) int32 flat bwd slots, sorted, unique
    impl: str             # impl every traced call on the plan runs
    n_blk: int            # SpMM column tile, forward and transposed (dB / dK)
    f_blk: int            # SDDMM feature tile (dVals / forward SDDMM)
    # Block-parallel schedules (DESIGN.md §11), present for the balanced
    # and sharded impls.  A and Aᵀ are scheduled independently — the
    # transposed format has its own skew (hub *columns* of A become hub
    # windows of Aᵀ).
    fwd_sched: Optional[Schedule] = None
    bwd_sched: Optional[Schedule] = None
    # Multi-device partitions (DESIGN.md §12), present for
    # impl="pallas_sharded": each direction's schedule partitioned over
    # the mesh's "data" axis.  ``fwd_part``/``bwd_part`` allow cuts
    # inside hub windows (the load-balancing lever — partial sums
    # recombine in the psum) and drive the sharded SpMM/SDDMM;
    # ``fwd_part_wa`` is the window-aligned variant the fused attention
    # megakernel requires (its online-softmax state cannot straddle
    # devices).  ``mesh`` rides in the pytree aux — jax.sharding.Mesh is
    # hashable, so the plan stays a valid static structure under jit.
    fwd_part: Optional[object] = None   # distributed.sparse_shard.ShardedSchedule
    bwd_part: Optional[object] = None
    fwd_part_wa: Optional[object] = None
    mesh: Optional[object] = None       # jax.sharding.Mesh
    # Pipeline depth for impl="pallas_sharded_overlap" (DESIGN.md §14):
    # the partitions above are built with this many segment batches per
    # device, and every traced call runs the ppermute ring at that depth.
    # 1 elsewhere (a single batch: ring == bulk order, no pipelining).
    overlap_batches: int = 1
    # Mixed-precision level (DESIGN.md §13) every traced call runs at:
    # None = operand dtypes as given; "int8" quantizes the forward SpMM's
    # sparse values per K-block *in trace* (fp32 masters, straight-through
    # gradients) while every other op runs the bf16 dense level.
    precision: Optional[str] = None
    # Nonfinite rescue (DESIGN.md §15): with a bf16/int8 plan, re-run the
    # forward SpMM at fp32 (lax.cond) when the narrow pass yields NaN/Inf
    # — the guarded forward returns fp32, the backward stays the plain
    # straight-through duality (it reads the fp32 masters regardless).
    guard_nonfinite: bool = False

    @property
    def vals(self) -> jax.Array:
        return self.fwd.vals

    @property
    def mask(self) -> jax.Array:
        return self.fwd.mask

    @property
    def shape(self) -> Tuple[int, int]:
        return self.fwd.shape

    def transpose_vals(self, vals: jax.Array) -> jax.Array:
        """Re-lay ``fwd``-layout values (NNZP, V) into ``bwd`` layout;
        a leading head dim (H, NNZP, V) is re-laid per head.

        One gather of a value per Aᵀ vector and kept level (``vec_idx``),
        expanded onto the vector's ``V`` slots by a ``where`` against the
        entry's slot (``vec_row``), then a unique scatter of the overflow
        entries.  Sources are exclusively mask-true ``fwd`` entries and
        every other slot is a literal zero, so junk (NaN included) in
        masked-off input positions never leaks into the transpose-SpMM.
        The indices are in bounds by construction, so the gathers clamp
        instead of filling: the TPU compiler takes minutes over a program
        with several fill-mode gathers of this size, and seconds with
        clamping ones.  Every op carries the tag ``fs.transpose_vals``
        (:func:`.metrics.op_tag`).
        """
        with op_tag("fs.transpose_vals"):
            lead = vals.shape[:-2]                     # () or (H,)
            # slot-major, as the kernels lay values out (vectors_on_lanes):
            # a row-major flatten would copy (NNZP, V) padded to 128 lanes
            flat = jnp.swapaxes(vals, -1, -2).reshape(lead + (-1,))
            nvec, v = self.bwd.mask.shape
            got = jnp.take(flat, self.vec_idx, axis=-1, mode="clip")
            # expanded slot-major too, (..., V, NNZP_T)
            slots = jnp.arange(v, dtype=self.vec_row.dtype)[:, None]
            out = jnp.zeros(lead + (v, nvec), vals.dtype)
            for lo in range(0, self.vec_idx.shape[0], nvec):  # each level
                out = jnp.where(self.vec_row[lo:lo + nvec] == slots,
                                got[..., None, lo:lo + nvec], out)
            out = jnp.swapaxes(out, -1, -2)
            if self.ovf_src.shape[0]:
                more = jnp.take(flat, self.ovf_src, axis=-1, mode="clip")
                out = out.at[..., self.ovf_dst // v, self.ovf_dst % v].set(
                    more, indices_are_sorted=True, unique_indices=True)
            return out

    def tree_flatten(self):
        return ((self.fwd, self.bwd, self.vec_idx, self.vec_row,
                 self.ovf_src, self.ovf_dst, self.fwd_sched,
                 self.bwd_sched, self.fwd_part, self.bwd_part,
                 self.fwd_part_wa),
                (self.impl, self.n_blk, self.f_blk, self.mesh,
                 self.precision, self.overlap_batches,
                 self.guard_nonfinite))

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        (fwd, bwd, vec_idx, vec_row, ovf_src, ovf_dst, fwd_sched, bwd_sched,
         fwd_part, bwd_part, fwd_part_wa) = leaves
        (impl, n_blk, f_blk, mesh, precision, overlap_batches,
         guard_nonfinite) = aux
        return cls(fwd=fwd, bwd=bwd, vec_idx=vec_idx, vec_row=vec_row,
                   ovf_src=ovf_src, ovf_dst=ovf_dst, impl=impl, n_blk=n_blk,
                   f_blk=f_blk, fwd_sched=fwd_sched,
                   bwd_sched=bwd_sched, fwd_part=fwd_part,
                   bwd_part=bwd_part, fwd_part_wa=fwd_part_wa, mesh=mesh,
                   precision=precision, overlap_batches=overlap_batches,
                   guard_nonfinite=guard_nonfinite)


def _relayout_map(blocked_a: BlockedMEBCRS, blocked_t: BlockedMEBCRS
                  ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """The re-layout map (module docstring) from ``blocked_a`` (A) values
    to ``blocked_t`` (Aᵀ) layout: ``(vec_idx, vec_row, ovf_src,
    ovf_dst)``.  Records the counters ``fs.ad_plan.relayout_levels`` and
    ``fs.ad_plan.relayout_overflow``."""
    v = blocked_a.vector_size
    _, k = blocked_a.shape

    mask_a = np.asarray(blocked_a.mask)
    ta, ra = np.nonzero(mask_a)
    rows_a = np.asarray(blocked_a.block_win)[ta // blocked_a.k_blk] * v + ra
    key_a = rows_a.astype(np.int64) * k + np.asarray(blocked_a.cols)[ta]
    order = np.argsort(key_a)
    key_sorted = key_a[order]
    flat_sorted = (ra * mask_a.shape[0] + ta)[order]     # slot-major

    mask_t = np.asarray(blocked_t.mask)
    tt, rt = np.nonzero(mask_t)            # row-major: per vector, by slot
    rows_t = np.asarray(blocked_t.block_win)[tt // blocked_t.k_blk] * v + rt
    # entry (rows_t, cols_t) of Aᵀ is element (cols_t, rows_t) of A
    key_t = np.asarray(blocked_t.cols)[tt].astype(np.int64) * k + rows_t
    pos = np.searchsorted(key_sorted, key_t)
    if not (pos.size == 0 or np.array_equal(key_sorted[pos], key_t)):
        raise AssertionError("transpose layouts disagree on the sparsity "
                             "pattern (corrupt format?)")
    src = flat_sorted[pos].astype(np.int32)

    nvec = mask_t.shape[0]
    fill = np.bincount(tt, minlength=nvec)
    level = np.arange(tt.size) - (np.cumsum(fill) - fill)[tt]
    # keep level j while 8 * (vectors with more than j entries) >= the
    # non-empty vectors: no kept level gathers mostly padding
    deeper = np.bincount(fill, minlength=v + 1)[::-1].cumsum()[::-1]
    levels = 1
    while (levels < v and deeper[levels + 1] > 0
           and 8 * deeper[levels + 1] >= deeper[1]):
        levels += 1
    # flat, level-major: a 2-D int8 map of one level would pad to 4 rows
    vec_idx = np.zeros(levels * nvec, np.int32)
    vec_row = np.full(levels * nvec, v, np.int8)
    kept = level < levels
    vec_idx[level[kept] * nvec + tt[kept]] = src[kept]
    vec_row[level[kept] * nvec + tt[kept]] = rt[kept]
    ovf_src = src[~kept]
    ovf_dst = (tt * v + rt)[~kept].astype(np.int32)   # sorted, unique
    record_counter("fs.ad_plan.relayout_levels", levels)
    record_counter("fs.ad_plan.relayout_overflow", ovf_src.size)
    return vec_idx, vec_row, ovf_src, ovf_dst


@span("fs.ad_plan")
def ad_plan(fmt: MEBCRS, *, impl: str = "blocked", k_blk: int = 8,
            n_blk: int = 128, f_blk: int = 128, split_blk: int = 1,
            n_example: int = 64, mesh=None,
            overlap_batches: Optional[int] = None,
            precision: Optional[str] = None,
            guard_nonfinite: bool = False) -> ADPlan:
    """Build (and memoize on ``fmt``) the differentiable-op plan.

    Host-side precompute, like ``block_format`` — call outside ``jit``.
    Every direction runs the tiles given here: ``k_blk`` for A and Aᵀ,
    ``n_blk`` for both SpMMs, ``f_blk`` for the SDDMM.  ``n_example`` is
    accepted and ignored (the benchmark harness still passes it).
    ``impl="pallas_balanced"`` builds the block-parallel
    :class:`Schedule` for **both** directions with ``split_blk`` (A and Aᵀ
    scheduled independently — the transpose has its own skew).
    ``impl="pallas_sharded"`` (DESIGN.md §12) additionally
    partitions each direction's schedule over ``mesh``'s "data" axis —
    cost-balanced with hub-window straddling allowed for SpMM/SDDMM,
    plus a window-aligned forward variant for the fused attention
    megakernel — so forward *and* both duality backward ops run one
    local balanced launch per device with a psum.  ``mesh`` is required
    (or an active ``distributed.ctx.activation_mesh``).

    ``precision`` fixes the mixed-precision level of every traced call on
    the plan (DESIGN.md §13): the forward SpMM runs it as given (``int8``
    quantizes the fp32 master values per K-block in-trace), all other ops
    — SDDMM, attention, both duality backward ops — run the *dense level*
    (bf16 for an int8 plan), and the custom_vjp epilogues cast gradients
    back to the residuals' dtypes, so fp32 masters accumulate fp32.

    ``impl="pallas_sharded_overlap"`` (DESIGN.md §14) builds the same
    per-direction partitions with ``overlap_batches`` segment batches per
    device (default 2; 1 disables pipelining), so every traced call —
    forward, both duality backward ops, and the attention recompute —
    replaces the bulk psum with the double-buffered ``ppermute`` ring.

    ``guard_nonfinite=True`` (DESIGN.md §15) arms the nonfinite rescue on
    a bf16/int8 plan: every traced forward SpMM checks its output and
    re-runs at fp32 via ``lax.cond`` when the narrow pass produced
    NaN/Inf, returning fp32.  Gradients stay the plain straight-through
    duality (the backward reads the fp32 masters regardless of which
    branch ran).  A no-op for fp32/None plans.

    The host work runs under the span ``fs.ad_plan`` with the children
    ``fs.ad_plan.transpose``, ``fs.ad_plan.block`` and ``fs.ad_plan.perm``
    (:func:`.metrics.span`).
    """
    from .quantize import validate_precision

    validate_precision(precision)
    guard_nonfinite = bool(guard_nonfinite) and precision in ("bf16", "int8")
    entry = _dispatch.require("spmm", impl, differentiable=True,
                              precision=precision)
    if precision is not None:
        _dispatch.require("sddmm", impl, differentiable=True,
                          precision=_dense_precision(precision))
    if isinstance(fmt, BlockedMEBCRS):
        raise ValueError("ad_plan needs the canonical MEBCRS (it blocks "
                         "both A and its transpose itself)")
    if overlap_batches is None:
        overlap_batches = 2 if entry.overlapped else 1
    elif not entry.overlapped and overlap_batches != 1:
        raise ValueError(
            f"ad_plan(overlap_batches={overlap_batches}) needs an "
            f"overlapped impl (got impl={impl!r}); only "
            f"'pallas_sharded_overlap' pipelines segment batches")
    if entry.multi_device:
        from repro.distributed.sparse_shard import _resolve_mesh

        mesh = _resolve_mesh(mesh)
    elif mesh is not None:
        raise ValueError(
            f"ad_plan(mesh=...) is only meaningful for a multi-device "
            f"impl like 'pallas_sharded' (got impl={impl!r}); dropping "
            f"the mesh would silently run single-device")
    del entry, n_example

    key = (impl, k_blk, n_blk, f_blk, int(split_blk), mesh, precision,
           int(overlap_batches), guard_nonfinite)
    memo = getattr(fmt, "_ad_plans", None)
    if memo is None:
        memo = {}
        object.__setattr__(fmt, "_ad_plans", memo)
    if key in memo:
        return memo[key]

    with span("fs.ad_plan.transpose"):
        fmt_t = fmt.transpose()
    with span("fs.ad_plan.block"):
        blocked_f = block_format(fmt, k_blk)
        blocked_t = block_format(fmt_t, k_blk)
    # pallas_balanced/_sharded always carry schedules — split_blk = 0 is the
    # valid *unsplit* schedule, not "no schedule".
    sharded_impls = ("pallas_sharded", "pallas_sharded_overlap")
    scheduled = impl in ("pallas_balanced",) + sharded_impls
    split = int(split_blk) if scheduled else 0
    fwd_part = bwd_part = fwd_part_wa = None
    if impl in sharded_impls:
        from repro.distributed.sparse_shard import sharded_schedule

        ndev = mesh.shape["data"]
        # SpMM/SDDMM partitions may cut inside hub windows (the balance
        # lever — partials recombine in the psum); attention gets its own
        # window-aligned forward partition (softmax cannot straddle).
        # Each partition is cost-balanced for the SpMM tile ``n_blk``
        # (SDDMM reuses fwd_part; its f_blk and n_blk share the 128
        # default, and the cut positions are only mildly tile-sensitive).  The overlap impl builds the same
        # partitions with ``overlap_batches`` segment batches per device
        # (batch cuts inherit each partition's window_split rule).
        nbat = overlap_batches
        fwd_part = sharded_schedule(blocked_f, ndev, split_blk=split,
                                    n_blk=n_blk, n_batches=nbat)
        bwd_part = sharded_schedule(blocked_t, ndev, split_blk=split,
                                    n_blk=n_blk, n_batches=nbat)
        fwd_part_wa = sharded_schedule(blocked_f, ndev, split_blk=split,
                                       n_blk=n_blk, window_split=False,
                                       n_batches=nbat)
    with span("fs.ad_plan.perm"):
        vec_idx, vec_row, ovf_src, ovf_dst = map(
            jnp.asarray, _relayout_map(blocked_f, blocked_t))
    plan = ADPlan(fwd=blocked_f, bwd=blocked_t, vec_idx=vec_idx,
                  vec_row=vec_row, ovf_src=ovf_src, ovf_dst=ovf_dst,
                  impl=impl, n_blk=n_blk, f_blk=f_blk,
                  fwd_sched=blocked_f.schedule(split) if scheduled else None,
                  bwd_sched=blocked_t.schedule(split) if scheduled else None,
                  fwd_part=fwd_part, bwd_part=bwd_part,
                  fwd_part_wa=fwd_part_wa, mesh=mesh, precision=precision,
                  overlap_batches=overlap_batches,
                  guard_nonfinite=guard_nonfinite)
    memo[key] = plan
    return plan


def _dense_precision(precision: Optional[str]) -> Optional[str]:
    """The precision level of every op except the forward SpMM's sparse
    values: int8 applies only there (per-K-block scales); its gradient
    path, SDDMM, and attention run bf16 — gradients stay straight-through
    to the fp32 masters."""
    return "bf16" if precision == "int8" else precision


def _is_pallas(impl: str) -> bool:
    """Pallas-family impls run native batched grids (no per-slice loop)."""
    return impl in ("pallas", "pallas_balanced", "pallas_sharded",
                    "pallas_sharded_overlap")


def _map_slices(entry, fn, batched_args, shared_args):
    """Apply ``fn(*slices, *shared)`` over a leading batch dim.

    Only reached for non-Pallas impls (the Pallas paths run their native
    batched grids, see ``_run_spmm``/``_run_sddmm``): vmap when the
    registry flags the impl as vmap-safe, otherwise unroll one call per
    slice.
    """
    h = next(a.shape[0] for a, ib in batched_args if ib)
    if entry.batched:
        in_axes = tuple(0 if ib else None for _, ib in batched_args)
        return jax.vmap(lambda *xs: fn(*xs, *shared_args), in_axes=in_axes)(
            *(a for a, _ in batched_args))
    outs = [fn(*(a[i] if ib else a for a, ib in batched_args), *shared_args)
            for i in range(h)]
    return jnp.stack(outs)


# ---------------------------------------------------------------------------
# SpMM:  C = A⟨vals⟩ @ B
# ---------------------------------------------------------------------------


def _run_spmm(impl, interpret, plan: ADPlan, vals, b, *, transposed: bool,
              precision=None):
    # The single-device Pallas launches name their direction on the trace
    # (``kernel_metadata``); the sharded ones carry no metadata.
    direction = "bwd" if transposed else "fwd"
    blocked = plan.bwd if transposed else plan.fwd
    sched = plan.bwd_sched if transposed else plan.fwd_sched
    if impl in ("pallas_sharded", "pallas_sharded_overlap"):
        # one local balanced launch per device over this direction's own
        # partition, outputs reassembled by the psum (DESIGN.md §12) —
        # dB's transpose-SpMM runs on the Aᵀ partition, which is exactly
        # the "psum for dB" of the sharded backward; the overlap impl
        # rides the same partitions (batched to plan.overlap_batches)
        # with the ppermute ring in place of the psum (§14)
        return _dispatch.dispatch("spmm", impl,
                                  with_values(blocked, vals), b,
                                  k_blk=blocked.k_blk, n_blk=plan.n_blk,
                                  schedule=sched, mesh=plan.mesh,
                                  part=plan.bwd_part if transposed
                                  else plan.fwd_part,
                                  interpret=interpret, precision=precision)
    if impl == "pallas_balanced":
        # block-parallel (H, N/N_BLK, NS) grid with this direction's own
        # schedule (Aᵀ is re-scheduled: its skew differs from A's)
        return _dispatch.dispatch("spmm", "pallas_balanced",
                                  with_values(blocked, vals), b,
                                  k_blk=blocked.k_blk, n_blk=plan.n_blk,
                                  schedule=sched, interpret=interpret,
                                  precision=precision, direction=direction)
    # "pallas" runs a native (H, N/N_BLK, W) grid: one launch for any head
    # count
    kw = {"direction": direction} if impl == "pallas" else {}
    return _dispatch.dispatch("spmm", impl,
                              with_values(blocked, vals), b,
                              k_blk=blocked.k_blk, n_blk=plan.n_blk,
                              interpret=interpret, precision=precision, **kw)


def _run_sddmm(impl, interpret, plan: ADPlan, q, k, *, precision=None):
    precision = _dense_precision(precision)   # SDDMM has no int8 level
    if impl in ("pallas_sharded", "pallas_sharded_overlap"):
        # SDDMM samples A's pattern → the forward partition's block list
        return _dispatch.dispatch("sddmm", impl, plan.fwd, q, k,
                                  k_blk=plan.fwd.k_blk, f_blk=plan.f_blk,
                                  schedule=plan.fwd_sched, mesh=plan.mesh,
                                  part=plan.fwd_part, interpret=interpret,
                                  precision=precision)
    if impl == "pallas_balanced":
        # SDDMM samples A's pattern → the forward schedule's block list
        return _dispatch.dispatch("sddmm", "pallas_balanced", plan.fwd, q, k,
                                  k_blk=plan.fwd.k_blk, f_blk=plan.f_blk,
                                  schedule=plan.fwd_sched,
                                  interpret=interpret, precision=precision)
    # "pallas" runs a native (H, NB, F/F_BLK) grid: one launch for any head
    # count
    return _dispatch.dispatch("sddmm", impl, plan.fwd, q, k,
                              k_blk=plan.fwd.k_blk, f_blk=plan.f_blk,
                              interpret=interpret, precision=precision)


@partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _spmm_ad(impl, interpret, plan: ADPlan, vals, b):
    vals_m = vals * plan.fwd.mask  # masked entries are structural zeros
    vb, bb = vals.ndim == 3, b.ndim == 3

    def fwd(precision):
        if not (vb or bb) or _is_pallas(impl):
            return _run_spmm(impl, interpret, plan, vals_m, b,
                             transposed=False, precision=precision)
        entry = _dispatch.get("spmm", impl)
        run = lambda v_, b_: _run_spmm(impl, interpret, plan, v_, b_,
                                       transposed=False, precision=precision)
        return _map_slices(entry, run, [(vals_m, vb), (b, bb)], ())

    out = fwd(plan.precision)
    if not plan.guard_nonfinite:
        return out
    # Nonfinite rescue (DESIGN.md §15): guarded output is always fp32 —
    # both lax.cond branches must share a dtype, and casting the fp32
    # rescue back down would re-overflow the very values it saved.
    ok = jnp.all(jnp.isfinite(out))
    record_counter("guard_nonfinite_rerun",
                   (1 - ok.astype(jnp.int32)))
    return jax.lax.cond(ok, lambda: out.astype(jnp.float32),
                        lambda: fwd("fp32").astype(jnp.float32))


def _spmm_ad_fwd(impl, interpret, plan, vals, b):
    return _spmm_ad(impl, interpret, plan, vals, b), (plan, vals, b)


def _spmm_ad_bwd(impl, interpret, res, g):
    plan, vals, b = res
    vb, bb = vals.ndim == 3, b.ndim == 3

    # The duality backward runs the *dense* precision level — straight-
    # through: int8 never quantizes cotangents, and dvals/db cast back to
    # the residuals' (master) dtypes below.
    bwd_prec = _dense_precision(plan.precision)

    def d_b(v_, g_):      # dB = Aᵀ G — transpose-SpMM through the registry
        return _run_spmm(impl, interpret, plan,
                         plan.transpose_vals(v_ * plan.fwd.mask), g_,
                         transposed=True, precision=bwd_prec)

    def d_vals(g_, b_):   # dVals = mask ⊙ SDDMM(G, B) (impls mask in-epilogue)
        return _run_sddmm(impl, interpret, plan, g_, b_, precision=bwd_prec)

    if not (vb or bb):
        db = d_b(vals, g)
        dvals = d_vals(g, b)
    elif _is_pallas(impl):
        # both duality ops on their native batched grids (g is batched
        # whenever the forward was; one launch each, shared metadata)
        db = d_b(vals, g)
        db = db if bb else jnp.sum(db, axis=0)
        dvals = d_vals(g, b)
        dvals = dvals if vb else jnp.sum(dvals, axis=0)
    else:
        entry = _dispatch.get("spmm", impl)
        db_sl = _map_slices(entry, d_b, [(vals, vb), (g, True)], ())
        db = db_sl if bb else jnp.sum(db_sl, axis=0)
        dv_sl = _map_slices(entry, d_vals, [(g, True), (b, bb)], ())
        dvals = dv_sl if vb else jnp.sum(dv_sl, axis=0)
    return None, dvals.astype(vals.dtype), db.astype(b.dtype)


_spmm_ad.defvjp(_spmm_ad_fwd, _spmm_ad_bwd)


def spmm_ad(plan: ADPlan, vals: jax.Array, b: jax.Array, *,
            impl: Optional[str] = None,
            interpret: Optional[bool] = None) -> jax.Array:
    """Differentiable SpMM: ``C = A⟨vals⟩ @ B`` on ``plan``'s pattern.

    ``vals``: (NNZP, V) forward-layout values (or (H, NNZP, V) batched);
    ``b``: (K, N) (or (H, K, N)).  Gradients flow to both: dVals via the
    masked SDDMM, dB via the transpose-SpMM, each dispatched through the
    registry (so the Pallas impls run the fused kernels backward too).
    Masked-off/padding ``vals`` entries are treated as structural zeros —
    the forward multiplies by the pattern mask, matching the dense-oracle
    semantics of ``to_dense``.
    """
    impl = impl or plan.impl
    _dispatch.require("spmm", impl, differentiable=True)
    return _spmm_ad(impl, interpret, plan, vals, b)


# ---------------------------------------------------------------------------
# SDDMM:  S = mask ⊙ (Q Kᵀ) sampled at the pattern
# ---------------------------------------------------------------------------


@partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _sddmm_ad(impl, interpret, plan: ADPlan, q, k):
    qb, kb = q.ndim == 3, k.ndim == 3
    prec = _dense_precision(plan.precision)
    if not (qb or kb) or _is_pallas(impl):
        return _run_sddmm(impl, interpret, plan, q, k, precision=prec)
    entry = _dispatch.get("sddmm", impl)
    run = lambda q_, k_: _run_sddmm(impl, interpret, plan, q_, k_,
                                    precision=prec)
    return _map_slices(entry, run, [(q, qb), (k, kb)], ())


def _sddmm_ad_fwd(impl, interpret, plan, q, k):
    return _sddmm_ad(impl, interpret, plan, q, k), (plan, q, k)


def _sddmm_ad_bwd(impl, interpret, res, g):
    plan, q, k = res
    qb, kb = q.ndim == 3, k.ndim == 3
    mask = plan.fwd.mask

    bwd_prec = _dense_precision(plan.precision)  # never quantize cotangents

    def d_q(g_, k_):      # dQ = A⟨g⟩ @ K — SpMM with the cotangent bound
        return _run_spmm(impl, interpret, plan, g_ * mask, k_,
                         transposed=False,
                         precision=bwd_prec)[..., : q.shape[-2], :]

    def d_k(g_, q_):      # dK = Aᵀ⟨g⟩ @ Q — transpose-SpMM
        return _run_spmm(impl, interpret, plan,
                         plan.transpose_vals(g_ * mask), q_,
                         transposed=True,
                         precision=bwd_prec)[..., : k.shape[-2], :]

    if not (qb or kb):
        dq, dk = d_q(g, k), d_k(g, q)
    elif _is_pallas(impl):
        dq = d_q(g, k)
        dq = dq if qb else jnp.sum(dq, axis=0)
        dk = d_k(g, q)
        dk = dk if kb else jnp.sum(dk, axis=0)
    else:
        entry = _dispatch.get("spmm", impl)
        dq_sl = _map_slices(entry, d_q, [(g, True), (k, kb)], ())
        dq = dq_sl if qb else jnp.sum(dq_sl, axis=0)
        dk_sl = _map_slices(entry, d_k, [(g, True), (q, qb)], ())
        dk = dk_sl if kb else jnp.sum(dk_sl, axis=0)
    return None, dq.astype(q.dtype), dk.astype(k.dtype)


_sddmm_ad.defvjp(_sddmm_ad_fwd, _sddmm_ad_bwd)


def sddmm_ad(plan: ADPlan, q: jax.Array, k: jax.Array, *,
             impl: Optional[str] = None,
             interpret: Optional[bool] = None) -> jax.Array:
    """Differentiable SDDMM → forward-layout values (NNZP, V).

    ``q``: (M, F) / (H, M, F); ``k``: (Mc, F) / (H, Mc, F).  The values
    come back in the **plan's** forward layout, so SDDMM → sparse softmax
    → SpMM compose without re-blocking.
    Backward is two dispatched SpMMs: dQ on A, dK on the cached Aᵀ.
    """
    impl = impl or plan.impl
    _dispatch.require("sddmm", impl, differentiable=True)
    return _sddmm_ad(impl, interpret, plan, q, k)


# ---------------------------------------------------------------------------
# Fused sparse attention:  out = softmax_sparse(scale · mask ⊙ QKᵀ) @ V
# ---------------------------------------------------------------------------


def _attend(impl, interpret, plan: ADPlan, scores, v, scale):
    """``softmax_sparse(scale · scores) @ V``: the staged composition's
    softmax and SpMM."""
    probs = sparse_softmax(plan.fwd, scores * scale)
    return _spmm_ad(impl, interpret, plan, probs.astype(v.dtype), v)


def _staged_attention(impl, interpret, plan: ADPlan, q, k, v, scale):
    """The 3-dispatch differentiable composition (scores through HBM).
    Serves as the XLA execution path, the fused kernel's recompute
    backward, and the parity/benchmark baseline."""
    return _attend(impl, interpret, plan, _sddmm_ad(impl, interpret, plan,
                                                    q, k), v, scale)


@partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _attention_ad(impl, interpret, plan: ADPlan, q, k, v, scale):
    if impl in ("pallas_sharded", "pallas_sharded_overlap"):
        # sharded single-pass megakernel on the window-aligned forward
        # partition; the recompute backward (below) re-dispatches the
        # sharded duality ops on each direction's own partition.  The
        # overlap impl pipelines window-aligned segment batches, so the
        # online-softmax state never crosses a ring step (§14).
        return _dispatch.dispatch("attention", impl, plan.fwd,
                                  q, k, v, scale=scale, k_blk=plan.fwd.k_blk,
                                  schedule=plan.fwd_sched, mesh=plan.mesh,
                                  part=plan.fwd_part_wa, interpret=interpret,
                                  precision=_dense_precision(plan.precision))
    if impl == "pallas_balanced":
        # balanced (H, NS) megakernel: online softmax carried across the
        # split segments of each window via the plan's forward schedule
        return _dispatch.dispatch("attention", "pallas_balanced", plan.fwd,
                                  q, k, v, scale=scale,
                                  k_blk=plan.fwd.k_blk,
                                  schedule=plan.fwd_sched,
                                  interpret=interpret,
                                  precision=_dense_precision(plan.precision))
    return _dispatch.dispatch("attention", "pallas_fused_attn", plan.fwd,
                              q, k, v, scale=scale, k_blk=plan.fwd.k_blk,
                              interpret=interpret,
                              precision=_dense_precision(plan.precision))


def _attention_ad_fwd(impl, interpret, plan, q, k, v, scale):
    out = _attention_ad(impl, interpret, plan, q, k, v, scale)
    return out, (plan, q, k, v, scale)


def _attention_ad_bwd(impl, interpret, res, g):
    plan, q, k, v, scale = res
    # FlashAttention-style recompute backward: re-derive scores/probs via
    # the staged differentiable composition — its own backward is the
    # dispatched transpose-SpMM / SDDMM duality on the batched grids — so
    # nothing from the forward megakernel needs to be residual.  The
    # barrier ties the recompute to the cotangent: otherwise XLA may
    # schedule every layer's recompute early and keep all their
    # score-sized buffers live at once.
    q, k, v, scale, g = jax.lax.optimization_barrier((q, k, v, scale, g))
    # The recomputed scores carry the tag ``fs.attn_recompute``.  They are
    # computed outside ``jax.vjp``, whose linear ops keep the tag context
    # they were traced in and would hand it to the whole backward; the
    # SDDMM's backward is its custom_vjp rule, called directly.
    with op_tag("fs.attn_recompute"):
        scores = _sddmm_ad(impl, interpret, plan, q, k)
    _, vjp = jax.vjp(partial(_attend, impl, interpret, plan), scores, v,
                     scale)
    d_scores, dv, ds = vjp(g)
    _, dq, dk = _sddmm_ad_bwd(impl, interpret, (plan, q, k), d_scores)
    return None, dq, dk, dv, ds


_attention_ad.defvjp(_attention_ad_fwd, _attention_ad_bwd)


def attention_ad(plan: ADPlan, q: jax.Array, k: jax.Array, v: jax.Array, *,
                 scale=None, impl: Optional[str] = None,
                 interpret: Optional[bool] = None) -> jax.Array:
    """Differentiable block-sparse attention on ``plan``'s pattern.

    ``q (M, F)``, ``k (Mc, F)``, ``v (Mc, FV)`` — each optionally with a
    leading head dim.  ``scale`` (default ``1/sqrt(F)``) may be a traced
    scalar (e.g. AGNN's learned β); it receives a cotangent.

    Pallas impls run the **single-pass fused megakernel** — per-window
    SDDMM scores into VMEM, row-segment online softmax, SpMM accumulation
    against V, one ``(H, W)`` launch, no HBM-resident scores/probs — with
    a recompute backward through the dispatched duality ops.  XLA impls
    run the staged SDDMM → sparse softmax → SpMM composition, which also
    survives as :func:`repro.models.layers.sparse_attention_staged` for
    parity tests and traffic benchmarks.

    ``impl="pallas_balanced"`` runs the **block-parallel** megakernel
    instead: the same single-pass math on the uniform-segment ``(H, NS)``
    grid, with the online-softmax statistics carried across each window's
    split segments (bitwise-equal outputs), and the recompute backward
    dispatching the balanced duality kernels on each direction's own
    schedule.
    """
    impl = impl or plan.impl
    _dispatch.require("spmm", impl, differentiable=True)
    _dispatch.require("sddmm", impl, differentiable=True)
    if plan.precision == "int8":
        # attention has no int8 level: run the whole composition — the
        # recompute backward included — at the plan's dense level (bf16)
        plan = dataclasses.replace(plan, precision="bf16")
    if scale is None:
        scale = 1.0 / float(np.sqrt(q.shape[-1]))
    scale = jnp.asarray(scale, jnp.float32)
    if _is_pallas(impl):
        return _attention_ad(impl, interpret, plan, q, k, v, scale)
    return _staged_attention(impl, interpret, plan, q, k, v, scale)
