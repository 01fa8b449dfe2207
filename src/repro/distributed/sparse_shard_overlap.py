"""Overlapped multi-device sparse ops: segment-batch ``ppermute`` rings.

``pallas_sharded`` (``distributed/sparse_shard.py``) is bulk-synchronous:
every device finishes its entire balanced launch before a single ``psum``
reassembles the output, so collective latency sits fully on the critical
path.  This module registers ``pallas_sharded_overlap`` (DESIGN.md §14),
which hides it:

  * each device's segment range is sub-split into ``n_batches``
    cost-balanced *segment batches*
    (:func:`~repro.distributed.sparse_shard.partition_schedule` with
    ``n_batches=``), one balanced kernel launch per batch;
  * instead of a trailing ``psum`` over the full ``(M, N)`` output, each
    batch emits a **compact partial** — only the rows its windows own,
    paired with their global row indices — that circulates the "data"
    ring via :func:`~repro.distributed.overlap.ring_scatter_pipeline`
    while the next batch computes, scatter-added on arrival;
  * every device folds every ``(origin device, batch)`` partial exactly
    once, so the result is the bulk output up to fp32 summation grouping
    (windows straddling device or batch cuts regroup) — and exactly
    fp32-allclose to ``pallas_sharded``.

Traffic also *shrinks*: a psum moves the full zero-padded buffer both
directions of the reduce-scatter/all-gather; the ring moves each owned
row once per hop.  Attention batches are window-aligned
(``window_split=False`` partitions only) so the megakernel's online-
softmax statistics never cross a pipeline step.

Same "model"-axis modes as the bulk ops (heads / output-columns /
contracted-feature); the ring runs over the ``"data"`` axis only.
Testable on CPU via ``XLA_FLAGS=--xla_force_host_platform_device_count``
with interpret-mode kernels; see ``tests/test_sparse_shard_overlap.py``.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import dispatch as _dispatch
from repro.core.format import BlockedMEBCRS, Schedule, block_format

from .overlap import ring_scatter_pipeline
from .sparse_shard import (
    ShardedSchedule,
    _check_part,
    _interp,
    _model_axis,
    _resolve_mesh,
    sharded_schedule,
)

__all__ = [
    "spmm_sharded_overlap",
    "sddmm_sharded_overlap",
    "attention_sharded_overlap",
]


def _check_batched(part: ShardedSchedule) -> None:
    if part.bseg_win is None:
        raise ValueError(
            "overlap ops need a segment-batched partition: rebuild it via "
            "partition_schedule(..., n_batches=...) / sharded_schedule")


def _gather_rows(out: jax.Array, idx: jax.Array, n_rows: int) -> jax.Array:
    """Compact (H, R, N) slice of ``out``'s rows listed in ``idx``.

    Pad entries (``idx == n_rows``) and rows the kernel never stored may
    hold garbage — clip the gather and zero-mask, so the buffer is safe
    to circulate and scatter-add blindly.
    """
    valid = idx < n_rows
    g = out[:, jnp.minimum(idx, n_rows - 1), :]
    return jnp.where(valid[None, :, None], g, 0)


def _scatter_rows(acc: jax.Array, buf: jax.Array, idx: jax.Array) -> jax.Array:
    """Scatter-add a compact partial; pads (zero rows) land harmlessly."""
    safe = jnp.minimum(idx, acc.shape[1] - 1)
    return acc.at[:, safe, :].add(buf)


def spmm_sharded_overlap(fmt, b: jax.Array, *, mesh: Optional[Mesh] = None,
                         part: Optional[ShardedSchedule] = None,
                         schedule: Optional[Schedule] = None,
                         split_blk: int = 1, k_blk: int = 8,
                         n_blk: int = 128, n_batches: int = 2,
                         interpret: Optional[bool] = None,
                         precision: Optional[str] = None) -> jax.Array:
    """Overlapped multi-device SpMM: per-batch launches + ``ppermute`` ring.

    Same contract as :func:`~repro.distributed.sparse_shard.spmm_sharded`
    (operands, model-axis modes, replicated output, precision policy);
    ``n_batches`` picks the pipeline depth when ``part`` is not supplied
    (else the partition's own ``n_batches`` wins).
    """
    from repro.kernels.layout import schedule_steps
    from repro.kernels.spmm_pallas import _apply_precision, _spmm_call

    blocked = fmt if isinstance(fmt, BlockedMEBCRS) else block_format(fmt, k_blk)
    mesh = _resolve_mesh(mesh)
    if part is None:
        part = sharded_schedule(blocked, mesh.shape["data"],
                                split_blk=split_blk, n_blk=n_blk,
                                n_batches=n_batches, schedule=schedule)
    _check_part(part, mesh)
    _check_batched(part)
    nbat = part.n_batches
    interpret = _interp(interpret)

    vals, scales, quantized, b = _apply_precision(blocked, b, precision)
    vb, bb = vals.ndim == 3, b.ndim == 3
    h = vals.shape[0] if vb else (b.shape[0] if bb else 1)
    m, _ = blocked.shape
    n = b.shape[-1]
    w = part.num_windows
    ndev = mesh.shape["data"]
    model_ax, tp = _model_axis(mesh)
    if model_ax and (vb or bb) and h % tp == 0:
        mode = "heads"
    elif model_ax and not (vb or bb) and n % tp == 0:
        mode = "cols"
    else:
        mode, model_ax = "none", None

    def local(bsw, bsm, bri, vals_l, b_l):
        bsw, bsm, bri = bsw[0], bsm[0], bri[0]
        vals3 = vals_l if vb else vals_l[None]
        b3 = b_l if bb else b_l[None]
        n_loc = b3.shape[-1]
        hh = vals3.shape[0] if vb else b3.shape[0]

        def compute(t):
            out = _spmm_call(
                schedule_steps(bsw[t], bsm[t]), blocked.cols, scales, vals3,
                b3, num_windows=w + 1, k_blk=blocked.k_blk, n_blk=n_blk,
                interpret=interpret, quantized=quantized)
            out = out[:, :m, :n_loc].astype(b3.dtype)
            return _gather_rows(out, bri[t], m), bri[t]

        acc = jnp.zeros((hh, m, n_loc), b3.dtype)
        out = ring_scatter_pipeline(compute, _scatter_rows, acc,
                                    axis_name="data", axis_size=ndev,
                                    n_batches=nbat)
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
    return fn(part.bseg_win, part.bseg_meta, part.brow_idx, vals, b)


def sddmm_sharded_overlap(fmt, q: jax.Array, k: jax.Array, *,
                          mesh: Optional[Mesh] = None,
                          part: Optional[ShardedSchedule] = None,
                          schedule: Optional[Schedule] = None,
                          split_blk: int = 1, k_blk: int = 8,
                          f_blk: int = 128, n_batches: int = 2,
                          interpret: Optional[bool] = None,
                          precision: Optional[str] = None) -> jax.Array:
    """Overlapped multi-device SDDMM → blocked values ``(NNZP, V)``.

    Value rows are uniquely owned by one (device, batch)'s blocks, so the
    ring's scatter-adds place each exactly once into a zero accumulator;
    the "feat" TP mode still ``psum``s the partial products over
    ``"model"`` after the data-axis ring.
    """
    from repro.kernels.layout import LANES
    from repro.kernels.sddmm_pallas import _cast_precision, _sddmm, chunk_range

    q, k = _cast_precision(precision, q, k)
    blocked = fmt if isinstance(fmt, BlockedMEBCRS) else block_format(fmt, k_blk)
    mesh = _resolve_mesh(mesh)
    if part is None:
        part = sharded_schedule(blocked, mesh.shape["data"],
                                split_blk=split_blk, n_blk=f_blk,
                                n_batches=n_batches, schedule=schedule)
    _check_part(part, mesh)
    _check_batched(part)
    nbat = part.n_batches
    interpret = _interp(interpret)

    qb, kb = q.ndim == 3, k.ndim == 3
    h = q.shape[0] if qb else (k.shape[0] if kb else 1)
    v = blocked.vector_size
    nb = blocked.num_blocks
    f = q.shape[-1]
    nnzp = nb * blocked.k_blk
    ndev = mesh.shape["data"]
    if part.num_blocks == 0:                     # all-empty pattern
        out = jnp.zeros((h, nnzp, v), q.dtype)
        return out if (qb or kb) else out[0]
    model_ax, tp = _model_axis(mesh)
    if model_ax and (qb or kb) and h % tp == 0:
        mode = "heads"
    elif model_ax and not (qb or kb) and f % tp == 0:
        mode = "feat"
    else:
        mode, model_ax = "none", None
    num_chunks = chunk_range(part.bblk_id.shape[-1], blocked.k_blk)

    def local(bbi, bvi, q_l, k_l):
        bbi, bvi = bbi[0], bvi[0]
        hh = q_l.shape[0] if qb else (k_l.shape[0] if kb else 1)

        def compute(t):
            # batch t's blocks are the contiguous range starting at bbi[t, 0]
            out = _sddmm(blocked, q_l, k_l, f_blk=f_blk, interpret=interpret,
                         precision=None,
                         chunk0=bbi[t, :1] * blocked.k_blk // LANES,
                         num_chunks=num_chunks)
            out = out if (qb or kb) else out[None]
            return _gather_rows(out, bvi[t], nnzp), bvi[t]

        acc = jnp.zeros((hh, nnzp, v), q_l.dtype)
        out = ring_scatter_pipeline(compute, _scatter_rows, acc,
                                    axis_name="data", axis_size=ndev,
                                    n_batches=nbat)
        if mode == "feat":
            out = jax.lax.psum(out, model_ax)
        return out if (qb or kb) else out[0]

    q_spec = (P(model_ax) if (mode == "heads" and qb)
              else (P(None, model_ax) if mode == "feat" else P()))
    k_spec = (P(model_ax) if (mode == "heads" and kb)
              else (P(None, model_ax) if mode == "feat" else P()))
    out_spec = P(model_ax) if mode == "heads" else P()
    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(P("data"), P("data"), q_spec, k_spec),
                       out_specs=out_spec, check_vma=False)
    return fn(part.bblk_id, part.bval_idx, q, k)


def attention_sharded_overlap(fmt, q: jax.Array, k: jax.Array, v: jax.Array,
                              *, mesh: Optional[Mesh] = None,
                              part: Optional[ShardedSchedule] = None,
                              schedule: Optional[Schedule] = None,
                              split_blk: int = 1, k_blk: int = 8, scale=None,
                              n_batches: int = 2,
                              interpret: Optional[bool] = None,
                              precision: Optional[str] = None) -> jax.Array:
    """Overlapped multi-device fused sparse attention.

    Needs a **window-aligned** partition (``window_split=False``): batch
    cuts inherit the window alignment, so a window's online-softmax
    statistics live entirely inside one (device, batch) launch and never
    cross a pipeline step.  Rows are then uniquely owned per batch and
    the ring scatter is placement, not accumulation.
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
                                n_batches=n_batches, schedule=schedule)
    _check_part(part, mesh, window_aligned=True)
    _check_batched(part)
    nbat = part.n_batches
    interpret = _interp(interpret)

    qb, kb, vb = q.ndim == 3, k.ndim == 3, v.ndim == 3
    batched = qb or kb or vb
    h = next((x.shape[0] for x, f in ((q, qb), (k, kb), (v, vb)) if f), 1)
    vsz = blocked.vector_size
    w = part.num_windows
    m, _ = blocked.shape
    ndev = mesh.shape["data"]
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    qs = (q.astype(jnp.float32) * scale).astype(q.dtype)
    model_ax, tp = _model_axis(mesh)
    mode = "heads" if (model_ax and batched and h % tp == 0) else "none"
    if mode == "none":
        model_ax = None

    def local(bsw, bsm, bri, q_l, k_l, v_l):
        bsw, bsm, bri = bsw[0], bsm[0], bri[0]
        q3 = q_l if qb else q_l[None]
        k3 = k_l if kb else k_l[None]
        v3 = v_l if vb else v_l[None]
        qpad = jnp.zeros((q3.shape[0], (w + 1) * vsz, q.shape[-1]), q.dtype
                         ).at[:, : q3.shape[1], :].set(q3)
        hh = next((x.shape[0] for x, f in ((q3, qb), (k3, kb), (v3, vb))
                   if f), 1)

        def compute(t):
            out = _attn_call(
                schedule_steps(bsw[t], bsm[t]), blocked.cols, blocked.mask,
                qpad, k3, v3, num_windows=w + 1, k_blk=blocked.k_blk,
                interpret=interpret)
            out = out[:, :m, :v3.shape[-1]].astype(v3.dtype)
            return _gather_rows(out, bri[t], m), bri[t]

        acc = jnp.zeros((hh, m, v3.shape[-1]), v3.dtype)
        out = ring_scatter_pipeline(compute, _scatter_rows, acc,
                                    axis_name="data", axis_size=ndev,
                                    n_batches=nbat)
        return out if batched else out[0]

    def spec(is_b):
        return P(model_ax) if (mode == "heads" and is_b) else P()

    out_spec = (P(model_ax) if mode == "heads" else P()) if batched else P()
    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(P("data"), P("data"), P("data"), spec(qb),
                                 spec(kb), spec(vb)),
                       out_specs=out_spec, check_vma=False)
    return fn(part.bseg_win, part.bseg_meta, part.brow_idx, qs, k, v)


# ---------------------------------------------------------------------------
# Registry adapters — impl "pallas_sharded_overlap" (overlapped capability
# flag on top of pallas_sharded's).  The autodiff layer passes the ADPlan's
# per-direction batched partitions explicitly; ``n_batches`` only matters
# when the partition is built here.
# ---------------------------------------------------------------------------


def _spmm_overlap_adapter(fmt, b, *, k_blk=8, n_blk=128, split_blk=1,
                          schedule=None, mesh=None, part=None, n_batches=2,
                          interpret=None, precision=None):
    return spmm_sharded_overlap(fmt, b, mesh=mesh, part=part,
                                schedule=schedule, split_blk=split_blk,
                                k_blk=k_blk, n_blk=n_blk,
                                n_batches=n_batches, interpret=interpret,
                                precision=precision)


def _sddmm_overlap_adapter(fmt, q, k, *, k_blk=8, f_blk=128, split_blk=1,
                           schedule=None, mesh=None, part=None, n_batches=2,
                           interpret=None, precision=None):
    return sddmm_sharded_overlap(fmt, q, k, mesh=mesh, part=part,
                                 schedule=schedule, split_blk=split_blk,
                                 k_blk=k_blk, f_blk=f_blk,
                                 n_batches=n_batches, interpret=interpret,
                                 precision=precision)


def _attention_overlap_adapter(fmt, q, k, v, *, scale=None, k_blk=8,
                               split_blk=1, schedule=None, mesh=None,
                               part=None, n_batches=2, interpret=None,
                               precision=None):
    return attention_sharded_overlap(fmt, q, k, v, mesh=mesh, part=part,
                                     schedule=schedule, split_blk=split_blk,
                                     k_blk=k_blk, scale=scale,
                                     n_batches=n_batches,
                                     interpret=interpret,
                                     precision=precision)


_dispatch.register("spmm", "pallas_sharded_overlap", _spmm_overlap_adapter,
                   differentiable=True, batched=True, load_balanced=True,
                   multi_device=True, overlapped=True,
                   precisions=("fp32", "bf16", "int8"))
_dispatch.register("sddmm", "pallas_sharded_overlap", _sddmm_overlap_adapter,
                   differentiable=True, batched=True, load_balanced=True,
                   multi_device=True, overlapped=True,
                   precisions=("fp32", "bf16"))
_dispatch.register("attention", "pallas_sharded_overlap",
                   _attention_overlap_adapter,
                   differentiable=True, batched=True, load_balanced=True,
                   multi_device=True, overlapped=True,
                   precisions=("fp32", "bf16"))
