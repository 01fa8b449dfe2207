"""Single-pass fused sparse-attention Pallas kernel (DESIGN.md §10).

SDDMM → row softmax → SpMM in **one** grid cell per (head, window): the
FlashAttention online-softmax pattern specialized to the ME-BCRS blocked
layout.  The key structural fact making this a *local* fusion is that a
sparse attention row (query token) lives in exactly one V-row window, and
*all* of that window's nonzero vectors are owned by the window's K-block
range ``[win_ptr[w], win_ptr[w+1])`` — so a single grid cell walking those
blocks sees every score of its V rows and can finish their softmax without
any cross-cell communication.

Per K-block the cell DMAs the sampled K rows *and* the matching V rows
(same column ids, one descriptor batch, double-buffered),
computes the (K_BLK, V) score tile on the MXU, folds it into running
per-row (max, sum) statistics, and accumulates the rescaled probability
tile against the V rows into a VMEM-resident (V, DV) accumulator:

    s      = K_rows @ (scale·Q_w)ᵀ          masked → -FLT_MAX
    m'     = max(m, max_k s)                α = exp(m - m')
    p      = exp(s - m') ⊙ mask
    l      = α·l + Σ_k p
    acc    = α·acc + pᵀ @ V_rows

The epilogue divides by ``max(l, 1e-20)`` (matching
:func:`repro.core.softmax.sparse_softmax`'s empty-row semantics) and casts
— scores and probabilities **never exist in HBM**.  The 3-dispatch
pipeline (SDDMM kernel → XLA sparse softmax → SpMM kernel), which round-
trips the full (NNZP, V) score tensor through HBM twice, survives as
:func:`attention_pallas_staged` — the baseline for the Fig. 12-style
traffic model :func:`attention_hbm_bytes` and for parity tests.

Grid ``(H, W)``: one launch for any head count, metadata shared across
heads; Q/K/V may each be per-head (leading H) or shared.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .layout import (CACHE_ROWS, LANES, SmemRows, block_tile, chunks_per_block,
                     dot, lane_tile, pad_cols, require_fp32, schedule_steps,
                     smem_cache, steps_table, sublanes, table,
                     vectors_on_lanes, window_steps)
from .sddmm_pallas import _cast_precision

__all__ = [
    "attention_pallas",
    "attention_pallas_balanced",
    "attention_pallas_staged",
    "attention_hbm_bytes",
    "attention_launch_counts",
]

_NEG = float(jnp.finfo(jnp.float32).min)  # same sentinel as sparse_softmax


def _attn_kernel(steps_hbm, cols_hbm, mask_hbm, q_hbm, k_hbm, v_hbm, o_hbm,
                 acc_ref, m_ref, l_ref, q_buf, k_buf, v_buf, mask_buf,
                 steps_buf, steps_base, cols_buf, cols_base, sems, *,
                 k_blk: int, v: int, kv_rows: int, num_windows: int,
                 q_batched: bool, k_batched: bool, v_batched: bool):
    h = pl.program_id(0)
    s = pl.program_id(1)
    # Heads are stacked on rows; shared operands read slice 0 (static).
    qrow = h * num_windows * v if q_batched else 0
    krow = h * kv_rows if k_batched else 0
    vrow = h * kv_rows if v_batched else 0
    steps = SmemRows(steps_hbm, steps_buf, steps_base, sems.at[0, 3])
    cols = SmemRows(cols_hbm, cols_buf, cols_base, sems.at[1, 3])

    @pl.when((h == 0) & (s == 0))
    def _reset():
        steps.reset()
        cols.reset()

    steps.ensure(8 * s, 8 * s + 4)
    lo = steps[8 * s]
    hi = lo + steps[8 * s + 1]
    first = steps[8 * s + 2]
    last = steps[8 * s + 3]
    win = steps[8 * s + 4]
    width = mask_buf.shape[-1]

    def copies(blk, slot, for_wait=False):
        """DMA descriptors for K-block ``blk``: the block's mask chunk plus
        K_BLK K-row and V-row slices at the block's column ids."""
        base = blk * k_blk
        chunk = 0 if for_wait else (base // LANES) * LANES
        cps = [pltpu.make_async_copy(mask_hbm.at[:, pl.ds(chunk, width)],
                                     mask_buf.at[slot], sems.at[slot, 0])]
        for r in range(k_blk):
            c = 0 if for_wait else cols[base + r]
            cps.append(pltpu.make_async_copy(
                k_hbm.at[pl.ds(krow + c, 1), :],
                k_buf.at[slot, pl.ds(r, 1)], sems.at[slot, 1]))
            cps.append(pltpu.make_async_copy(
                v_hbm.at[pl.ds(vrow + c, 1), :],
                v_buf.at[slot, pl.ds(r, 1)], sems.at[slot, 2]))
        return cps

    def start(blk, slot):
        cols.ensure(blk * k_blk, blk * k_blk + k_blk - 1)
        for cp in copies(blk, slot):
            cp.start()

    @pl.when(first == 1)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    cp = pltpu.make_async_copy(q_hbm.at[pl.ds(qrow + win * v, v), :], q_buf,
                               sems.at[0, 4])
    cp.start()
    cp.wait()
    qwin = q_buf[...]                                        # (V, D) scaled Q

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

        maskf = block_tile(mask_buf[slot], blk * k_blk, k_blk)  # (V, K_BLK)
        sc = dot(qwin, k_buf[slot, pl.ds(0, k_blk), :], ((1,), (1,)))
        sc = jnp.where(maskf > 0, sc, _NEG)
        m_new = jnp.maximum(m_ref[...],
                            jnp.max(sc, axis=1, keepdims=True))  # (V, 1)
        alpha = jnp.exp(m_ref[...] - m_new)                      # (V, 1)
        p = jnp.exp(sc - m_new) * maskf                          # (V, K_BLK)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + dot(
            p, v_buf[slot, pl.ds(0, k_blk), :])                  # (V, DV)
        m_ref[...] = m_new
        return carry

    jax.lax.fori_loop(lo, hi, body, 0)

    @pl.when(last == 1)
    def _epilogue():
        # Normalize in-kernel.  Empty windows / fully masked rows keep
        # l = 0 → output 0, matching sparse_softmax ∘ SpMM.
        acc_ref[...] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-20)
        cp = pltpu.make_async_copy(
            acc_ref, o_hbm.at[pl.ds((h * num_windows + win) * v, v), :],
            sems.at[1, 4])
        cp.start()
        cp.wait()


@functools.partial(
    jax.jit, static_argnames=("num_windows", "k_blk", "interpret",
                              "direction"))
def _attn_call(steps, cols, mask, q3, k3, v3, *, num_windows, k_blk,
               interpret, direction=None):
    """Launch :func:`_attn_kernel` over ``steps`` (``(S, 5)``, see
    :func:`layout.window_steps`).  ``q3`` ``(1 | H, num_windows·V, D)``
    (scaled, window rows padded); ``k3``/``v3`` ``(1 | H, Mc, D | DV)``.
    Returns fp32 ``(H, num_windows·V, DV_pad)``; rows of windows no step
    stores are left unwritten.

    ``direction`` (``"fwd"``: the kernel samples A's pattern) stamps the
    kernel's ``kernel_metadata`` with it and
    :func:`attention_launch_counts`, which hold when the steps run every
    K-block once and store every window once.  Sharded launches, whose
    steps cover one device's share, pass none and carry no metadata."""
    require_fp32(interpret, q3, k3, v3)
    _, v = mask.shape
    hq, _, d = q3.shape
    hk, kv_rows, _ = k3.shape
    hv, _, dv = v3.shape
    h = max(hq, hk, hv)
    q3, k3 = (pad_cols(x.astype(jnp.float32), lane_tile(d, d))
              for x in (q3, k3))
    v3 = pad_cols(v3.astype(jnp.float32), lane_tile(dv, dv))
    width = chunks_per_block(k_blk) * LANES
    cols_rows = max(CACHE_ROWS, width // LANES + 1)
    kernel = functools.partial(
        _attn_kernel, k_blk=k_blk, v=v, kv_rows=kv_rows,
        num_windows=num_windows, q_batched=hq > 1, k_batched=hk > 1,
        v_batched=hv > 1)
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    dp, dvp = q3.shape[-1], v3.shape[-1]
    metadata = None
    if direction is not None:
        counts = attention_launch_counts(
            nnzp=cols.shape[0], num_windows=num_windows,
            num_steps=steps.shape[0], heads=h, d_pad=dp, dv_pad=dvp,
            k_blk=k_blk, v=v)
        metadata = {"op": "attention", "dir": direction,
                    **{k: str(c) for k, c in counts.items()}}
    return pl.pallas_call(
        kernel,
        grid=(h, steps.shape[0]),
        in_specs=[any_spec] * 6,
        out_specs=any_spec,
        out_shape=jax.ShapeDtypeStruct((h * num_windows * v, dvp),
                                       jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((v, dvp), jnp.float32),          # output accumulator
            pltpu.VMEM((v, 1), jnp.float32),            # running row max
            pltpu.VMEM((v, 1), jnp.float32),            # running row sum
            pltpu.VMEM((v, dp), jnp.float32),           # the window's Q
            pltpu.VMEM((2, sublanes(k_blk), dp), jnp.float32),   # K rows
            pltpu.VMEM((2, sublanes(k_blk), dvp), jnp.float32),  # V rows
            pltpu.VMEM((2, v, width), jnp.float32),     # mask chunks
            *smem_cache(),                              # step table
            *smem_cache(rows=cols_rows),                # cols
            pltpu.SemaphoreType.DMA((2, 5)),
        ],
        interpret=interpret,
        metadata=metadata,
    )(steps_table(steps), table(cols, cols_rows),
      vectors_on_lanes(mask, k_blk), q3.reshape(-1, dp),
      k3.reshape(-1, dp), v3.reshape(-1, dvp)).reshape(h, num_windows * v,
                                                       dvp)


def _attention(blocked, q, k, v, steps, *, scale, precision, interpret,
               num_windows=None):
    """Precision policy, scale folding and launch: ``(M, DV)`` or
    ``(H, M, DV)`` in ``v``'s (narrowed) dtype."""
    q, k, v = _cast_precision(precision, q, k, v)
    vsz = blocked.vector_size
    w = num_windows or blocked.num_windows
    m, _ = blocked.shape
    qb, kb, vb = q.ndim == 3, k.ndim == 3, v.ndim == 3
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    qs = (q.astype(jnp.float32) * scale).astype(q.dtype)
    q3 = qs if qb else qs[None]
    qpad = jnp.zeros((q3.shape[0], w * vsz, q.shape[-1]), q.dtype
                     ).at[:, : q3.shape[1], :].set(q3)
    out = _attn_call(steps, blocked.cols, blocked.mask, qpad,
                     k if kb else k[None], v if vb else v[None],
                     num_windows=w, k_blk=blocked.k_blk, interpret=interpret,
                     direction="fwd")
    out = out[:, :m, : v.shape[-1]].astype(v.dtype)
    return out if (qb or kb or vb) else out[0]


def attention_pallas(blocked, q: jax.Array, k: jax.Array, v: jax.Array, *,
                     scale=None, interpret: bool = True,
                     precision: str | None = None) -> jax.Array:
    """Single-pass fused sparse attention over a :class:`BlockedMEBCRS`.

    ``q (M, D)``, ``k (Mc, D)``, ``v (Mc, DV)`` — each optionally with a
    leading head dim H; any mix of per-head and shared operands runs in
    **one** ``(H, W)`` grid launch.  ``scale`` defaults to ``1/sqrt(D)``
    and may be a traced scalar (it is folded into Q before the kernel —
    the scores themselves never exist outside VMEM).  Returns ``(M, DV)``
    or ``(H, M, DV)`` in ``v`` dtype.  ``precision`` ("fp32"/"bf16")
    rounds Q/K/V before the launch; the online-softmax statistics and the
    output accumulator stay fp32 in VMEM either way (DESIGN.md §13).
    bf16 runs in interpret mode only: compiled, the kernel takes fp32.
    """
    return _attention(blocked, q, k, v, window_steps(blocked.win_ptr),
                      scale=scale, precision=precision, interpret=interpret)


# ---------------------------------------------------------------------------
# Load-balanced grid (DESIGN.md §11): the same kernel over a Schedule's
# uniform segments instead of ragged windows — a hub window's online
# softmax is split across several steps, each walking at most
# ``split_blk`` K-blocks.  The running statistics (row max ``m``, row sum
# ``l``) and the (V, DV) accumulator live in VMEM scratch, which persists
# across the sequential grid, so carrying them across the split segments
# of one window is the same per-block rescale in the same ascending order
# (bitwise-equal fp32): init on ``first``, normalize + store on ``last``.
# ---------------------------------------------------------------------------


def attention_pallas_balanced(blocked, q: jax.Array, k: jax.Array,
                              v: jax.Array, *, schedule=None,
                              split_blk: int = 1, scale=None,
                              interpret: bool = True,
                              precision: str | None = None) -> jax.Array:
    """Load-balanced single-pass fused sparse attention.

    Same contract as :func:`attention_pallas` — per-head or shared
    Q/K/V, traced ``scale`` folded into Q, one launch for any head count —
    but the grid runs over the :class:`~repro.core.format.Schedule`'s
    uniform segments with the online-softmax statistics carried across the
    split segments of each window.  Outputs are bitwise-equal to
    :func:`attention_pallas`.
    """
    if schedule is None:
        schedule = blocked.schedule(split_blk)
    return _attention(blocked, q, k, v,
                      schedule_steps(schedule.seg_win, schedule.seg_meta),
                      scale=scale, precision=precision, interpret=interpret)


def attention_pallas_staged(blocked, q: jax.Array, k: jax.Array,
                            v: jax.Array, *, scale=None, n_blk: int = 128,
                            f_blk: int = 128, interpret: bool = True,
                            precision: str | None = None) -> jax.Array:
    """3-dispatch baseline: SDDMM kernel → XLA sparse softmax → SpMM kernel.

    The (NNZP, V) score tensor is written to HBM by the SDDMM, re-read and
    re-written by the softmax, and re-read by the SpMM — the traffic the
    fused kernel eliminates.  Batched operands use the batched kernels, so
    fused-vs-staged comparisons isolate the *fusion* win, not batching.
    ``precision`` casts Q/K/V up front; the sparse softmax itself runs fp32
    on the scores and the probabilities ride at ``v``'s (cast) dtype.
    """
    from repro.core.sddmm import with_values
    from repro.core.softmax import sparse_softmax

    from .sddmm_pallas import sddmm_pallas
    from .spmm_pallas import spmm_pallas

    q, k, v = _cast_precision(precision, q, k, v)
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    scores = sddmm_pallas(blocked, q, k, f_blk=f_blk,
                                  interpret=interpret)
    probs = sparse_softmax(blocked, scores.astype(jnp.float32) * scale)
    return spmm_pallas(with_values(blocked, probs.astype(v.dtype)),
                               v, n_blk=n_blk, interpret=interpret)


def attention_hbm_bytes(blocked, d: int, dv: int, *, h: int = 1,
                        impl: str = "fused", value_bytes: int = 4,
                        schedule=None) -> int:
    """Modeled HBM bytes moved by one sparse-attention call under ``impl``.

    ``fused``: per head, the Q window tiles are read once, each sampled
    K row and V row is DMA'd exactly once per owning block, the f32 mask
    is read once per block, and the output is written once.  **No scores
    or probabilities tensor appears** — that is the entire difference.

    ``staged``: the 3-dispatch pipeline additionally writes the (NNZP, V)
    f32 scores (SDDMM epilogue), re-reads and re-writes them (sparse
    softmax, plus its segment-stats traffic), and re-reads the
    probabilities inside the SpMM — four extra score-sized HBM passes per
    head that the fused kernel keeps resident in VMEM.

    These are structural counts of a narrow layout, not of the committed
    kernels (:mod:`.layout`): each K-block's mask arrives as a whole
    128-lane chunk, Q/K/V are padded to 128 lanes, and every word is 4
    bytes whatever the precision.  Use them to compare paths, not as the
    bytes of a roofline share.
    """
    from .sddmm_pallas import sddmm_hbm_bytes
    from .spmm_pallas import spmm_hbm_bytes

    v = blocked.vector_size
    nnzp = int(blocked.cols.shape[0])
    w = blocked.num_windows
    meta = 4 * (w + 1) + 4 * nnzp                 # win_ptr + cols

    if impl in ("fused", "balanced"):
        q_bytes = w * v * d * value_bytes         # Q window tiles, once
        kv_pass = nnzp * (d + dv) * value_bytes   # K + V rows, once per block
        mask_bytes = nnzp * v * 4                 # f32 mask per block
        out_bytes = w * v * dv * value_bytes      # output written once
        total = h * (q_bytes + kv_pass + mask_bytes + out_bytes) + meta
        if impl == "balanced":
            # identical data movement; add the prefetched segment metadata
            sched = schedule if schedule is not None else blocked.schedule(1)
            total += 20 * sched.num_segments      # seg_win (4) + seg_meta (16)
        return total
    if impl == "staged":
        score_bytes = nnzp * v * 4                # fp32 (NNZP, V) in HBM
        softmax_pass = 2 * score_bytes + nnzp * v  # read + write + bool mask
        per_head = (sddmm_hbm_bytes(blocked, d, f_blk=d, impl="fused",
                                    value_bytes=value_bytes)
                    + softmax_pass
                    + spmm_hbm_bytes(blocked, dv, n_blk=dv, impl="fused",
                                     value_bytes=value_bytes))
        return h * per_head
    raise ValueError(f"unknown impl {impl!r}")


def attention_launch_counts(*, nnzp: int, num_windows: int, num_steps: int,
                            heads: int, d_pad: int, dv_pad: int, k_blk: int,
                            v: int = 8) -> dict:
    """What one launch of the committed attention kernel starts, from its
    static shapes: ``grid_steps``, ``dmas``, ``dma_bytes`` and
    ``mxu_macs``.

    Per head, each of the ``num_steps`` steps copies its window's
    ``(v, d_pad)`` Q tile; each of the ``nnzp / k_blk`` K-blocks starts
    one mask-chunk DMA of ``v × width × 4`` bytes (``width`` as in
    :func:`.spmm_pallas.spmm_launch_counts`) and ``k_blk`` K-row and
    ``k_blk`` V-row DMAs of ``d_pad × 4`` and ``dv_pad × 4`` bytes, and
    contracts ``v × k_blk × (d_pad + dv_pad)`` multiply-adds on the MXU
    (scores, then probabilities against V); each of the ``num_windows``
    windows is stored once, ``v × dv_pad × 4`` bytes.  This holds for
    step tables that run every K-block once and store every window once;
    the SMEM metadata refills are left out.
    """
    nb = nnzp // k_blk
    width = chunks_per_block(k_blk) * LANES
    return {
        "grid_steps": heads * num_steps,
        "dmas": heads * (num_steps + nb * (1 + 2 * k_blk) + num_windows),
        "dma_bytes": 4 * heads * (num_steps * v * d_pad
                                  + nb * (v * width
                                          + k_blk * (d_pad + dv_pad))
                                  + num_windows * v * dv_pad),
        "mxu_macs": heads * nb * v * k_blk * (d_pad + dv_pad),
    }
