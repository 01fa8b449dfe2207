"""The program's own tracing (DESIGN.md §15): host spans, device-op tags
and the per-launch counts each SpMM, SDDMM and attention kernel carries.
CPU only; the kernels' metadata on a lowered TPU program is checked in
``test_tpu_compile.py``."""

import pickle

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ad_plan, block_format, from_dense, metrics
from repro.kernels.attention_pallas import attention_launch_counts
from repro.kernels.layout import (LANES, chunks_per_block, lane_tile,
                                  schedule_steps, window_steps)
from repro.kernels.sddmm_pallas import sddmm_launch_counts
from repro.kernels.spmm_pallas import spmm_launch_counts
from repro.models.gnn import GNNConfig, init_agnn, init_gcn, make_train_step

NEW_TAGS = ("fs.attn_recompute", "fs.sparse_softmax")


def skewed(rng, m=72, k=64):
    """Two hub rows over a sparse tail, with empty windows."""
    a = (rng.random((m, k)) < 0.05) * rng.standard_normal((m, k))
    a[:2] = rng.standard_normal((2, k))
    a[40:56] = 0.0
    return a.astype(np.float32)


def walk(steps, *, heads, n_pad, n_blk, k_blk, v):
    """The DMAs, bytes and multiply-adds of a launch, walked over its step
    table the way ``_spmm_kernel.copies`` and its store build them."""
    width = chunks_per_block(k_blk) * LANES
    out = dict(grid_steps=0, dmas=0, dma_bytes=0, mxu_macs=0)
    for _ in range(heads * (n_pad // n_blk)):
        for lo, length, _first, last, _win in np.asarray(steps):
            out["grid_steps"] += 1
            for _blk in range(lo, lo + length):
                out["dmas"] += 1 + k_blk            # value chunk + rows
                out["dma_bytes"] += 4 * (v * width + k_blk * n_blk)
                out["mxu_macs"] += v * k_blk * n_blk
            if last:
                out["dmas"] += 1                    # the window's store
                out["dma_bytes"] += 4 * v * n_blk
    return out


@pytest.mark.parametrize("grid", ["window", "balanced"])
@pytest.mark.parametrize("k_blk,n,heads", [(8, 128, 1), (12, 200, 2)])
def test_launch_counts_match_a_walk_of_the_steps(grid, k_blk, n, heads):
    fmt = from_dense(skewed(np.random.default_rng(k_blk)))
    blocked = block_format(fmt, k_blk)
    if grid == "window":
        steps = window_steps(blocked.win_ptr)
    else:
        sched = blocked.schedule(1)
        steps = schedule_steps(sched.seg_win, sched.seg_meta)
    n_blk = lane_tile(128, n)
    n_pad = -(-n // n_blk) * n_blk
    counts = spmm_launch_counts(
        nnzp=blocked.cols.shape[0], num_windows=blocked.num_windows,
        num_steps=steps.shape[0], heads=heads, n_pad=n_pad, k_blk=k_blk,
        n_blk=n_blk, v=blocked.vector_size)
    assert counts == walk(steps, heads=heads, n_pad=n_pad, n_blk=n_blk,
                          k_blk=k_blk, v=blocked.vector_size)
    # the MXU work is core/metrics' executed FLOPs, one head at a time
    executed = metrics.padded_flops(fmt, n_pad, k_blk)["executed_flops"]
    assert counts["mxu_macs"] == heads * executed / 2 > 0


def walk_attention(steps, *, heads, d_pad, dv_pad, k_blk, v):
    """The DMAs, bytes and multiply-adds of an attention launch, walked
    over its step table the way ``_attn_kernel`` builds them."""
    width = chunks_per_block(k_blk) * LANES
    out = dict(grid_steps=0, dmas=0, dma_bytes=0, mxu_macs=0)
    for _ in range(heads):
        for lo, length, _first, last, _win in np.asarray(steps):
            out["grid_steps"] += 1
            out["dmas"] += 1                        # the window's Q tile
            out["dma_bytes"] += 4 * v * d_pad
            for _blk in range(lo, lo + length):
                out["dmas"] += 1 + 2 * k_blk        # mask chunk, K, V rows
                out["dma_bytes"] += 4 * (v * width + k_blk * (d_pad + dv_pad))
                out["mxu_macs"] += v * k_blk * (d_pad + dv_pad)
            if last:
                out["dmas"] += 1                    # the window's store
                out["dma_bytes"] += 4 * v * dv_pad
    return out


@pytest.mark.parametrize("grid", ["window", "balanced"])
@pytest.mark.parametrize("k_blk,d,heads", [(8, 32, 1), (12, 200, 2)])
def test_attention_launch_counts_match_a_walk_of_the_steps(grid, k_blk, d,
                                                           heads):
    fmt = from_dense(skewed(np.random.default_rng(k_blk)))
    blocked = block_format(fmt, k_blk)
    if grid == "window":
        steps = window_steps(blocked.win_ptr)
    else:
        sched = blocked.schedule(1)
        steps = schedule_steps(sched.seg_win, sched.seg_meta)
    d_pad = dv_pad = lane_tile(d, d)
    counts = attention_launch_counts(
        nnzp=blocked.cols.shape[0], num_windows=blocked.num_windows,
        num_steps=steps.shape[0], heads=heads, d_pad=d_pad, dv_pad=dv_pad,
        k_blk=k_blk, v=blocked.vector_size)
    assert counts == walk_attention(steps, heads=heads, d_pad=d_pad,
                                    dv_pad=dv_pad, k_blk=k_blk,
                                    v=blocked.vector_size)


def walk_sddmm(blocked, *, heads, f_pad, f_blk):
    """The DMAs, bytes and multiply-adds of a whole-pattern SDDMM launch,
    walked over its ``(H, chunks, F / F_BLK)`` grid the way
    ``_sddmm_kernel`` and its block pipeline build them, with the
    pattern's own windows."""
    nnzp, v = blocked.mask.shape
    k_blk = blocked.k_blk
    win_ptr = np.asarray(blocked.win_ptr)
    block_win = np.asarray(blocked.block_win)
    num_chunks = -(-nnzp // LANES)
    nf = f_pad // f_blk
    out = dict(grid_steps=0, dmas=0, dma_bytes=0, mxu_macs=0)
    last_mask = None
    for _ in range(heads):
        for c in range(num_chunks):
            t0 = c * LANES
            if c != last_mask:                      # the mask block
                out["dmas"] += 1
                out["dma_bytes"] += 4 * v * LANES
                last_mask = c
            out["dmas"] += 1                        # the output block
            out["dma_bytes"] += 4 * v * LANES
            w_lo = block_win[min(t0, nnzp - 1) // k_blk]
            w_hi = block_win[min(t0 + LANES - 1, nnzp - 1) // k_blk]
            for _fi in range(nf):
                out["grid_steps"] += 1
                out["dmas"] += LANES                # sampled K rows
                out["dma_bytes"] += 4 * LANES * f_blk
                for w in range(w_lo, w_hi + 1):
                    if win_ptr[w] < win_ptr[w + 1]:  # the window's Q tile
                        out["dmas"] += 1
                        out["dma_bytes"] += 4 * v * f_blk
                        out["mxu_macs"] += v * LANES * f_blk
    return out


@pytest.mark.parametrize("k_blk,f,heads", [(8, 32, 1), (16, 200, 2),
                                           (8, 32, 3)])
def test_sddmm_launch_counts_match_a_walk_of_the_grid(k_blk, f, heads):
    """Exact where every window is nonempty and none ends on an inner chunk
    boundary; otherwise over by one Q tile per feature tile and head for
    each empty window and each such window end."""
    a = skewed(np.random.default_rng(k_blk + heads), m=160, k=96)
    blocked = block_format(from_dense(a), k_blk)
    f_blk = lane_tile(128, f)
    f_pad = -(-f // f_blk) * f_blk
    nnzp = blocked.cols.shape[0]
    num_chunks = -(-nnzp // LANES)
    counts = sddmm_launch_counts(
        nnzp=nnzp, num_windows=blocked.num_windows, num_chunks=num_chunks,
        heads=heads, f_pad=f_pad, f_blk=f_blk, v=blocked.vector_size)
    walked = walk_sddmm(blocked, heads=heads, f_pad=f_pad, f_blk=f_blk)
    win_ptr = np.asarray(blocked.win_ptr)
    ends = win_ptr[1:] * k_blk
    off = int(np.sum(win_ptr[1:] == win_ptr[:-1])) + int(np.sum(
        (ends % LANES == 0) & (ends > 0) & (ends < nnzp)))
    assert off > 0                      # the skewed pattern has empty windows
    over = heads * (f_pad // f_blk) * off
    v = blocked.vector_size
    assert counts["grid_steps"] == walked["grid_steps"]
    assert counts["dmas"] - walked["dmas"] == over
    assert counts["dma_bytes"] - walked["dma_bytes"] == over * 4 * v * f_blk
    assert counts["mxu_macs"] - walked["mxu_macs"] == over * v * LANES * f_blk


def test_ad_plan_spans_nest_under_it():
    metrics.reset_spans()
    fmt = from_dense(skewed(np.random.default_rng(0)))
    ad_plan(fmt, impl="pallas")
    rec = metrics.spans()
    assert rec["fs.ad_plan"]["count"] == 1
    assert rec["fs.ad_plan"]["parent"] is None
    for child in ("transpose", "block", "perm"):
        r = rec[f"fs.ad_plan.{child}"]
        assert r["parent"] == "fs.ad_plan" and r["count"] == 1
        assert 0 <= r["last_s"] <= r["max_s"] <= rec["fs.ad_plan"]["total_s"]


def test_span_record_stays_bounded():
    metrics.reset_spans()

    def calls(k):
        for _ in range(k):
            with metrics.span("fs.test.outer"), metrics.span("fs.test.inner"):
                pass

    calls(300)
    size = len(pickle.dumps(metrics.spans()))
    calls(1000)
    rec = metrics.spans()
    assert len(pickle.dumps(rec)) == size
    assert rec["fs.test.inner"]["count"] == 1300
    assert rec["fs.test.inner"]["parent"] == "fs.test.outer"
    metrics.reset_spans("fs.test.inner")
    assert set(metrics.spans()) == {"fs.test.outer"}
    with pytest.raises(ValueError, match="fs."):
        with metrics.span("outer"):
            pass
    metrics.reset_spans()


def test_gcn_step_tags_the_value_gather():
    rng = np.random.default_rng(3)
    n = 48
    plan = ad_plan(from_dense(skewed(rng, n, n)), impl="blocked")
    cfg = GNNConfig(in_dim=16, hidden_dim=16, num_classes=4, num_layers=2,
                    impl="blocked")
    params = init_gcn(jax.random.key(0), cfg)
    mom = jax.tree.map(jnp.zeros_like, params)
    x = jnp.ones((n, 16))
    labels = jnp.zeros((n,), jnp.int32)
    text = jax.jit(make_train_step(cfg)).lower(
        params, mom, plan, x, labels, jnp.ones((n,))).as_text(dialect="hlo")
    gathers = [ln for ln in text.splitlines() if " gather(" in ln
               and 'flashsparse_op="fs.transpose_vals"' in ln]
    assert len(gathers) == 1
    assert not any(f'flashsparse_op="{tag}"' in text for tag in NEW_TAGS)


def test_agnn_step_tags_the_recompute_and_the_softmax():
    """The fused attention's backward tags its recomputed scores
    ``fs.attn_recompute`` and the sparse softmax ``fs.sparse_softmax``;
    the rest of the backward carries neither: the duality SpMMs and the
    probabilities' SDDMM stay untagged, the value gathers keep
    ``fs.transpose_vals``."""
    rng = np.random.default_rng(4)
    n = 48
    plan = ad_plan(from_dense(skewed(rng, n, n)), impl="pallas")
    cfg = GNNConfig(model="agnn", in_dim=16, hidden_dim=16, num_classes=4,
                    num_layers=1, impl="pallas", interpret=True)
    params = init_agnn(jax.random.key(0), cfg)
    mom = jax.tree.map(jnp.zeros_like, params)
    x = jnp.ones((n, 16))
    labels = jnp.zeros((n,), jnp.int32)
    text = jax.jit(make_train_step(cfg)).lower(
        params, mom, plan, x, labels, jnp.ones((n,))).as_text(dialect="hlo")
    tags = {}
    for ln in text.splitlines():
        for tag in NEW_TAGS + ("fs.transpose_vals",):
            if f'flashsparse_op="{tag}"' in ln:
                tags[tag] = tags.get(tag, 0) + 1
    assert set(tags) == set(NEW_TAGS) | {"fs.transpose_vals"}
    # the interpreted kernels lower to loops: one SDDMM kernel carries the
    # recompute's tag, three SpMM and one SDDMM kernel carry none
    loops = [ln for ln in text.splitlines() if " while(" in ln]
    tagged = [ln for ln in loops if 'flashsparse_op="fs.attn_recompute"'
              in ln]
    assert tagged and len(tagged) < len(loops)
