"""The program's own tracing (DESIGN.md §15): host spans, device-op tags
and the per-launch counts each SpMM kernel carries.  CPU only; the
kernels' metadata on a lowered TPU program is checked in
``test_tpu_compile.py``."""

import pickle

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ad_plan, block_format, from_dense, metrics
from repro.kernels.layout import (LANES, chunks_per_block, lane_tile,
                                  schedule_steps, window_steps)
from repro.kernels.spmm_pallas import spmm_launch_counts
from repro.models.gnn import GNNConfig, init_gcn, make_train_step


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
