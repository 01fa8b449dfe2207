"""Block-parallel scheduling (DESIGN.md §11): Schedule invariants, bitwise
balanced-vs-window kernel parity (fwd + grad, batched, edge cases), the
skew-aware autotuner, and the all-empty zero-block path."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import (
    ad_plan,
    attention_ad,
    block_format,
    build_schedule,
    dispatch,
    from_dense,
    sddmm_ad,
    spmm,
    spmm_ad,
    window_skew,
)
from repro.kernels import ops
from repro.kernels.autotune import (
    SCHEMA_VERSION,
    AutotuneCache,
    TuneConfig,
    matrix_stats_key,
    tune_spmm,
)

SPLITS = (1, 2, 8)


def random_sparse(rng, m, k, density):
    a = rng.standard_normal((m, k)).astype(np.float32)
    a *= rng.random((m, k)) < density
    return a


def skewed_sparse(rng, m, k, hub_rows=2, hub_density=0.9, tail_density=0.05):
    """A few hub rows own most nonzeros — the §11 imbalance regime."""
    a = np.zeros((m, k), np.float32)
    a[:hub_rows] = (rng.standard_normal((hub_rows, k)).astype(np.float32)
                    * (rng.random((hub_rows, k)) < hub_density))
    tail = (rng.standard_normal((m - hub_rows, k)).astype(np.float32)
            * (rng.random((m - hub_rows, k)) < tail_density))
    a[hub_rows:] = tail
    return a


def make_blocked(a, v=8, k_blk=8):
    return block_format(from_dense(a, vector_size=v), k_blk=k_blk)


# ---------------------------------------------------------- invariants -----


@pytest.mark.parametrize("split_blk", list(SPLITS) + [0])
def test_schedule_round_trip_invariants(split_blk):
    """Every K-block of every window covered exactly once, in ascending
    contiguous order; flags mark window boundaries; empty windows get a
    single zero-length segment."""
    rng = np.random.default_rng(0)
    a = skewed_sparse(rng, 80, 64)
    a[24:40] = 0.0  # windows 3 and 4 empty
    blocked = make_blocked(a)
    sched = build_schedule(blocked, split_blk)
    wp = np.asarray(blocked.win_ptr)
    seg_win = np.asarray(sched.seg_win)
    meta = np.asarray(sched.seg_meta)

    assert sched.num_blocks == blocked.num_blocks == int(wp[-1])
    covered = []
    for w in range(blocked.num_windows):
        segs = np.nonzero(seg_win == w)[0]
        assert segs.size >= 1
        assert np.array_equal(segs, np.arange(segs[0], segs[-1] + 1)), \
            "segments of one window must be contiguous in grid order"
        lo, ln, first, last = meta[segs].T
        assert first[0] == 1 and last[-1] == 1
        assert np.all(first[1:] == 0) and np.all(last[:-1] == 0)
        if wp[w] == wp[w + 1]:  # empty window: one zero-length segment
            assert segs.size == 1 and ln[0] == 0
            continue
        if split_blk:
            assert np.all(ln <= split_blk) and np.all(ln >= 1)
        else:
            assert segs.size == 1  # unsplit: the window-parallel assignment
        blocks = np.concatenate([np.arange(l, l + n) for l, n in zip(lo, ln)])
        assert np.array_equal(blocks, np.arange(wp[w], wp[w + 1])), \
            "every K-block covered exactly once, ascending"
        covered.append(blocks)
    assert np.array_equal(np.concatenate(covered),
                          np.arange(int(wp[-1]))), \
        "windows ascend, so the segments walk every owned block in order"


def test_schedule_all_empty_is_zero_block():
    blocked = make_blocked(np.zeros((24, 24), np.float32))
    sched = build_schedule(blocked, 1)
    assert sched.num_blocks == 0           # valid zero-block schedule...
    assert sched.num_segments == 3         # ...one store-only seg per window
    assert np.all(np.asarray(sched.seg_meta)[:, 1] == 0)
    assert np.asarray(sched.seg_win).shape == (3,)


def test_schedule_memoized_on_blocked():
    blocked = make_blocked(random_sparse(np.random.default_rng(1), 32, 32, 0.3))
    assert blocked.schedule(2) is blocked.schedule(2)
    assert blocked.schedule(2) is not blocked.schedule(4)


def test_window_skew_statistic():
    rng = np.random.default_rng(2)
    uniform = from_dense(random_sparse(rng, 128, 128, 0.2), vector_size=8)
    skewed = from_dense(skewed_sparse(rng, 128, 128, tail_density=0.02),
                        vector_size=8)
    assert window_skew(uniform) < 2.0
    assert window_skew(skewed) > 3.0
    assert window_skew(from_dense(np.zeros((16, 16), np.float32))) == 1.0
    # transposed view of a hub-row matrix: its own (different) skew
    assert window_skew(skewed.transpose()) != window_skew(skewed)


# ------------------------------------------------------ kernel parity ------


@pytest.mark.parametrize("split_blk", SPLITS)
def test_spmm_balanced_bitwise_vs_fused(split_blk):
    rng = np.random.default_rng(3)
    a = skewed_sparse(rng, 72, 64)
    a[16:32] = 0.0  # empty windows between hubs
    blocked = make_blocked(a)
    for n, n_blk in [(48, 128), (33, 32), (1, 128)]:  # incl. ragged N
        b = jnp.asarray(rng.standard_normal((64, n)), dtype=jnp.float32)
        out_f = np.asarray(ops.spmm(blocked, b, n_blk=n_blk, interpret=True))
        out_b = np.asarray(ops.spmm_balanced(
            blocked, b, split_blk=split_blk, n_blk=n_blk, interpret=True))
        assert np.array_equal(out_f, out_b), (split_blk, n, n_blk)
        np.testing.assert_allclose(out_b, a @ np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("h", [1, 4])
def test_spmm_balanced_batched_bitwise(h):
    rng = np.random.default_rng(4)
    a = skewed_sparse(rng, 40, 48)
    blocked = make_blocked(a)
    b3 = jnp.asarray(rng.standard_normal((h, 48, 20)), dtype=jnp.float32)
    out_f = np.asarray(ops.spmm(blocked, b3, interpret=True))
    out_b = np.asarray(ops.spmm_balanced(blocked, b3, split_blk=2,
                                         interpret=True))
    assert out_b.shape == (h, 40, 20)
    assert np.array_equal(out_f, out_b)


def test_spmm_balanced_all_empty_returns_zeros():
    blocked = make_blocked(np.zeros((24, 24), np.float32))
    b = jnp.ones((24, 8), jnp.float32)
    out = np.asarray(ops.spmm_balanced(blocked, b, interpret=True))
    assert out.shape == (24, 8) and np.all(out == 0.0)


@pytest.mark.parametrize("split_blk", SPLITS)
def test_sddmm_balanced_bitwise_vs_fused(split_blk):
    rng = np.random.default_rng(5)
    a = skewed_sparse(rng, 40, 48)
    a[8:16] = 0.0
    blocked = make_blocked(a)
    q = jnp.asarray(rng.standard_normal((40, 33)), dtype=jnp.float32)
    k = jnp.asarray(rng.standard_normal((48, 33)), dtype=jnp.float32)
    out_f = np.asarray(ops.sddmm(blocked, q, k, f_blk=32, interpret=True))
    out_b = np.asarray(ops.sddmm_balanced(blocked, q, k,
                                          split_blk=split_blk, f_blk=32,
                                          interpret=True))
    assert np.array_equal(out_f, out_b)
    # batched: one (H, NSB, F/F_BLK) launch
    q3 = jnp.asarray(rng.standard_normal((3, 40, 16)), dtype=jnp.float32)
    out_f3 = np.asarray(ops.sddmm(blocked, q3, k[:, :16],
                                          interpret=True))
    out_b3 = np.asarray(ops.sddmm_balanced(blocked, q3, k[:, :16],
                                           split_blk=split_blk,
                                           interpret=True))
    assert np.array_equal(out_f3, out_b3)


def test_sddmm_balanced_all_empty_returns_zeros():
    blocked = make_blocked(np.zeros((16, 16), np.float32))
    q = jnp.ones((16, 8), jnp.float32)
    k = jnp.ones((16, 8), jnp.float32)
    out = np.asarray(ops.sddmm_balanced(blocked, q, k, interpret=True))
    assert out.shape == (blocked.num_blocks * 8, 8) and np.all(out == 0.0)


@pytest.mark.parametrize("split_blk", SPLITS)
@pytest.mark.parametrize("h", [1, 4])
def test_attention_balanced_bitwise_vs_fused(split_blk, h):
    """Segment-aware online softmax: running (m, l) carried across split
    segments of one window must reproduce the (H, W) megakernel bitwise."""
    rng = np.random.default_rng(6)
    a = skewed_sparse(rng, 40, 40)
    a[8:16] = 0.0  # empty windows → zero rows
    blocked = make_blocked(a)
    q = rng.standard_normal((h, 40, 16)).astype(np.float32) if h > 1 \
        else rng.standard_normal((40, 16)).astype(np.float32)
    k = jnp.asarray(rng.standard_normal((40, 16)), dtype=jnp.float32)
    v = jnp.asarray(rng.standard_normal((40, 12)), dtype=jnp.float32)
    q = jnp.asarray(q)
    out_f = np.asarray(ops.attention(blocked, q, k, v, interpret=True))
    out_b = np.asarray(ops.attention_balanced(
        blocked, q, k, v, split_blk=split_blk, interpret=True))
    assert np.array_equal(out_f, out_b)
    empty_rows = out_b[..., 8:16, :]
    assert np.all(empty_rows == 0.0)


def test_attention_balanced_all_empty_returns_zeros():
    blocked = make_blocked(np.zeros((16, 16), np.float32))
    x = jnp.ones((16, 8), jnp.float32)
    out = np.asarray(ops.attention_balanced(blocked, x, x, x,
                                            interpret=True))
    assert out.shape == (16, 8) and np.all(out == 0.0)


# ------------------------------------------------------ dispatch layer -----


def test_registry_flags_and_core_dispatch():
    for op in ("spmm", "sddmm", "attention"):
        entry = dispatch.get(op, "pallas_balanced")
        assert entry.load_balanced and entry.batched and entry.differentiable
    assert not dispatch.get("spmm", "pallas").load_balanced

    rng = np.random.default_rng(7)
    a = random_sparse(rng, 32, 32, 0.25)
    fmt = from_dense(a, vector_size=8)
    b = jnp.asarray(rng.standard_normal((32, 16)), dtype=jnp.float32)
    with dispatch.record_calls() as log:
        out = spmm(fmt, b, impl="pallas_balanced", split_blk=2,
                   interpret=True)
    assert log == [("spmm", "pallas_balanced")]
    np.testing.assert_allclose(np.asarray(out), a @ np.asarray(b),
                               rtol=2e-4, atol=2e-4)


# ------------------------------------------------------------ autodiff -----


@pytest.mark.parametrize("split_blk", SPLITS)
def test_spmm_ad_balanced_grads_match_dense_oracle(split_blk):
    rng = np.random.default_rng(8)
    a = skewed_sparse(rng, 32, 32)
    a[8:16] = 0.0
    plan = ad_plan(from_dense(a, vector_size=8), impl="pallas_balanced",
                   split_blk=split_blk)
    assert plan.fwd_sched is not None and plan.bwd_sched is not None
    assert plan.fwd_sched.split_blk == split_blk
    b = jnp.asarray(rng.standard_normal((32, 12)), dtype=jnp.float32)

    with dispatch.record_calls() as log:
        out = spmm_ad(plan, plan.vals, b, interpret=True)
        gv, gb = jax.grad(
            lambda v_, b_: spmm_ad(plan, v_, b_, interpret=True).sum(),
            argnums=(0, 1))(plan.vals, b)
    np.testing.assert_allclose(np.asarray(out), a @ np.asarray(b),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(gb),
                               a.T @ np.ones((32, 12), np.float32),
                               rtol=1e-5, atol=1e-5)
    # dVals via the balanced SDDMM, dB via the balanced transpose-SpMM
    assert log.count(("spmm", "pallas_balanced")) == 3, log
    assert ("sddmm", "pallas_balanced") in log, log
    # gv agrees with the plain-pallas plan (bitwise kernels → equal grads)
    plan_p = ad_plan(from_dense(a, vector_size=8), impl="pallas")
    gv_p = jax.grad(
        lambda v_: spmm_ad(plan_p, v_, b, interpret=True).sum())(plan_p.vals)
    np.testing.assert_allclose(np.asarray(gv), np.asarray(gv_p),
                               rtol=1e-6, atol=1e-6)


def test_spmm_ad_balanced_unsplit_plan_jits():
    """split_blk = 0 is the valid *unsplit* schedule, not "no schedule":
    the plan must still carry schedules so traced calls never rebuild one
    from tracer arrays."""
    rng = np.random.default_rng(14)
    a = random_sparse(rng, 32, 32, 0.3)
    plan = ad_plan(from_dense(a, vector_size=8), impl="pallas_balanced",
                   split_blk=0)
    assert plan.fwd_sched is not None and plan.fwd_sched.split_blk == 0
    assert plan.bwd_sched is not None
    b = jnp.asarray(rng.standard_normal((32, 8)), dtype=jnp.float32)
    out = jax.jit(lambda p, v_, b_: spmm_ad(p, v_, b_, interpret=True))(
        plan, plan.vals, b)
    np.testing.assert_allclose(np.asarray(out), a @ np.asarray(b),
                               rtol=2e-4, atol=2e-4)


def test_sddmm_ad_balanced_grads(interpret=True):
    rng = np.random.default_rng(9)
    a = skewed_sparse(rng, 32, 32)
    plan = ad_plan(from_dense(a, vector_size=8), impl="pallas_balanced",
                   split_blk=2)
    q = jnp.asarray(rng.standard_normal((32, 10)), dtype=jnp.float32)
    k = jnp.asarray(rng.standard_normal((32, 10)), dtype=jnp.float32)
    amask = jnp.asarray((a != 0).astype(np.float32))

    def fn(qq, kk):
        return (sddmm_ad(plan, qq, kk, interpret=interpret) ** 2).sum()

    def oracle(qq, kk):
        return (((qq @ kk.T) * amask) ** 2).sum()

    with dispatch.record_calls() as log:
        gq, gk = jax.grad(fn, argnums=(0, 1))(q, k)
    oq, ok = jax.grad(oracle, argnums=(0, 1))(q, k)
    np.testing.assert_allclose(np.asarray(gq), np.asarray(oq),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(gk), np.asarray(ok),
                               rtol=1e-4, atol=1e-4)
    assert all(impl == "pallas_balanced" for _, impl in log), log


@pytest.mark.parametrize("h", [1, 4])
def test_attention_ad_balanced_fwd_and_grads(h):
    rng = np.random.default_rng(10)
    a = skewed_sparse(rng, 24, 24)
    fmt = from_dense(a, vector_size=8)
    plan = ad_plan(fmt, impl="pallas_balanced", split_blk=2)
    plan_p = ad_plan(fmt, impl="pallas")
    shape_q = (h, 24, 8) if h > 1 else (24, 8)
    q = jnp.asarray(rng.standard_normal(shape_q), dtype=jnp.float32)
    k = jnp.asarray(rng.standard_normal((24, 8)), dtype=jnp.float32)
    v = jnp.asarray(rng.standard_normal((24, 8)), dtype=jnp.float32)

    with dispatch.record_calls() as log:
        out = attention_ad(plan, q, k, v, interpret=True)
    assert ("attention", "pallas_balanced") in log, log
    out_p = attention_ad(plan_p, q, k, v, interpret=True)
    assert np.array_equal(np.asarray(out), np.asarray(out_p))

    def loss(pl_, qq, kk, vv):
        return (attention_ad(pl_, qq, kk, vv, interpret=True) ** 2).sum()

    with dispatch.record_calls() as log2:
        g = jax.grad(loss, argnums=(1, 2, 3))(plan, q, k, v)
    g_p = jax.grad(loss, argnums=(1, 2, 3))(plan_p, q, k, v)
    for gb, gp in zip(g, g_p):
        np.testing.assert_allclose(np.asarray(gb), np.asarray(gp),
                                   rtol=1e-5, atol=1e-6)
    bwd = [(op, impl) for op, impl in log2 if op in ("spmm", "sddmm")]
    assert bwd and all(impl == "pallas_balanced" for _, impl in bwd), log2


# ------------------------------------------------------------ autotuner ----


def test_tuneconfig_roundtrip_and_stale_schema_discard(tmp_path):
    import json

    path = str(tmp_path / "tune.json")
    # a v2-era file (no split_blk/precision, old schema tag) must be
    # discarded wholesale — its buckets no longer mean the same thing
    with open(path, "w") as f:
        json.dump({"schema": 2, "configs": {"stale": {
            "k_blk": 8, "n_blk": 64, "median_ms": 1.0}}}, f)
    cache = AutotuneCache(path)
    assert cache.get("stale") is None
    assert SCHEMA_VERSION == 6

    cfg = TuneConfig(k_blk=8, n_blk=64, median_ms=0.5, split_blk=2,
                     precision="bf16", overlap_batches=2)
    cache.put("k", cfg)
    assert AutotuneCache(path).get("k") == cfg
    with open(path) as f:
        raw = json.load(f)
    assert raw["schema"] == 6
    assert raw["configs"]["k"]["split_blk"] == 2
    assert raw["configs"]["k"]["precision"] == "bf16"
    assert raw["configs"]["k"]["overlap_batches"] == 2


def test_stats_key_has_skew_bucket():
    """Hub-row and uniform matrices of the same size/density land in
    different tuning buckets (exercised through the synthetic sparse
    generators the skewed benchmarks are built on)."""
    from repro.data.synthetic import (
        synthetic_sparse_coo,
        synthetic_sparse_format,
    )

    uniform = synthetic_sparse_format(512, 8.0, kind="uniform", seed=0)
    skewed = synthetic_sparse_format(512, 8.0, kind="hub_row", skew=2.0,
                                     seed=0)
    assert window_skew(skewed) > 2 * window_skew(uniform)
    ku = matrix_stats_key(uniform, 64, "spmm", interpret=True)
    ks = matrix_stats_key(skewed, 64, "spmm", interpret=True)
    assert "sk" in ku
    assert ku.split("|") != ks.split("|"), "skewed and uniform matrices " \
        "must not share a tuning bucket"
    # deterministic regeneration: pure function of (args, seed)
    r1 = synthetic_sparse_coo(256, 4.0, kind="hub_row", skew=1.5, seed=3)
    r2 = synthetic_sparse_coo(256, 4.0, kind="hub_row", skew=1.5, seed=3)
    for x, y in zip(r1[:3], r2[:3]):
        assert np.array_equal(x, y)


def test_tune_spmm_sweeps_split_and_matches_oracle(tmp_path):
    rng = np.random.default_rng(12)
    a = skewed_sparse(rng, 48, 48)
    fmt = from_dense(a, vector_size=8)
    b = jnp.asarray(rng.standard_normal((48, 32)), dtype=jnp.float32)
    cache = AutotuneCache(str(tmp_path / "tune.json"))
    cfg = tune_spmm(fmt, b, k_blks=(8,), n_blks=(32,), split_blks=(0, 1, 8),
                    interpret=True, reps=1, cache=cache)
    assert cfg.split_blk in (0, 1, 8)
    out = ops.spmm_tuned(fmt, b, interpret=True, cache=cache, k_blks=(8,),
                         n_blks=(32,))
    np.testing.assert_allclose(np.asarray(out), a @ np.asarray(b),
                               rtol=2e-4, atol=2e-4)


# ------------------------------------------------------------ HBM model ----


def test_balanced_hbm_model_matches_fused_plus_metadata():
    rng = np.random.default_rng(13)
    blocked = make_blocked(skewed_sparse(rng, 64, 64))
    sched = blocked.schedule(1)
    fused = ops.spmm_hbm_bytes(blocked, 128, impl="fused")
    bal = ops.spmm_hbm_bytes(blocked, 128, impl="balanced", schedule=sched)
    assert bal == fused + 20 * sched.num_segments
    a_f = ops.attention_hbm_bytes(blocked, 32, 32, impl="fused")
    a_b = ops.attention_hbm_bytes(blocked, 32, 32, impl="balanced",
                                  schedule=sched)
    assert a_b == a_f + 20 * sched.num_segments
