"""Unified (op, impl) dispatch registry: resolution, flags, call log,
and the sparse-op sharding helpers."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import dispatch, from_dense, sddmm, spmm


def make_fmt(seed=0, m=40, k=36, density=0.25):
    rng = np.random.default_rng(seed)
    a = rng.standard_normal((m, k)).astype(np.float32)
    a *= rng.random((m, k)) < density
    return a, from_dense(a, vector_size=8)


def test_every_layer_resolves_the_same_table():
    """core.spmm/core.sddmm are thin shims over the registry: the impl
    lists match and unknown impls fail with the available set."""
    assert set(dispatch.impls("spmm")) == {
        "blocked", "coo_segment", "pallas", "pallas_balanced",
        "pallas_noncoalesced", "pallas_sharded", "pallas_sharded_overlap",
        "pallas_staged"}
    assert set(dispatch.impls("sddmm")) == {
        "blocked", "coo", "pallas", "pallas_balanced", "pallas_sharded",
        "pallas_sharded_overlap"}
    assert set(dispatch.impls("attention")) == {
        "blocked", "pallas_balanced", "pallas_fused_attn",
        "pallas_sharded", "pallas_sharded_overlap", "pallas_staged"}
    with pytest.raises(ValueError, match="unknown impl .* available"):
        dispatch.get("spmm", "nope")


def test_capability_flags():
    assert dispatch.get("spmm", "blocked").differentiable
    assert dispatch.get("spmm", "blocked").batched
    assert dispatch.get("spmm", "pallas").differentiable
    assert dispatch.get("spmm", "pallas").batched  # native (H, ...) grid
    assert dispatch.get("sddmm", "pallas").batched
    assert not dispatch.get("spmm", "pallas_staged").differentiable
    assert not dispatch.get("spmm", "pallas_staged").batched
    assert [f.name for f in dataclasses.fields(dispatch.OpImpl)] == [
        "op", "name", "fn", "differentiable", "batched", "load_balanced",
        "multi_device", "overlapped", "precisions"]
    with pytest.raises(ValueError, match="not differentiable"):
        dispatch.require("spmm", "pallas_staged", differentiable=True)
    with pytest.raises(ValueError, match="no native batched"):
        dispatch.require("spmm", "pallas_staged", batched=True)


def test_all_spmm_impls_agree():
    a, fmt = make_fmt()
    b = jnp.asarray(np.random.default_rng(1).standard_normal(
        (36, 16)).astype(np.float32))
    ref = a @ np.asarray(b)
    for impl in ("blocked", "pallas", "pallas_staged",
                 "pallas_noncoalesced", "coo_segment"):
        out = spmm(fmt, b, impl=impl, interpret=True)
        np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-4,
                                   atol=2e-4, err_msg=impl)


def test_call_log_records_dispatches():
    a, fmt = make_fmt(seed=2)
    b = jnp.ones((36, 8), jnp.float32)
    q = jnp.ones((40, 8), jnp.float32)
    with dispatch.record_calls() as log:
        spmm(fmt, b, impl="blocked")
        sddmm(fmt, q, jnp.ones((36, 8), jnp.float32), impl="pallas",
              interpret=True)
    assert log == [("spmm", "blocked"), ("sddmm", "pallas")]
    with dispatch.record_calls() as log2:
        pass
    assert log2 == []  # recorder scoped to its context


def test_gnn_train_step_validates_impl_capability():
    from repro.models.gnn import GNNConfig, make_train_step

    make_train_step(GNNConfig(impl="pallas"))  # differentiable: ok
    with pytest.raises(ValueError, match="not differentiable"):
        make_train_step(GNNConfig(impl="pallas_staged"))
    with pytest.raises(ValueError, match="unknown impl"):
        make_train_step(GNNConfig(impl="typo"))


def test_gnn_train_step_requires_plan_for_pallas():
    """A Pallas impl with a bare blocked adjacency must fail fast with the
    ad_plan remedy — not with a NotImplementedError deep in grad tracing."""
    from repro.core import block_format
    from repro.models.gnn import GNNConfig, init_gcn, make_train_step
    from repro.models.layers import sparse_attention

    a, fmt = make_fmt(seed=5, m=32, k=32)
    blocked = block_format(fmt, 8)
    cfg = GNNConfig(model="gcn", in_dim=8, hidden_dim=8, num_classes=3,
                    num_layers=2, impl="pallas", interpret=True)
    params = init_gcn(jax.random.key(0), cfg)
    mom = jax.tree.map(jnp.zeros_like, params)
    step = make_train_step(cfg, lr=0.1)
    x = jnp.ones((32, 8), jnp.float32)
    labels = jnp.zeros((32,), jnp.int32)
    mask = jnp.ones((32,), jnp.float32)
    with pytest.raises(ValueError, match="ADPlan"):
        step(params, mom, blocked, x, labels, mask)

    q = jnp.ones((32, 8), jnp.float32)
    with pytest.raises(ValueError, match="ADPlan"):
        sparse_attention(blocked, q, q, q, impl="pallas", interpret=True)


def test_sparse_sharding_helpers():
    from jax.sharding import Mesh, PartitionSpec as P

    from repro.core import block_format
    from repro.core.autodiff import ad_plan
    from repro.distributed.sharding import (
        sparse_format_shardings,
        sparse_operand_pspec,
    )

    _, fmt = make_fmt(seed=3)
    plan = ad_plan(fmt, impl="blocked")
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))
    sh = sparse_format_shardings(plan, mesh)
    for leaf in jax.tree.leaves(sh):
        assert leaf.spec == P()  # pattern metadata replicates
    sh_b = sparse_format_shardings(block_format(fmt, 8), mesh)
    assert all(s.spec == P() for s in jax.tree.leaves(sh_b))
    assert sparse_operand_pspec(mesh) == P(None, "model")
    assert sparse_operand_pspec(mesh, batched=True) == \
        P("data", None, "model")


# ---------------------------------------------------------------------------
# Graceful degradation (DESIGN.md §15): fallback ladder, strict mode,
# nonfinite guard
# ---------------------------------------------------------------------------


def test_fallback_chain_shape():
    chain = dispatch.fallback_chain("spmm", "pallas")
    assert chain[-1] == "coo_segment" and "blocked" in chain
    assert "pallas" not in chain  # rungs strictly below the requested impl
    # off-ladder impls enter at the default tier
    assert dispatch.fallback_chain("spmm", "pallas_noncoalesced")[0] == \
        "pallas"
    # sddmm "coo" returns edge values, not blocked layout: no fallback
    assert dispatch.fallback_chain("sddmm", "coo") == ()
    assert dispatch.fallback_for("sddmm", "coo") is None
    # every op's ladder terminates in a pure-XLA rung
    for op, first, last in (("spmm", "pallas", "coo_segment"),
                            ("sddmm", "pallas", "blocked"),
                            ("attention", "pallas_fused_attn", "blocked")):
        chain = dispatch.fallback_chain(op, first)
        assert chain and chain[-1] == last
        assert dispatch.fallback_for(op, first) is not None


def test_robust_dispatch_recovers_and_logs():
    a, fmt = make_fmt(seed=11)
    b = jnp.asarray(np.random.default_rng(3).standard_normal(
        (36, 16)).astype(np.float32))
    ref = a @ np.asarray(b)
    with pytest.warns(dispatch.FallbackWarning) as wlog:
        with dispatch.record_calls() as log:
            out = spmm(fmt, b, impl="pallas", n_blk=0, interpret=True,
                       strict=False)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-4, atol=2e-4)
    assert ("spmm", "fallback:pallas->blocked") in log
    assert len([w for w in wlog
                if issubclass(w.category, dispatch.FallbackWarning)]) == 1
    w = wlog[0].message
    assert w.op == "spmm" and w.requested == "pallas" and w.used == "blocked"
    assert w.failures and w.failures[0][0] == "pallas"


def test_robust_dispatch_strict_reraises():
    a, fmt = make_fmt(seed=12)
    b = jnp.ones((36, 8), jnp.float32)
    with pytest.raises(ZeroDivisionError):
        spmm(fmt, b, impl="pallas", n_blk=0, interpret=True, strict=True)


def test_robust_dispatch_never_swallows_validation_errors():
    from repro.core.validate import ValidationError
    from repro.testing.faults import corrupt_blocked

    from repro.core import block_format

    a, fmt = make_fmt(seed=13)
    bad = corrupt_blocked(block_format(fmt, 8), "oob_col")
    b = jnp.ones((36, 8), jnp.float32)
    with pytest.raises(ValidationError, match=r"\[col-in-bounds\]"):
        spmm(bad, b, impl="pallas", interpret=True, check="full",
             strict=False)


def test_guard_nonfinite_rescues_bf16_overflow():
    """3.3999e38 is finite in fp32 but rounds to inf in bf16: the guarded
    call re-runs at fp32 and matches the oracle; unguarded overflows."""
    rng = np.random.default_rng(14)
    m = k = 40
    a = (rng.random((m, k)) < 0.3) * rng.standard_normal((m, k))
    a = a.astype(np.float32)
    a[3, 5] = 3.3999e38
    fmt = from_dense(a)
    b = jnp.asarray(rng.standard_normal((k, 16)) * 1e-5, jnp.float32)
    bad = np.asarray(spmm(fmt, b, impl="blocked", precision="bf16"))
    assert not np.isfinite(bad).all()
    with pytest.warns(dispatch.FallbackWarning, match="non-finite"):
        out = spmm(fmt, b, impl="blocked", precision="bf16",
                   guard_nonfinite=True)
    assert out.dtype == jnp.float32
    ref = a.astype(np.float64) @ np.asarray(b, np.float64)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-4, atol=1e-12)


def test_guard_nonfinite_benign_passthrough():
    a, fmt = make_fmt(seed=15)
    b = jnp.ones((36, 8), jnp.float32)
    plain = spmm(fmt, b, impl="blocked", precision="bf16")
    guarded = spmm(fmt, b, impl="blocked", precision="bf16",
                   guard_nonfinite=True)
    # promoted dtype, identical numerics (the narrow pass was kept)
    assert guarded.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(plain, np.float32),
                                  np.asarray(guarded))


def test_ad_plan_guard_nonfinite():
    from repro.core import ad_plan, spmm_ad
    from repro.core import metrics as metrics_mod

    rng = np.random.default_rng(16)
    m = k = 32
    a = ((rng.random((m, k)) < 0.3)
         * rng.standard_normal((m, k))).astype(np.float32)
    a[3, 5] = 3.3999e38
    fmt = from_dense(a)
    b = jnp.asarray(rng.standard_normal((k, 16)) * 1e-5, jnp.float32)
    plan = ad_plan(fmt, impl="blocked", precision="bf16",
                   guard_nonfinite=True)
    metrics_mod.reset_counters("guard_nonfinite_rerun")
    out = spmm_ad(plan, plan.fwd.vals, b)
    assert out.dtype == jnp.float32 and bool(jnp.isfinite(out).all())
    assert metrics_mod.counters().get("guard_nonfinite_rerun", 0) >= 1
    # gradients stay the plain straight-through duality: dVals is finite;
    # dB legitimately overflows in the rows fed by the poisoned master
    # (the guard covers the forward only)
    g = jax.grad(lambda v, bb: spmm_ad(plan, v, bb).sum(),
                 argnums=(0, 1))(plan.fwd.vals, b)
    assert bool(jnp.isfinite(g[0]).all())
    finite_rows = np.isfinite(np.asarray(g[1])).all(axis=1)
    assert not finite_rows[5] and finite_rows.sum() >= k - 1
    # fp32/None plans ignore the flag entirely
    assert not ad_plan(fmt, impl="blocked",
                       guard_nonfinite=True).guard_nonfinite
