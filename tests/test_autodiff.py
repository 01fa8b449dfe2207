"""Gradient correctness for the custom_vjp sparse ops (DESIGN.md §9).

Every test checks ``jax.grad`` of ``spmm_ad``/``sddmm_ad`` — w.r.t. the
sparse values AND the dense operands — against the dense-oracle gradient,
fp32, including empty windows and ragged N.  The Pallas variants run in
interpret mode (CPU CI); the registry call log proves their backward
executed the fused transpose-SpMM/SDDMM kernels, not a dense fallback.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from _hypothesis_compat import given, settings, st  # degrades to skips

from repro.core import dispatch, from_dense
from repro.core.autodiff import ad_plan, sddmm_ad, spmm_ad
from repro.core.format import BlockedMEBCRS

IMPLS = ["blocked", "pallas"]


def random_sparse(rng, m, k, density, empty_window=False):
    a = rng.standard_normal((m, k)).astype(np.float32)
    a *= rng.random((m, k)) < density
    if empty_window and m >= 16:
        a[8:16] = 0.0  # a whole V=8 window with no nonzero vectors
    return a


def dense_scatter(plan, vals):
    """Dense (M, K) matrix from blocked-layout values — the oracle's view
    of the same function ``spmm_ad`` computes (mask ⊙ vals scattered)."""
    blocked = plan.fwd
    v = blocked.vector_size
    m, k = blocked.shape
    cols = np.asarray(blocked.cols)
    bw = np.asarray(blocked.block_win)
    t = np.arange(cols.shape[0])
    rows = bw[t // blocked.k_blk][:, None] * v + np.arange(v)[None, :]
    out = jnp.zeros((blocked.num_windows * v, k), jnp.float32)
    out = out.at[rows.reshape(-1), np.repeat(cols, v)].add(
        (vals * blocked.mask).reshape(-1))
    return out[:m]


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("m,k,n,empty", [
    (40, 36, 21, True),    # ragged N + empty window
    (64, 64, 32, False),
    (16, 48, 7, False),    # N < any tile
])
def test_spmm_ad_grads_match_dense_oracle(impl, m, k, n, empty):
    rng = np.random.default_rng(0)
    a = random_sparse(rng, m, k, 0.25, empty_window=empty)
    plan = ad_plan(from_dense(a, vector_size=8), impl=impl)
    b = jnp.asarray(rng.standard_normal((k, n)).astype(np.float32))
    co = jnp.asarray(rng.standard_normal((m, n)).astype(np.float32))

    def f(vals, bb):
        return jnp.vdot(spmm_ad(plan, vals, bb, interpret=True), co)

    def oracle(vals, bb):
        return jnp.vdot(dense_scatter(plan, vals) @ bb, co)

    gv, gb = jax.grad(f, argnums=(0, 1))(plan.vals, b)
    ov, ob = jax.grad(oracle, argnums=(0, 1))(plan.vals, b)
    np.testing.assert_allclose(np.asarray(gv), np.asarray(ov),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(gb), np.asarray(ob),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("impl", IMPLS)
def test_sddmm_ad_grads_match_dense_oracle(impl):
    rng = np.random.default_rng(1)
    m, mc, f = 40, 36, 13
    a = random_sparse(rng, m, mc, 0.25, empty_window=True)
    plan = ad_plan(from_dense(a, vector_size=8), impl=impl)
    q = jnp.asarray(rng.standard_normal((m, f)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((mc, f)).astype(np.float32))
    gs = jnp.asarray(rng.standard_normal(plan.vals.shape).astype(np.float32))
    amask = jnp.asarray((a != 0).astype(np.float32))

    def fn(qq, kk):
        return jnp.vdot(sddmm_ad(plan, qq, kk, interpret=True),
                        gs * plan.fwd.mask)

    def oracle(qq, kk):
        return jnp.vdot((qq @ kk.T) * amask, dense_scatter(plan, gs))

    gq, gk = jax.grad(fn, argnums=(0, 1))(q, k)
    oq, ok = jax.grad(oracle, argnums=(0, 1))(q, k)
    np.testing.assert_allclose(np.asarray(gq), np.asarray(oq),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(gk), np.asarray(ok),
                               rtol=1e-5, atol=1e-5)


def test_pallas_backward_runs_fused_kernels_not_dense():
    """The acceptance-criterion assertion: grad through the Pallas SpMM
    dispatches the fused transpose-SpMM (dB) and SDDMM (dVals) kernels —
    visible in the registry call log — rather than any dense fallback."""
    rng = np.random.default_rng(2)
    a = random_sparse(rng, 32, 32, 0.3)
    plan = ad_plan(from_dense(a, vector_size=8), impl="pallas")
    b = jnp.asarray(rng.standard_normal((32, 16)).astype(np.float32))

    with dispatch.record_calls() as log:
        jax.grad(lambda v, bb: spmm_ad(plan, v, bb, interpret=True).sum(),
                 argnums=(0, 1))(plan.vals, b)
    # forward spmm + backward transpose-spmm + backward sddmm, all pallas
    assert log.count(("spmm", "pallas")) == 2, log
    assert ("sddmm", "pallas") in log, log
    assert all(impl.startswith("pallas") for _, impl in log), log


@pytest.mark.parametrize("impl", IMPLS)
def test_spmm_ad_batched_leading_dim(impl):
    """(H, K, N) dense operand: forward and gradient equal the per-slice
    stack (per-head sparse attention's data flow)."""
    rng = np.random.default_rng(4)
    a = random_sparse(rng, 24, 24, 0.3)
    plan = ad_plan(from_dense(a, vector_size=8), impl=impl)
    b3 = jnp.asarray(rng.standard_normal((3, 24, 10)).astype(np.float32))

    out = spmm_ad(plan, plan.vals, b3, interpret=True)
    ref = jnp.stack([jnp.asarray(a) @ b3[i] for i in range(3)])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)

    gb = jax.grad(lambda x: spmm_ad(plan, plan.vals, x,
                                    interpret=True).sum())(b3)
    gref = jnp.broadcast_to(jnp.asarray(a.T @ np.ones((24, 10), np.float32)),
                            gb.shape)
    np.testing.assert_allclose(np.asarray(gb), np.asarray(gref),
                               rtol=1e-5, atol=1e-5)

    # batched vals (per-head probabilities) against the unbatched slices
    v3 = jnp.stack([plan.vals, 2.0 * plan.vals, 0.5 * plan.vals])
    out_v = spmm_ad(plan, v3, b3, interpret=True)
    ref_v = jnp.stack([spmm_ad(plan, v3[i], b3[i], interpret=True)
                       for i in range(3)])
    np.testing.assert_allclose(np.asarray(out_v), np.asarray(ref_v),
                               rtol=1e-5, atol=1e-5)


def test_sparse_attention_layer_trains_per_head():
    from repro.models.layers import sparse_attention

    rng = np.random.default_rng(5)
    seq, d, heads = 32, 8, 2
    pat = (rng.random((seq, seq)) < 0.3) | np.eye(seq, dtype=bool)
    plan = ad_plan(from_dense(pat.astype(np.float32), vector_size=8),
                   impl="pallas")
    q = jnp.asarray(rng.standard_normal((heads, seq, d)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((heads, seq, d)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((heads, seq, d)).astype(np.float32))

    out = sparse_attention(plan, q, k, v, interpret=True)
    assert out.shape == (heads, seq, d)

    # dense-masked oracle per head, values and grads
    def oracle(qq, kk, vv):
        outs = []
        for h in range(heads):
            s = (qq[h] @ kk[h].T) / np.sqrt(d)
            s = jnp.where(jnp.asarray(pat), s, -1e30)
            outs.append(jax.nn.softmax(s, axis=-1) @ vv[h])
        return jnp.stack(outs)

    np.testing.assert_allclose(np.asarray(out), np.asarray(oracle(q, k, v)),
                               rtol=1e-4, atol=1e-4)
    g = jax.grad(lambda qq: sparse_attention(plan, qq, k, v,
                                             interpret=True).sum())(q)
    go = jax.grad(lambda qq: oracle(qq, k, v).sum())(q)
    np.testing.assert_allclose(np.asarray(g), np.asarray(go),
                               rtol=1e-4, atol=1e-4)


def test_ad_plan_rejects_blocked_and_nondifferentiable():
    rng = np.random.default_rng(6)
    a = random_sparse(rng, 16, 16, 0.3)
    fmt = from_dense(a, vector_size=8)
    from repro.core import block_format

    with pytest.raises(ValueError, match="canonical"):
        ad_plan(block_format(fmt, 8))
    with pytest.raises(ValueError, match="not differentiable"):
        ad_plan(fmt, impl="pallas_staged")


@settings(max_examples=10, deadline=None)
@given(
    m=st.integers(1, 40),
    k=st.integers(1, 40),
    n=st.integers(1, 16),
    v=st.sampled_from([8, 16]),
    density=st.floats(0.0, 0.5),
    seed=st.integers(0, 2**31 - 1),
)
def test_spmm_ad_gradient_property(m, k, n, v, density, seed):
    """Property check (blocked impl for speed): ∂/∂B of sum(A@B) = Aᵀ·1
    and ∂/∂vals matches the masked sampled G·Bᵀ, any shape/sparsity."""
    rng = np.random.default_rng(seed)
    a = random_sparse(rng, m, k, density)
    plan = ad_plan(from_dense(a, vector_size=v), impl="blocked")
    b = jnp.asarray(rng.standard_normal((k, n)).astype(np.float32))
    gv, gb = jax.grad(lambda vv, bb: spmm_ad(plan, vv, bb).sum(),
                      argnums=(0, 1))(plan.vals, b)
    np.testing.assert_allclose(
        np.asarray(gb), a.T @ np.ones((m, n), np.float32),
        rtol=1e-4, atol=1e-4)
    # oracle gradient: (G Bᵀ) sampled where the pattern has true nonzeros
    sampled = np.ones((m, n), np.float32) @ np.asarray(b).T  # dense G·Bᵀ
    blocked = plan.fwd
    cols = np.asarray(blocked.cols)
    bw = np.asarray(blocked.block_win)
    t = np.arange(cols.shape[0])
    rows = bw[t // blocked.k_blk][:, None] * blocked.vector_size + \
        np.arange(blocked.vector_size)[None, :]
    rows = np.minimum(rows, m - 1)  # padding lanes: clamped, masked below
    ref = sampled[rows, cols[:, None]] * np.asarray(blocked.mask)
    np.testing.assert_allclose(np.asarray(gv), ref, rtol=1e-4, atol=1e-4)
