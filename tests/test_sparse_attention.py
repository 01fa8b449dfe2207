"""Fused sparse-attention megakernel (DESIGN.md §10): parity + call log.

The single-pass SDDMM→softmax→SpMM kernel must match the staged
3-dispatch pipeline and the dense-softmax oracle — values and gradients,
fp32, including empty windows and ragged N — execute exactly one kernel
launch for any head count (dispatch call log), and model strictly less
HBM traffic than the staged path.
"""

import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import block_format, dispatch, from_dense
from repro.core.autodiff import ad_plan, attention_ad
from repro.kernels.ops import attention_hbm_bytes
from repro.models.layers import sparse_attention, sparse_attention_staged


def random_pattern(rng, m, density=0.3, empty_window=False, diag=True):
    pat = rng.random((m, m)) < density
    if diag:
        pat |= np.eye(m, dtype=bool)
    if empty_window and m >= 16:
        pat[8:16] = False  # a whole V=8 window with no nonzero vectors
    return pat


def dense_oracle(pat, q, k, v, scale):
    """Masked-softmax attention; rows with no pattern entries output 0
    (the sparse softmax's empty-row semantics)."""
    outs = []
    qs = q if q.ndim == 3 else q[None]
    ks = k if k.ndim == 3 else k[None]
    vs = v if v.ndim == 3 else v[None]
    for h in range(qs.shape[0]):
        s = jnp.where(jnp.asarray(pat), (qs[h] @ ks[h].T) * scale, -1e30)
        e = jax.nn.softmax(s, axis=-1) * jnp.asarray(pat)
        den = jnp.maximum(e.sum(axis=1, keepdims=True), 1e-20)
        outs.append((e / den) @ vs[h])
    out = jnp.stack(outs)
    return out if q.ndim == 3 else out[0]


@pytest.mark.parametrize("m,heads,density,empty", [
    (37, 1, 0.3, True),    # ragged N (last window partial) + empty window
    (40, 2, 0.3, True),
    (64, 4, 0.15, False),
    (16, 1, 0.5, False),
])
def test_fused_matches_staged_and_dense_oracle(m, heads, density, empty):
    rng = np.random.default_rng(0)
    pat = random_pattern(rng, m, density, empty_window=empty)
    plan = ad_plan(from_dense(pat.astype(np.float32), vector_size=8),
                   impl="pallas")
    d = 16
    shape = (heads, m, d) if heads > 1 else (m, d)
    q = jnp.asarray(rng.standard_normal(shape).astype(np.float32))
    k = jnp.asarray(rng.standard_normal(shape).astype(np.float32))
    v = jnp.asarray(rng.standard_normal(shape).astype(np.float32))
    scale = 1.0 / math.sqrt(d)

    fused = sparse_attention(plan, q, k, v, interpret=True)
    staged = sparse_attention_staged(plan, q, k, v, impl="pallas",
                                     interpret=True)
    oracle = dense_oracle(pat, q, k, v, scale)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(staged),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(oracle),
                               rtol=1e-4, atol=1e-4)


def test_fused_all_empty_pattern_returns_zeros():
    rng = np.random.default_rng(1)
    m, d = 24, 8
    plan = ad_plan(from_dense(np.zeros((m, m), np.float32), vector_size=8),
                   impl="pallas")
    q = jnp.asarray(rng.standard_normal((m, d)).astype(np.float32))
    out = sparse_attention(plan, q, q, q, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), 0.0)


def test_fused_gradients_match_staged_and_oracle():
    rng = np.random.default_rng(2)
    m, d, heads = 40, 8, 2
    pat = random_pattern(rng, m, 0.3, empty_window=True)
    plan = ad_plan(from_dense(pat.astype(np.float32), vector_size=8),
                   impl="pallas")
    q = jnp.asarray(rng.standard_normal((heads, m, d)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((heads, m, d)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((heads, m, d)).astype(np.float32))
    scale = 1.0 / math.sqrt(d)
    co = jnp.asarray(rng.standard_normal((heads, m, d)).astype(np.float32))

    def loss(fn, qq, kk, vv):
        return jnp.vdot(fn(qq, kk, vv), co)

    f_fused = lambda qq, kk, vv: sparse_attention(plan, qq, kk, vv,
                                                  interpret=True)
    f_staged = lambda qq, kk, vv: sparse_attention_staged(
        plan, qq, kk, vv, impl="pallas", interpret=True)
    f_oracle = lambda qq, kk, vv: dense_oracle(pat, qq, kk, vv, scale)

    g_f = jax.grad(lambda *a: loss(f_fused, *a), argnums=(0, 1, 2))(q, k, v)
    g_s = jax.grad(lambda *a: loss(f_staged, *a), argnums=(0, 1, 2))(q, k, v)
    g_o = jax.grad(lambda *a: loss(f_oracle, *a), argnums=(0, 1, 2))(q, k, v)
    for gf, gs, go in zip(g_f, g_s, g_o):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gs),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(gf), np.asarray(go),
                                   rtol=1e-4, atol=1e-4)


def test_fused_scale_is_differentiable():
    """AGNN's learned β enters as the scale — it must receive a cotangent
    through the fused path, matching the staged composition."""
    rng = np.random.default_rng(3)
    m, d = 32, 8
    pat = random_pattern(rng, m, 0.3)
    plan = ad_plan(from_dense(pat.astype(np.float32), vector_size=8),
                   impl="pallas")
    q = jnp.asarray(rng.standard_normal((m, d)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((m, d)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((m, d)).astype(np.float32))

    g_f = jax.grad(lambda s: attention_ad(plan, q, k, v, scale=s,
                                          interpret=True).sum())(
        jnp.float32(0.8))
    g_s = jax.grad(lambda s: sparse_attention_staged(
        plan, q, k, v, scale=s, impl="pallas",
        interpret=True).sum())(jnp.float32(0.8))
    np.testing.assert_allclose(float(g_f), float(g_s), rtol=1e-4)


@pytest.mark.parametrize("heads", [1, 4])
def test_fused_attention_is_one_launch(heads):
    """Acceptance criterion: H heads dispatch exactly one kernel — no
    per-head loop, no separate SDDMM/softmax/SpMM dispatches."""
    rng = np.random.default_rng(4)
    m, d = 32, 8
    pat = random_pattern(rng, m, 0.3)
    plan = ad_plan(from_dense(pat.astype(np.float32), vector_size=8),
                   impl="pallas")
    shape = (heads, m, d) if heads > 1 else (m, d)
    q = jnp.asarray(rng.standard_normal(shape).astype(np.float32))

    with dispatch.record_calls() as log:
        sparse_attention(plan, q, q, q, interpret=True)
    assert log == [("attention", "pallas_fused_attn")], log


def test_fused_backward_runs_batched_duality_kernels():
    """The recompute backward must execute the dispatched sparse kernels
    (batched grids for H > 1) — never a dense fallback."""
    rng = np.random.default_rng(5)
    m, d, heads = 32, 8, 2
    pat = random_pattern(rng, m, 0.3)
    plan = ad_plan(from_dense(pat.astype(np.float32), vector_size=8),
                   impl="pallas")
    q = jnp.asarray(rng.standard_normal((heads, m, d)).astype(np.float32))

    with dispatch.record_calls() as log:
        jax.grad(lambda qq: sparse_attention(plan, qq, q, q,
                                             interpret=True).sum())(q)
    assert log[0] == ("attention", "pallas_fused_attn"), log
    bwd = log[1:]
    assert bwd, "backward dispatched nothing"
    assert all(impl == "pallas" for _, impl in bwd), log
    assert {"spmm", "sddmm"} <= {op for op, _ in bwd}, log


def test_staged_blocked_impl_matches_pallas_paths():
    rng = np.random.default_rng(6)
    m, d = 40, 8
    pat = random_pattern(rng, m, 0.25, empty_window=True)
    fmt = from_dense(pat.astype(np.float32), vector_size=8)
    plan_p = ad_plan(fmt, impl="pallas")
    plan_b = ad_plan(fmt, impl="blocked")
    q = jnp.asarray(rng.standard_normal((m, d)).astype(np.float32))
    out_p = sparse_attention(plan_p, q, q, q, interpret=True)
    out_b = sparse_attention(plan_b, q, q, q)
    out_raw = sparse_attention(block_format(fmt, 8), q, q, q)
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_b),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(out_b), np.asarray(out_raw),
                               rtol=1e-6, atol=1e-6)


def test_attention_hbm_model_fused_strictly_below_staged():
    """The modeled-traffic acceptance criterion, at format level: fused
    moves strictly fewer bytes than the 3-dispatch staged pipeline for
    every (pattern, H) — scores/probs never round-trip HBM."""
    rng = np.random.default_rng(7)
    for m, density in [(16, 0.5), (40, 0.25), (64, 0.1)]:
        pat = random_pattern(rng, m, density)
        blocked = block_format(from_dense(pat.astype(np.float32),
                                          vector_size=8), 8)
        for h in (1, 4):
            fused = attention_hbm_bytes(blocked, 32, 32, h=h, impl="fused")
            staged = attention_hbm_bytes(blocked, 32, 32, h=h, impl="staged")
            assert fused < staged, (m, density, h, fused, staged)
