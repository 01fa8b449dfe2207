"""Batched (H, ...) Pallas grids vs the per-slice loop (DESIGN.md §10).

The batched SpMM/SDDMM kernels run the same per-cell arithmetic as the
single-head kernels, so stacking H per-slice launches must reproduce the
batched launch **bitwise** (fp32, interpret mode) — forward and, for
batched operands, gradients too.  The dispatch call log proves H heads
cost exactly one kernel launch through the autodiff layer.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import block_format, dispatch, from_dense
from repro.core.autodiff import ad_plan, sddmm_ad, spmm_ad
from repro.core.sddmm import with_values
from repro.kernels.sddmm_pallas import sddmm_pallas
from repro.kernels.spmm_pallas import spmm_pallas


def make_blocked(rng, m=40, k=36, density=0.25, empty_window=True):
    a = rng.standard_normal((m, k)).astype(np.float32)
    a *= rng.random((m, k)) < density
    if empty_window and m >= 16:
        a[8:16] = 0.0
    return a, block_format(from_dense(a, vector_size=8), 8)


@pytest.mark.parametrize("h", [1, 4])
def test_spmm_batched_bitwise_vs_per_slice(h):
    rng = np.random.default_rng(0)
    _, blocked = make_blocked(rng)
    b3 = jnp.asarray(rng.standard_normal((h, 36, 21)).astype(np.float32))
    v3 = jnp.stack([(1.0 + i) * blocked.vals for i in range(h)])

    # both operands per-head
    out = spmm_pallas(with_values(blocked, v3), b3, interpret=True)
    ref = jnp.stack([spmm_pallas(with_values(blocked, v3[i]), b3[i],
                                 interpret=True) for i in range(h)])
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    # shared vals / shared b (no HBM broadcast, slice-0 reads)
    out_sv = spmm_pallas(blocked, b3, interpret=True)
    ref_sv = jnp.stack([spmm_pallas(blocked, b3[i], interpret=True)
                        for i in range(h)])
    np.testing.assert_array_equal(np.asarray(out_sv), np.asarray(ref_sv))
    out_sb = spmm_pallas(with_values(blocked, v3), b3[0], interpret=True)
    ref_sb = jnp.stack([spmm_pallas(with_values(blocked, v3[i]), b3[0],
                                    interpret=True) for i in range(h)])
    np.testing.assert_array_equal(np.asarray(out_sb), np.asarray(ref_sb))


@pytest.mark.parametrize("h", [1, 4])
def test_sddmm_batched_bitwise_vs_per_slice(h):
    rng = np.random.default_rng(1)
    _, blocked = make_blocked(rng)
    q3 = jnp.asarray(rng.standard_normal((h, 40, 13)).astype(np.float32))
    k3 = jnp.asarray(rng.standard_normal((h, 36, 13)).astype(np.float32))

    out = sddmm_pallas(blocked, q3, k3, interpret=True)
    ref = jnp.stack([sddmm_pallas(blocked, q3[i], k3[i], interpret=True)
                     for i in range(h)])
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    out_sk = sddmm_pallas(blocked, q3, k3[0], interpret=True)
    ref_sk = jnp.stack([sddmm_pallas(blocked, q3[i], k3[0], interpret=True)
                        for i in range(h)])
    np.testing.assert_array_equal(np.asarray(out_sk), np.asarray(ref_sk))


def test_batched_unbatched_inputs_fall_through():
    rng = np.random.default_rng(2)
    _, blocked = make_blocked(rng)
    b = jnp.asarray(rng.standard_normal((36, 10)).astype(np.float32))
    out = spmm_pallas(blocked, b, interpret=True)
    assert out.shape == (40, 10)
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(spmm_pallas(blocked, b[None],
                                                interpret=True)[0]))


@pytest.mark.parametrize("h", [1, 4])
def test_spmm_ad_batched_one_launch_fwd_and_grad(h):
    """H heads through spmm_ad = ONE (H, N/N_BLK, W) launch, forward and
    each backward duality op; results/grads bitwise vs the per-slice
    composition for per-head operands."""
    rng = np.random.default_rng(3)
    a, _ = make_blocked(rng, m=32, k=32)
    plan = ad_plan(from_dense(a, vector_size=8), impl="pallas")
    b3 = jnp.asarray(rng.standard_normal((h, 32, 10)).astype(np.float32))

    with dispatch.record_calls() as log:
        out = spmm_ad(plan, plan.vals, b3, interpret=True)
    assert log == [("spmm", "pallas")], log

    ref = jnp.stack([spmm_ad(plan, plan.vals, b3[i], interpret=True)
                     for i in range(h)])
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    with dispatch.record_calls() as log:
        gb = jax.grad(lambda x: spmm_ad(plan, plan.vals, x,
                                        interpret=True).sum())(b3)
    # fwd spmm + bwd transpose-spmm + bwd sddmm: one batched launch each
    assert log.count(("spmm", "pallas")) == 2, log
    assert log.count(("sddmm", "pallas")) == 1, log
    assert len(log) == 3, log

    gb_ref = jnp.stack([jax.grad(lambda x: spmm_ad(
        plan, plan.vals, x, interpret=True).sum())(b3[i]) for i in range(h)])
    np.testing.assert_array_equal(np.asarray(gb), np.asarray(gb_ref))


@pytest.mark.parametrize("h", [1, 4])
def test_sddmm_ad_batched_one_launch_fwd_and_grad(h):
    rng = np.random.default_rng(4)
    a, _ = make_blocked(rng, m=32, k=32)
    plan = ad_plan(from_dense(a, vector_size=8), impl="pallas")
    q3 = jnp.asarray(rng.standard_normal((h, 32, 12)).astype(np.float32))
    k3 = jnp.asarray(rng.standard_normal((h, 32, 12)).astype(np.float32))

    with dispatch.record_calls() as log:
        out = sddmm_ad(plan, q3, k3, interpret=True)
    assert log == [("sddmm", "pallas")], log
    ref = jnp.stack([sddmm_ad(plan, q3[i], k3[i], interpret=True)
                     for i in range(h)])
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    with dispatch.record_calls() as log:
        gq, gk = jax.grad(lambda qq, kk: sddmm_ad(
            plan, qq, kk, interpret=True).sum(), argnums=(0, 1))(q3, k3)
    # fwd sddmm + bwd dQ spmm + bwd dK transpose-spmm
    assert log.count(("sddmm", "pallas")) == 1, log
    assert log.count(("spmm", "pallas")) == 2, log
    assert len(log) == 3, log

    g_ref = [jax.grad(lambda qq, kk: sddmm_ad(
        plan, qq, kk, interpret=True).sum(), argnums=(0, 1))(q3[i], k3[i])
        for i in range(h)]
    np.testing.assert_array_equal(
        np.asarray(gq), np.asarray(jnp.stack([g[0] for g in g_ref])))
    np.testing.assert_array_equal(
        np.asarray(gk), np.asarray(jnp.stack([g[1] for g in g_ref])))


def test_shared_operand_grad_matches_per_slice_sum():
    """Shared (2-D) operands get a summed cotangent over heads — equal to
    the per-slice sum up to fp32 summation order (allclose, not bitwise)."""
    rng = np.random.default_rng(5)
    a, _ = make_blocked(rng, m=32, k=32)
    plan = ad_plan(from_dense(a, vector_size=8), impl="pallas")
    h = 3
    b3 = jnp.asarray(rng.standard_normal((h, 32, 10)).astype(np.float32))

    gv = jax.grad(lambda vv: spmm_ad(plan, vv, b3,
                                     interpret=True).sum())(plan.vals)
    gv_ref = sum(jax.grad(lambda vv: spmm_ad(
        plan, vv, b3[i], interpret=True).sum())(plan.vals) for i in range(h))
    np.testing.assert_allclose(np.asarray(gv), np.asarray(gv_ref),
                               rtol=1e-5, atol=1e-6)


def test_batched_registry_flags():
    assert dispatch.get("spmm", "pallas").batched
    assert dispatch.get("spmm", "pallas").differentiable
    assert dispatch.get("sddmm", "pallas").batched
    assert dispatch.get("attention", "pallas_fused_attn").batched
    assert dispatch.get("attention", "pallas_fused_attn").differentiable
    assert not dispatch.get("attention", "pallas_staged").differentiable
    assert "pallas_fused_attn" in dispatch.impls("attention")
