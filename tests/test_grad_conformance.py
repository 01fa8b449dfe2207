"""Gradient conformance of the path the benchmark cells train through.

``jax.grad`` through ``ad_plan(fmt, impl="pallas")`` — the Pallas kernels
in interpret mode, forward and the transpose-SpMM / SDDMM duality
backward (DESIGN.md §9) — must match the same plan at ``impl="blocked"``
(the XLA reference) on every vendored real matrix (``tests/data``).  The
set has hub rows (a hub window of Aᵀ, re-layout overflow), dense blocks
(every re-layout level kept), bands and meshes, which the synthetic
patterns of ``test_autodiff`` / ``test_relayout`` do not reach.

  * ``spmm_ad``: gradients w.r.t. the values and the dense operand, all
    matrices;
  * ``sddmm_ad``: gradients w.r.t. ``q`` and ``k``, all matrices;
  * ``attention_ad``: gradients w.r.t. ``q``, ``k`` and ``v``, the square
    matrices.

Tolerances follow ``test_autodiff`` (SpMM/SDDMM) and
``test_sparse_attention`` (attention against the staged composition).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.autodiff import ad_plan, attention_ad, sddmm_ad, spmm_ad
from repro.data.datasets import load_vendored

N_FEAT = 16

_SAMPLES = {s.name: s for s in load_vendored()}
_SQUARE = sorted(n for n, s in _SAMPLES.items() if s.is_square)
_FMTS = {}


def _plans(name):
    """(pallas plan, blocked plan) on one format instance: both share the
    blocked layouts, so one value array feeds either."""
    if name not in _FMTS:
        _FMTS[name] = _SAMPLES[name].to_format(vector_size=8)
    fmt = _FMTS[name]
    return ad_plan(fmt, impl="pallas"), ad_plan(fmt, impl="blocked")


def _dense(rng, *shape):
    return jnp.asarray(rng.standard_normal(shape).astype(np.float32))


def _assert_grads_match(got, want, rtol, atol):
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=rtol, atol=atol)


def _spmm_case(name):
    plan_p, plan_b = _plans(name)
    m, k = plan_p.shape
    rng = np.random.default_rng(0)
    b, co = _dense(rng, k, N_FEAT), _dense(rng, m, N_FEAT)

    def grads(plan):
        f = lambda vals, bb: jnp.vdot(
            spmm_ad(plan, vals, bb, interpret=True), co)
        return jax.jit(jax.grad(f, argnums=(0, 1)))(plan.vals, b)

    _assert_grads_match(grads(plan_p), grads(plan_b), 1e-5, 1e-5)


def _sddmm_case(name):
    plan_p, plan_b = _plans(name)
    m, k = plan_p.shape
    rng = np.random.default_rng(1)
    q, kk = _dense(rng, m, N_FEAT), _dense(rng, k, N_FEAT)
    gs = _dense(rng, *plan_p.vals.shape) * plan_p.mask

    def grads(plan):
        f = lambda qq, kx: jnp.vdot(
            sddmm_ad(plan, qq, kx, interpret=True), gs)
        return jax.jit(jax.grad(f, argnums=(0, 1)))(q, kk)

    _assert_grads_match(grads(plan_p), grads(plan_b), 1e-5, 1e-5)


def _attention_case(name):
    plan_p, plan_b = _plans(name)
    m, _ = plan_p.shape
    rng = np.random.default_rng(2)
    q, kk, v, co = (_dense(rng, m, N_FEAT) for _ in range(4))

    def grads(plan):
        f = lambda qq, kx, vv: jnp.vdot(
            attention_ad(plan, qq, kx, vv, interpret=True), co)
        return jax.jit(jax.grad(f, argnums=(0, 1, 2)))(q, kk, v)

    _assert_grads_match(grads(plan_p), grads(plan_b), 1e-5, 1e-5)


_CASES = {"spmm_ad": _spmm_case, "sddmm_ad": _sddmm_case,
          "attention_ad": _attention_case}


@pytest.mark.parametrize("op,name", (
    [("spmm_ad", n) for n in sorted(_SAMPLES)]
    + [("sddmm_ad", n) for n in sorted(_SAMPLES)]
    + [("attention_ad", n) for n in _SQUARE]))
def test_pallas_grads_match_blocked_on_vendored_matrix(op, name):
    _CASES[op](name)
