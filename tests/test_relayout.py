"""The value re-layout of the transpose plan (DESIGN.md §9).

``ADPlan.transpose_vals`` re-lays forward-layout values into Aᵀ's layout
through a per-vector map: a gather of one value per Aᵀ vector and kept
level, a ``where`` onto the vector's slots, and a scatter of the overflow.
These tests hold it bitwise to a plain NumPy re-layout built from the COO
handed to ``from_coo`` (a dictionary from matrix element to slot on each
side), check the counters the plan build records, and check that the
differentiable ops' gradients equal those of a run through the reference
re-layout.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import from_coo, metrics
from repro.core.autodiff import (ADPlan, ad_plan, attention_ad, sddmm_ad,
                                 spmm_ad)

V = 8


def power_law(rng):
    """Mostly single entries per 8-wide row segment, a hub column."""
    a = (rng.random((96, 96)) < 0.02).astype(np.float32)
    a[:, 5] = 1.0
    return a


def uniform(rng):
    return (rng.random((120, 104)) < 0.02).astype(np.float32)


def dense_tiles(rng):
    """Dense 8x8 tiles on the block diagonal: every Aᵀ vector is full."""
    return np.kron(np.eye(6), np.ones((V, V))).astype(np.float32)


def overflow(rng):
    """Sparse uniform plus one dense tile: a few full vectors among many
    single ones, so their entries past level 0 go to the scatter."""
    a = (rng.random((96, 96)) < 0.02).astype(np.float32)
    a[16:24, 40:48] = 1.0
    return a


PATTERNS = {"power_law": power_law, "uniform": uniform,
            "dense_tiles": dense_tiles, "overflow": overflow}


def make_plan(pattern, seed=0):
    rng = np.random.default_rng(seed)
    a = PATTERNS[pattern](rng)
    rows, cols = np.nonzero(a)
    vals = rng.standard_normal(rows.size).astype(np.float32)
    fmt = from_coo(rows, cols, vals, a.shape, vector_size=V)
    return ad_plan(fmt, impl="pallas"), rows, cols


def slots(blocked):
    """``{(row, col): flat slot}`` over the mask-true entries."""
    mask = np.asarray(blocked.mask)
    cols = np.asarray(blocked.cols)
    win = np.asarray(blocked.block_win)
    out = {}
    for t, r in zip(*np.nonzero(mask)):
        out[(int(win[t // blocked.k_blk]) * V + int(r), int(cols[t]))] = (
            int(t) * V + int(r))
    return out


def reference_relayout(plan, rows, cols, vals):
    """NumPy re-layout from the COO: element (i, j) of A moves from its
    forward slot to the slot of element (j, i) of Aᵀ; every other slot
    is zero."""
    src, dst = slots(plan.fwd), slots(plan.bwd)
    lead = vals.shape[:-2]
    flat = vals.reshape(lead + (-1,))
    out = np.zeros(lead + (int(np.prod(plan.bwd.mask.shape)),), vals.dtype)
    for i, j in zip(rows, cols):
        out[..., dst[(int(j), int(i))]] = flat[..., src[(int(i), int(j))]]
    return out.reshape(lead + plan.bwd.mask.shape)


def levels_and_overflow(plan):
    return (plan.vec_idx.shape[0] // plan.bwd.mask.shape[0],
            plan.ovf_src.shape[0])


@pytest.mark.parametrize("heads", [None, 3], ids=["2d", "3d"])
@pytest.mark.parametrize("pattern,levels,overflows", [
    ("power_law", 1, True), ("uniform", 1, True),
    ("dense_tiles", V, False), ("overflow", 1, True)])
def test_transpose_vals_matches_a_numpy_relayout(pattern, levels, overflows,
                                                 heads):
    plan, rows, cols = make_plan(pattern)
    assert levels_and_overflow(plan)[0] == levels
    assert (levels_and_overflow(plan)[1] > 0) == overflows
    rng = np.random.default_rng(1)
    shape = plan.fwd.vals.shape if heads is None else (
        (heads,) + plan.fwd.vals.shape)
    vals = rng.standard_normal(shape).astype(np.float32)
    # junk in every masked-off input position must not reach the output
    vals = np.where(np.asarray(plan.fwd.mask), vals, np.nan)
    got = np.asarray(jax.jit(ADPlan.transpose_vals)(plan, jnp.asarray(vals)))
    want = reference_relayout(plan, rows, cols, vals)
    assert not np.isnan(got).any()
    assert got.dtype == np.float32 and got.shape == want.shape
    np.testing.assert_array_equal(got.view(np.uint32), want.view(np.uint32))


def segments(fill):
    """A 16 x 64 matrix whose Aᵀ vectors (8-wide row segments of A) hold
    ``fill`` entries each, one segment per entry of ``fill``."""
    a = np.zeros((16, 64), np.float32)
    for s, f in enumerate(fill):
        a[s % 16, (s // 16) * V:(s // 16) * V + f] = 1.0
    return a


@pytest.mark.parametrize("fill,levels,overflow", [
    # 48 non-empty vectors: 8 hold more than one entry (8 x 8 >= 48, so
    # level 1 is kept), 2 more than two (2 x 8 < 48): the two 4-entry
    # vectors leave 2 entries each to the scatter
    ([1] * 40 + [2] * 6 + [4] * 2, 2, 4),
    # 5 of 48 hold more than one: one level, 5 x 2 entries overflow
    ([1] * 43 + [3] * 5, 1, 10),
    # every vector full: all 8 levels, nothing left over
    ([V] * 12, V, 0)])
def test_relayout_counters_read_the_fill_histogram(fill, levels, overflow):
    a = segments(fill)
    rows, cols = np.nonzero(a)
    fmt = from_coo(rows, cols, a[rows, cols], a.shape, vector_size=V)
    metrics.reset_counters()
    plan = ad_plan(fmt, impl="pallas")
    got = metrics.counters()
    assert got["fs.ad_plan.relayout_levels"] == levels
    assert got["fs.ad_plan.relayout_overflow"] == overflow
    assert levels_and_overflow(plan) == (levels, overflow)
    metrics.reset_counters()


def reference_transpose_vals(plan, rows, cols):
    """``ADPlan.transpose_vals`` as one gather per slot, its map built
    from the COO (the reference re-layout, in JAX)."""
    src, dst = slots(plan.fwd), slots(plan.bwd)
    per_slot = np.full(int(np.prod(plan.bwd.mask.shape)), -1, np.int32)
    for i, j in zip(rows, cols):
        per_slot[dst[(int(j), int(i))]] = src[(int(i), int(j))]
    shape = plan.bwd.mask.shape

    def relayout(self, vals):
        lead = vals.shape[:-2]
        got = jnp.take(vals.reshape(lead + (-1,)), np.maximum(per_slot, 0),
                       axis=-1)
        return jnp.where(per_slot >= 0, got, 0).reshape(lead + shape)
    return relayout


def grads(op, plan, args):
    return jax.jit(jax.grad(lambda *xs: jnp.sum(op(plan, *xs) ** 2),
                            argnums=tuple(range(len(args)))))(*args)


OPS = {
    "spmm_ad": lambda plan, vals, b: spmm_ad(plan, vals, b, interpret=True),
    "sddmm_ad": lambda plan, q, k: sddmm_ad(plan, q, k, interpret=True),
    "attention_ad": lambda plan, q, k, v: attention_ad(plan, q, k, v,
                                                       interpret=True),
}


@pytest.mark.parametrize("op", sorted(OPS))
def test_gradients_equal_a_run_through_the_reference_relayout(op,
                                                              monkeypatch):
    plan, rows, cols = make_plan("overflow", seed=2)
    m, k = plan.shape
    rng = np.random.default_rng(3)
    f = 16
    if op == "spmm_ad":
        args = (jnp.asarray(rng.standard_normal(plan.fwd.vals.shape),
                            jnp.float32),
                jnp.asarray(rng.standard_normal((k, f)), jnp.float32))
    else:
        args = tuple(jnp.asarray(rng.standard_normal(s), jnp.float32)
                     for s in [(m, f), (k, f), (k, f)][:2 if op == "sddmm_ad"
                                                       else 3])
    got = grads(OPS[op], plan, args)
    monkeypatch.setattr(ADPlan, "transpose_vals",
                        reference_transpose_vals(plan, rows, cols))
    want = grads(OPS[op], plan, args)
    for g, w in zip(got, want):
        assert np.isfinite(np.asarray(g)).all()
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
