"""GNN models on FlashSparse ops: correctness + trainability."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import block_format, from_dense, sddmm
from repro.core.softmax import sparse_softmax
from repro.models.gnn import (
    GNNConfig,
    agnn_forward,
    gcn_forward,
    init_agnn,
    init_gcn,
    make_train_step,
)
from repro.sparse.graphs import erdos_renyi_graph, gcn_normalized


def make_graph(n=64, deg=6, seed=0):
    rows, cols = erdos_renyi_graph(n, deg, seed=seed)
    loops = np.arange(n)
    rows = np.concatenate([rows, loops])
    cols = np.concatenate([cols, loops])
    vals = gcn_normalized(rows, cols, n)
    a = np.zeros((n, n), np.float32)
    a[rows, cols] = vals
    return a, block_format(from_dense(a, vector_size=8), k_blk=8)


def test_sparse_softmax_matches_dense():
    rng = np.random.default_rng(0)
    a = (rng.random((40, 40)) < 0.2).astype(np.float32)
    blocked = block_format(from_dense(a, vector_size=8), k_blk=8)
    q = jnp.asarray(rng.standard_normal((40, 16)), jnp.float32)
    scores = sddmm(blocked, q, q)
    p = sparse_softmax(blocked, scores)

    # dense reference
    s_dense = np.asarray(q @ q.T).astype(np.float64)
    s = np.where(a != 0, s_dense, -1e30)
    e = np.exp(s - s.max(axis=1, keepdims=True)) * (a != 0)
    denom = e.sum(axis=1, keepdims=True)
    ref = np.where(denom > 0, e / np.maximum(denom, 1e-20), 0.0)

    # scatter blocked p back to dense
    out = np.zeros_like(ref)
    cols = np.asarray(blocked.cols)
    mask = np.asarray(blocked.mask)
    bw = np.asarray(blocked.block_win)
    pv = np.asarray(p)
    v = blocked.vector_size
    for t in range(pv.shape[0]):
        w = bw[t // blocked.k_blk]
        for r in range(v):
            if mask[t, r] and w * v + r < 40:
                out[w * v + r, cols[t]] += pv[t, r]
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-5)
    # rows with any edge sum to 1
    row_has = (a != 0).any(axis=1)
    np.testing.assert_allclose(out.sum(1)[row_has], 1.0, rtol=1e-5)


@pytest.mark.parametrize("impl", ["blocked", "pallas"])
def test_gcn_forward_shapes(impl):
    a, adj = make_graph()
    cfg = GNNConfig(model="gcn", in_dim=32, hidden_dim=16, num_classes=4,
                    num_layers=3, impl=impl)
    params = init_gcn(jax.random.key(0), cfg)
    x = jax.random.normal(jax.random.key(1), (64, 32))
    logits = gcn_forward(params, adj, x, cfg)
    assert logits.shape == (64, 4)
    assert not np.any(np.isnan(np.asarray(logits)))


@pytest.mark.parametrize("impl", ["blocked", "pallas"])
def test_agnn_forward_shapes(impl):
    a, adj = make_graph()
    cfg = GNNConfig(model="agnn", in_dim=32, hidden_dim=16, num_classes=4,
                    num_layers=2, impl=impl)
    params = init_agnn(jax.random.key(0), cfg)
    x = jax.random.normal(jax.random.key(1), (64, 32))
    logits = agnn_forward(params, adj, x, cfg)
    assert logits.shape == (64, 4)
    assert not np.any(np.isnan(np.asarray(logits)))


def test_pallas_and_blocked_gcn_agree():
    a, adj = make_graph()
    cfg_b = GNNConfig(model="gcn", in_dim=32, hidden_dim=16, num_classes=4,
                      num_layers=3, impl="blocked")
    cfg_p = dataclasses_replace(cfg_b, impl="pallas")
    params = init_gcn(jax.random.key(0), cfg_b)
    x = jax.random.normal(jax.random.key(1), (64, 32))
    out_b = gcn_forward(params, adj, x, cfg_b)
    out_p = gcn_forward(params, adj, x, cfg_p)
    np.testing.assert_allclose(np.asarray(out_b), np.asarray(out_p),
                               rtol=1e-4, atol=1e-4)


def dataclasses_replace(cfg, **kw):
    import dataclasses
    return dataclasses.replace(cfg, **kw)


@pytest.mark.parametrize("model", ["gcn", "agnn"])
@pytest.mark.parametrize("impl", ["blocked", "pallas"])
def test_training_through_pallas_plan_matches_blocked(model, impl):
    """The tier-1 acceptance path: grads through the ADPlan adjacency are
    impl-invariant — the Pallas forward/backward (interpret mode on CPU)
    produces the same first training step as the XLA blocked path."""
    from repro.core.autodiff import ad_plan
    from repro.core.format import from_dense as fmt_from_dense

    a, _ = make_graph(n=48, deg=5, seed=7)
    plan = ad_plan(fmt_from_dense(a, vector_size=8), impl=impl)
    cfg = GNNConfig(model=model, in_dim=16, hidden_dim=16, num_classes=3,
                    num_layers=2, impl=impl, interpret=True)
    x = jax.random.normal(jax.random.key(2), (48, 16))
    labels = jnp.argmax(x @ jax.random.normal(jax.random.key(3), (16, 3)), -1)
    mask = jnp.ones((48,), jnp.float32)
    params = (init_gcn if model == "gcn" else init_agnn)(jax.random.key(0), cfg)
    mom = jax.tree.map(jnp.zeros_like, params)
    step = make_train_step(cfg, lr=0.3)
    p1, m1, loss1, _ = step(params, mom, plan, x, labels, mask)

    cfg_b = dataclasses_replace(cfg, impl="blocked")
    step_b = make_train_step(cfg_b, lr=0.3)
    p1b, _, loss1b, _ = step_b(params, mom, plan, x, labels, mask)
    np.testing.assert_allclose(float(loss1), float(loss1b), rtol=1e-5)
    for l1, l2 in zip(jax.tree.leaves(p1), jax.tree.leaves(p1b)):
        np.testing.assert_allclose(np.asarray(l1), np.asarray(l2),
                                   rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("model", ["gcn", "agnn"])
def test_training_reduces_loss(model):
    a, adj = make_graph(n=48, deg=5, seed=3)
    cfg = GNNConfig(model=model, in_dim=16, hidden_dim=16, num_classes=3,
                    num_layers=2)
    x = jax.random.normal(jax.random.key(2), (48, 16))
    # learnable task: labels from a hidden linear map of the features
    wtrue = jax.random.normal(jax.random.key(3), (16, 3))
    labels = jnp.argmax(x @ wtrue, axis=-1)
    mask = jnp.ones((48,), jnp.float32)

    init = init_gcn if model == "gcn" else init_agnn
    params = init(jax.random.key(0), cfg)
    mom = jax.tree.map(jnp.zeros_like, params)
    step = make_train_step(cfg, lr=0.3)

    losses = []
    for _ in range(120):
        params, mom, loss, acc = step(params, mom, adj, x, labels, mask)
        losses.append(float(loss))
    assert losses[-1] < 0.5 * losses[0], losses[::30]


def agnn_edge_reference(params, rows, cols, n, x, labels, mask):
    """AGNN's loss over a de-duplicated edge list, in plain ``jax.numpy``:
    the equations of the benchmark's plain reference (cosine over each
    edge's two rows with the norm floored at 1e-6, a shift-free softmax
    over each row's edges, aggregation by segment sum), independent of
    the sparse format and the kernels."""
    def edge_sum(e):
        return jax.ops.segment_sum(e, rows, num_segments=n)

    h = jax.nn.relu(x @ params["w_in"])
    for beta in params["beta"]:
        hn = h / jnp.maximum(jnp.linalg.norm(h, axis=-1, keepdims=True), 1e-6)
        s = beta * jnp.sum(hn[rows] * hn[cols], axis=1)
        row_max = jax.lax.stop_gradient(
            jax.ops.segment_max(s, rows, num_segments=n))
        e = jnp.exp(s - row_max[rows])
        p = e / jnp.maximum(edge_sum(e), 1e-20)[rows]
        h = edge_sum(p[:, None] * h[cols])
    logp = jax.nn.log_softmax(h @ params["w_out"], axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    return jnp.sum(nll * mask) / jnp.sum(mask)


def test_agnn_pallas_step_matches_an_edge_list_reference():
    """The AGNN train step through the Pallas path (fused attention
    forward, recompute backward; interpret mode) against the edge-list
    reference in float64, on seeded random weights and learned betas, on
    a graph with a hub column and an empty row."""
    from repro.core.autodiff import ad_plan
    from repro.core.format import from_coo

    n, rng = 40, np.random.default_rng(11)
    rows = rng.integers(0, n, 160)
    cols = rng.integers(0, n, 160)
    rows = np.concatenate([rows, np.arange(0, n, 2)])   # hub column 7
    cols = np.concatenate([cols, np.full(n // 2, 7)])
    keep = rows != 13                                    # empty row 13
    edges = np.unique(np.stack([rows[keep], cols[keep]], 1), axis=0)
    rows, cols = edges[:, 0], edges[:, 1]
    plan = ad_plan(from_coo(rows, cols, np.ones(rows.size, np.float32),
                            (n, n)), impl="pallas")
    cfg = GNNConfig(model="agnn", in_dim=8, hidden_dim=32, num_classes=4,
                    num_layers=2, impl="pallas", interpret=True)
    params = init_agnn(jax.random.key(5), cfg)
    params["beta"] = [jnp.float32(b) for b in 1 + rng.standard_normal(2)]
    x = jax.random.normal(jax.random.key(6), (n, 8))
    labels = jax.random.randint(jax.random.key(7), (n,), 0, 4)
    mask = (jnp.arange(n) % 3 != 0).astype(jnp.float32)
    mom = jax.tree.map(jnp.zeros_like, params)
    _, grad, loss, _ = make_train_step(cfg)(params, mom, plan, x, labels,
                                            mask)

    cpu = jax.devices("cpu")[0]
    with jax.enable_x64(True):
        wide = jax.tree.map(
            lambda a: jax.device_put(np.asarray(a, np.float64), cpu),
            (params, x))
        ref_loss, ref_grad = jax.value_and_grad(agnn_edge_reference)(
            wide[0], rows, cols, n, wide[1], np.asarray(labels),
            np.asarray(mask, np.float64))
        ref_grad = jax.tree.map(np.asarray, ref_grad)
    # float32 rounds the loss, a mean of 26 terms, by about 1e-7
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    for (path, want), got in zip(
            jax.tree_util.tree_leaves_with_path(ref_grad),
            jax.tree.leaves(grad)):
        name = jax.tree_util.keystr(path)
        err = (np.max(np.abs(np.asarray(got, np.float64) - want))
               / np.max(np.abs(want)))
        # A weight's gradient sums float32 products over at most a few
        # dozen edges and nodes: about 3e-7 of its largest entry.  A
        # beta's gradient is a sum over every edge of terms that cancel
        # row by row, so float32 rounds it relative to the terms, up to
        # 1e-5 of the (smaller) result.
        tol = 1e-4 if name.startswith("['beta']") else 1e-5
        assert err <= tol, (name, err)
