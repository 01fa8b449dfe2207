"""``chip_smoke.py`` off the chip: it refuses to run without a TPU, and
its plain ``jax.numpy`` reference agrees with a dense product and with
the XLA model path on a small graph."""

from __future__ import annotations

import importlib.util
import os
import pathlib
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import block_format, from_coo
from repro.models.gnn import GNNConfig, gnn_loss, init_agnn, init_gcn
from repro.sparse.graphs import make_dataset

REPO = pathlib.Path(__file__).resolve().parents[1]


def _load_chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("alone", [False, True],
                         ids=["in-repo", "script-alone"])
def test_refuses_without_tpu(tmp_path, alone):
    script = REPO / "chip_smoke.py"
    if alone:
        script = pathlib.Path(shutil.copy(script, tmp_path))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    run = subprocess.run([sys.executable, str(script)], cwd=script.parent,
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert run.returncode != 0
    assert '"ok"' not in run.stdout
    assert "needs a TPU" in run.stderr


@pytest.fixture(scope="module")
def small_task():
    g = make_dataset("Amazon", scale=0.002, seed=0)
    n = g.num_nodes
    cs = _load_chip_smoke()
    edges, ref = cs.make_reference(g.rows, g.cols, g.vals, n)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((n, 16)).astype(np.float32))
    labels = jnp.asarray(rng.integers(0, 4, n).astype(np.int32))
    mask = jnp.asarray((rng.random(n) < 0.7).astype(np.float32))
    return g, edges, ref, x, labels, mask


def test_reference_aggregation_is_dense_product(small_task):
    g, edges, ref, x, _, _ = small_task
    n = g.num_nodes
    dense = np.zeros((n, n), np.float64)
    np.add.at(dense, (g.rows, g.cols), g.vals)     # duplicates sum
    np.testing.assert_allclose(np.asarray(ref["aggregate"](edges, x)),
                               dense @ np.asarray(x, np.float64),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("model", ["gcn", "agnn"])
def test_reference_matches_xla_model(small_task, model):
    g, edges, ref, x, labels, mask = small_task
    n = g.num_nodes
    adj = block_format(from_coo(g.rows, g.cols, g.vals, (n, n),
                                vector_size=8), k_blk=8)
    cfg = GNNConfig(model=model, in_dim=16, hidden_dim=16, num_classes=4,
                    num_layers=2, impl="blocked")
    init = init_gcn if model == "gcn" else init_agnn
    params = init(jax.random.key(1), cfg)
    want, want_grad = jax.value_and_grad(
        lambda p: gnn_loss(p, adj, x, labels, mask, cfg)[0])(params)
    got, got_grad = ref[model](params, edges, x, labels, mask)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(got_grad), jax.tree.leaves(want_grad)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_rel_err_holds_each_leaf_to_its_own_scale():
    """An error that is small next to the largest gradient but large next
    to its own leaf is reported, with that leaf's path."""
    cs = _load_chip_smoke()
    want = {"big": np.ones(4, np.float32) * 1e3,
            "small": np.ones(3, np.float32) * 1e-3}
    got = {"big": want["big"], "small": want["small"] * 1.01}
    err, leaf = cs.rel_err(got, want)
    np.testing.assert_allclose(err, 1e-2, rtol=1e-4)
    assert leaf == "['small']"
    assert cs.rel_err(want, want) == (0.0, "['small']")


@pytest.mark.parametrize("model", ["gcn", "agnn"])
def test_float64_reference_gradient(small_task, model):
    """The gradient reference runs the same code in float64 on the host:
    float64 leaves, equal to the fp32 run up to fp32 rounding."""
    _, edges, ref, x, labels, mask = small_task
    cs = _load_chip_smoke()
    cfg = GNNConfig(model=model, in_dim=16, hidden_dim=16, num_classes=4,
                    num_layers=2, impl="blocked")
    init = init_gcn if model == "gcn" else init_agnn
    params = init(jax.random.key(1), cfg)
    loss32, grad32 = ref[model](params, edges, x, labels, mask)
    loss64, grad64 = cs.on_host_f64(ref[model], params, edges, x, labels,
                                    mask)
    assert loss64.dtype == np.float64
    assert all(a.dtype == np.float64 for a in jax.tree.leaves(grad64))
    np.testing.assert_allclose(float(loss64), float(loss32), rtol=1e-5)
    err, leaf = cs.rel_err(grad32, grad64)
    assert err < 1e-3, (err, leaf)
