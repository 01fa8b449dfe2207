"""Work counts of the models: hand counts on a 16-node graph, and
independence from the sparse format's block and vector sizes."""

import numpy as np
import pytest

from bench import harness

RING = 16


def ring():
    """A 16-node ring with self-loops: 32 nonzeros."""
    i = np.arange(RING)
    rows = np.concatenate([i, i])
    cols = np.concatenate([(i + 1) % RING, i])
    return rows, cols, np.ones(rows.size, np.float32)


def small(model):
    return {"num_layers": 2, "in_dim": 4, "hidden_dim": 4, "num_classes": 2}


def module(name):
    return harness.load_module(harness.BENCH / "models" / f"{name}.py")


def test_gcn_hand_count():
    got = module("gcn").counts({"nnz": 32, "m": RING, "n": RING},
                               small("gcn"))
    # layer 0: SpMM 2*32*4 = 256, dense 2*16*4*4 = 512, dW 512
    # layer 1: SpMM 256, dense 2*16*4*2 = 256, dW 256, dH 256, SpMM^T 256
    # each SpMM: 4*(2*32 + 16 + 1) + 4*4*(16 + 16) = 836 bytes
    assert got == {"spmm": {"calls": 3, "ops": 768, "bytes": 3 * 836},
                   "step_flops": 2560}


@pytest.mark.parametrize("model", ["gcn"])
def test_counts_ignore_the_format(model):
    from repro.core import from_coo
    from repro.core.autodiff import ad_plan

    rows, cols, vals = ring()
    seen = []
    for vector_size in (4, 8, 16):
        for k_blk in (2, 8):
            plan = ad_plan(from_coo(rows, cols, vals, (RING, RING),
                                    vector_size=vector_size),
                           impl="blocked", k_blk=k_blk)
            graph = harness.Graph(RING, rows, cols, vals, plan)
            seen.append(module(model).counts(graph.counts, small(model)))
    assert graph.counts == {"nnz": 32, "m": RING, "n": RING}
    assert all(c == seen[0] for c in seen)
