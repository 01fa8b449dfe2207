"""The benchmark's graphs: a copy of the program's structural generator
(``repro.sparse.graphs.make_dataset``) and an on-disk cache of its output.

A traffic file names the graph: its Table-4 preset numbers (``nodes``,
``avg_degree``, ``generator``), ``scale`` and ``graph_seed``.  The graph
does not vary with a run's ``--seed``.  Generating the power-law replica
takes tens of seconds of host time, and users load their graph rather than
generate it, so the first run writes the COO triplets under
``experiments/bench/graphs/`` in the checkout and later runs read them.

The generator below is kept bit-identical to the program's
(``bench/tests/test_graphs.py`` checks it), so the benchmark owns the
graphs it measures.
"""

from __future__ import annotations

import hashlib
import json
import os
import pathlib

import numpy as np

CACHE_DIR = (pathlib.Path(__file__).resolve().parents[1] / "experiments"
             / "bench" / "graphs")

GENERATOR_KEYS = ("nodes", "avg_degree", "generator", "scale", "graph_seed")


def power_law_graph(num_nodes, avg_degree, seed=0, alpha=1.8):
    """Directed power-law graph (Zipf-ish in-degrees), returns (rows, cols)."""
    rng = np.random.default_rng(seed)
    num_edges = int(num_nodes * avg_degree)
    weights = 1.0 / np.arange(1, num_nodes + 1) ** alpha
    weights /= weights.sum()
    cols = rng.choice(num_nodes, size=num_edges, p=weights)
    rows = rng.integers(0, num_nodes, size=num_edges)
    perm = rng.permutation(num_nodes)
    cols = perm[cols]
    edges = np.unique(np.stack([rows, cols], axis=1), axis=0)
    return edges[:, 0], edges[:, 1]


def erdos_renyi_graph(num_nodes, avg_degree, seed=0):
    rng = np.random.default_rng(seed)
    num_edges = int(num_nodes * avg_degree)
    rows = rng.integers(0, num_nodes, size=num_edges)
    cols = rng.integers(0, num_nodes, size=num_edges)
    edges = np.unique(np.stack([rows, cols], axis=1), axis=0)
    return edges[:, 0], edges[:, 1]


def gcn_normalized(rows, cols, num_nodes):
    """Per-edge values D^-1/2 (A+I) D^-1/2 over the given edge list."""
    deg = np.bincount(rows, minlength=num_nodes) + 1.0
    dinv = 1.0 / np.sqrt(deg)
    return (dinv[rows] * dinv[cols]).astype(np.float32)


def generate(traffic: dict):
    """``(num_nodes, rows, cols, vals)`` of the traffic's graph, with
    self-loops and GCN-normalised values, as ``make_dataset`` makes them."""
    n = max(int(traffic["nodes"] * traffic["scale"]), 16)
    gen = (power_law_graph if traffic["generator"] == "power_law"
           else erdos_renyi_graph)
    rows, cols = gen(n, traffic["avg_degree"], seed=traffic["graph_seed"])
    loops = np.arange(n)
    rows = np.concatenate([rows, loops])
    cols = np.concatenate([cols, loops])
    return n, rows, cols, gcn_normalized(rows, cols, n)


def cache_path(traffic: dict, cache_dir=CACHE_DIR) -> pathlib.Path:
    key = json.dumps({k: traffic[k] for k in GENERATOR_KEYS}, sort_keys=True)
    tag = hashlib.sha256(key.encode()).hexdigest()[:12]
    return pathlib.Path(cache_dir) / f"{traffic['preset']}-{tag}.npz"


def load(traffic: dict, cache_dir=CACHE_DIR):
    """The traffic's graph, from the cache when it is there."""
    path = cache_path(traffic, cache_dir)
    if path.exists():
        with np.load(path) as z:
            return int(z["n"]), z["rows"], z["cols"], z["vals"]
    n, rows, cols, vals = generate(traffic)
    path.parent.mkdir(parents=True, exist_ok=True)
    tmp = path.with_suffix(".tmp.npz")
    np.savez(tmp, n=n, rows=rows, cols=cols, vals=vals)
    os.replace(tmp, path)
    return n, rows, cols, vals
