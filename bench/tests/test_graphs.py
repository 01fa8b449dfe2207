"""The benchmark's copy of the graph generator against the program's."""

import numpy as np
import pytest

from bench import graphs
from repro.sparse.graphs import DATASET_PRESETS, make_dataset


@pytest.mark.parametrize("preset,scale", [("Amazon", 0.002), ("DD", 0.01)])
def test_copy_is_bit_identical(preset, scale, tmp_path):
    nodes, deg, kind = DATASET_PRESETS[preset]
    traffic = {"preset": preset, "nodes": nodes, "avg_degree": deg,
               "generator": kind, "scale": scale, "graph_seed": 0}
    want = make_dataset(preset, scale=scale, seed=0)
    for _ in range(2):          # generated, then read from the cache
        n, rows, cols, vals = graphs.load(traffic, tmp_path)
        assert n == want.num_nodes
        for got, ref in ((rows, want.rows), (cols, want.cols),
                         (vals, want.vals)):
            assert got.dtype == ref.dtype
            np.testing.assert_array_equal(got, ref)
    assert len(list(tmp_path.iterdir())) == 1
