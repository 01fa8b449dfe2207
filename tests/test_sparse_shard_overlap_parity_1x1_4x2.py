"""Forward parity of the overlapped sharded path on meshes 1x1 and 4x2.

One 8-device child (``tests/_overlap_parity.py``) computes the
single-device references once and checks both meshes; each parametrised
case reads its own mesh's line.  Meshes 2x1 and 2x2 are in
``tests/test_sparse_shard_overlap_parity_2x1_2x2.py``, the rest of the
overlapped path's tests in ``tests/test_sparse_shard_overlap.py``.
"""

import pytest

from _child import assert_mesh_ok
from _overlap_parity import run_overlap_parity

_MESHES = [(1, 1, 1), (4, 2, 8)]


@pytest.fixture(scope="module")
def parity_out():
    return run_overlap_parity([(d, m) for d, m, _ in _MESHES],
                              timeout=360)


@pytest.mark.parametrize("data,model,devices", _MESHES)
def test_overlap_parity_vs_balanced(parity_out, data, model, devices):
    assert data * model == devices
    assert_mesh_ok(parity_out, "OVERLAP_PARITY", data, model)
