"""Real-matrix dataset layer: parsers, symmetric expansion, taxonomy.

Covers the MatrixMarket/edge-list loaders (repro.data.datasets), the
symmetric-expansion diagonal regression (a mirrored diagonal entry must
not double under ``duplicates="sum"`` nor manufacture phantom duplicates
under ``duplicates="error"``), property-based round-trip + malformed-
input fuzzing (skips cleanly offline via tests/_hypothesis_compat), and
the structure-taxonomy classifier against the vendored manifest.
"""

import json
import pathlib

import numpy as np
import pytest

from repro.core.format import from_coo, to_dense
from repro.core.validate import ValidationError
from repro.data.datasets import (
    MatrixSample,
    load_edgelist,
    load_manifest,
    load_mtx,
    load_vendored,
    loads_edgelist,
    loads_mtx,
    save_mtx,
    vendored_dir,
    vendored_names,
)
from repro.sparse.structure import (
    STRUCTURE_CLASSES,
    classify_format,
    classify_structure,
    structure_stats,
)

from _hypothesis_compat import given, settings, st

DATA = pathlib.Path(__file__).parent / "data"


def canonical(rows, cols, vals, shape):
    """Coalesced, (row, col)-sorted triplets for order-insensitive compare."""
    lin = np.asarray(rows) * shape[1] + np.asarray(cols)
    uniq, inv = np.unique(lin, return_inverse=True)
    summed = np.zeros(uniq.size, np.float64)
    np.add.at(summed, inv, np.asarray(vals, np.float64))
    return uniq // shape[1], uniq % shape[1], summed


# ------------------------------------------------------------ parser -------


def test_coordinate_general_real():
    s = loads_mtx("%%MatrixMarket matrix coordinate real general\n"
                  "% a comment\n3 4 3\n1 1 2.5\n3 4 -1\n2 2 1e-3\n")
    assert s.shape == (3, 4) and s.nnz == 3
    d = s.dense()
    assert d[0, 0] == 2.5 and d[2, 3] == -1.0 and d[1, 1] == np.float32(1e-3)
    assert s.meta["symmetry"] == "general"


def test_coordinate_pattern_and_integer_fields():
    pat = loads_mtx("%%MatrixMarket matrix coordinate pattern general\n"
                    "2 2 2\n1 2\n2 1\n")
    assert np.array_equal(pat.dense(), [[0, 1], [1, 0]])
    integer = loads_mtx("%%MatrixMarket matrix coordinate integer general\n"
                        "2 2 1\n2 2 -7\n")
    assert integer.dense()[1, 1] == -7.0


def test_symmetric_expansion_mirrors_off_diagonal_once():
    s = loads_mtx("%%MatrixMarket matrix coordinate real symmetric\n"
                  "3 3 4\n1 1 4\n2 1 -1\n3 3 5\n3 2 -2\n")
    d = s.dense()
    assert d[1, 0] == d[0, 1] == -1.0
    assert d[2, 1] == d[1, 2] == -2.0
    # stored 4 entries (2 diagonal), expanded = 4 + 2 mirrors
    assert s.nnz == 6


def test_symmetric_diagonal_not_doubled_regression():
    """The bugfix regression: a symmetric matrix with a full explicit
    diagonal must keep its diagonal values exactly once — a naive
    expansion that mirrors every stored entry doubles them (and trips
    ``from_coo(duplicates="error")`` with phantom duplicates)."""
    s = load_mtx(DATA / "mesh2d_10.mtx")
    d = s.dense()
    np.testing.assert_array_equal(np.diag(d), np.full(100, 4.0))
    assert (d == d.T).all()
    # duplicates="error" is the proof no coordinate appears twice
    fmt = s.to_format(duplicates="error")
    np.testing.assert_allclose(np.asarray(to_dense(fmt)), d)
    # 100 diagonal + 2*180 mirrored neighbor couplings
    assert s.nnz == 460


def test_duplicates_policy_forwarded_to_from_coo():
    text = ("%%MatrixMarket matrix coordinate real general\n"
            "2 2 3\n1 1 1.0\n1 1 2.0\n2 2 3.0\n")
    s = loads_mtx(text)
    with pytest.raises(ValidationError):
        s.to_format(duplicates="error")
    fmt = s.to_format(duplicates="sum")
    assert np.asarray(to_dense(fmt))[0, 0] == 3.0


def test_skew_symmetric_negates_mirror_and_rejects_diagonal():
    s = loads_mtx("%%MatrixMarket matrix coordinate real skew-symmetric\n"
                  "3 3 2\n2 1 5\n3 1 2\n")
    d = s.dense()
    assert d[1, 0] == 5.0 and d[0, 1] == -5.0
    assert d[2, 0] == 2.0 and d[0, 2] == -2.0
    with pytest.raises(ValueError, match="diagonal"):
        loads_mtx("%%MatrixMarket matrix coordinate real skew-symmetric\n"
                  "2 2 1\n1 1 3\n")


def test_symmetric_upper_triangle_entry_rejected():
    with pytest.raises(ValueError, match="upper"):
        loads_mtx("%%MatrixMarket matrix coordinate real symmetric\n"
                  "3 3 1\n1 3 1.0\n")


def test_array_general_and_symmetric():
    gen = loads_mtx("%%MatrixMarket matrix array real general\n"
                    "2 3 \n1\n0\n2\n3\n0\n4\n".replace(" \n", "\n"))
    np.testing.assert_array_equal(gen.dense(), [[1, 2, 0], [0, 3, 4]])
    sym = loads_mtx("%%MatrixMarket matrix array real symmetric\n"
                    "2 2\n1\n5\n2\n")
    np.testing.assert_array_equal(sym.dense(), [[1, 5], [5, 2]])


def test_vendored_files_all_load_and_match_manifest():
    manifest = load_manifest()
    by_name = {d["name"]: d for d in manifest["datasets"]}
    samples = load_vendored()
    assert len(samples) == len(vendored_names()) >= 8
    for s in samples:
        entry = by_name[s.name]
        assert s.nnz > 0
        assert s.structure_class() == entry["structure_class"], s.name
        assert s.meta["structure_class"] == entry["structure_class"]
        # every vendored matrix must survive strict format construction
        s.to_format(duplicates="error")


def test_vendored_subset_and_unknown_name():
    (s,) = load_vendored(["tridiag_64"])
    assert s.name == "tridiag_64" and s.shape == (64, 64)
    with pytest.raises(KeyError, match="no_such"):
        load_vendored(["no_such_matrix"])


def test_manifest_missing_dir(monkeypatch, tmp_path):
    monkeypatch.setenv("REPRO_DATASETS_DIR", str(tmp_path))
    assert vendored_dir() == tmp_path
    with pytest.raises(FileNotFoundError, match="manifest"):
        load_manifest()


def test_manifest_download_entries_have_urls():
    remote = [d for d in load_manifest()["datasets"] if not d.get("file")]
    assert remote, "manifest should list download-only SuiteSparse entries"
    for d in remote:
        assert d["url"].startswith("https://")
        assert d["structure_class"] in STRUCTURE_CLASSES


# ------------------------------------------------------------ edge list ----


def test_edgelist_parsing():
    s = loads_edgelist("# comment\n0 1 2.0\n1 2\n2 0 0.5 # tail\n")
    assert s.shape == (3, 3) and s.nnz == 3
    assert s.dense()[0, 1] == 2.0 and s.dense()[1, 2] == 1.0
    fixed = loads_edgelist("0 1\n", num_nodes=5)
    assert fixed.shape == (5, 5)
    with pytest.raises(ValueError, match="out of bounds"):
        loads_edgelist("0 7\n", num_nodes=3)
    with pytest.raises(ValueError, match="line 2"):
        loads_edgelist("0 1\nnope nope\n")
    with pytest.raises(ValueError, match="negative"):
        loads_edgelist("-1 2\n")


def test_vendored_edgelist_loads():
    s = load_edgelist(DATA / "hubgraph_100.edges", num_nodes=100)
    assert s.shape == (100, 100)
    assert s.structure_class() == "hub"


# ------------------------------------------------------------ writer -------


def test_save_mtx_roundtrip_fields(tmp_path):
    rows, cols = np.array([0, 2, 1]), np.array([1, 0, 2])
    vals = np.array([1.5, -2.0, 3.0], np.float32)
    for field in ("real", "integer", "pattern"):
        path = tmp_path / f"t_{field}.mtx"
        save_mtx(path, rows, cols, vals, (3, 3), field=field,
                 comment="roundtrip")
        back = load_mtx(path)
        r2, c2, v2 = canonical(back.rows, back.cols, back.vals, (3, 3))
        r1, c1, v1 = canonical(rows, cols,
                               np.ones(3) if field == "pattern" else
                               np.trunc(vals) if field == "integer" else vals,
                               (3, 3))
        np.testing.assert_array_equal(r2, r1)
        np.testing.assert_array_equal(c2, c1)
        np.testing.assert_allclose(v2, v1)
    with pytest.raises(ValueError, match="out of bounds"):
        save_mtx(tmp_path / "bad.mtx", [5], [0], [1.0], (3, 3))
    with pytest.raises(ValueError, match="field"):
        save_mtx(tmp_path / "bad.mtx", [0], [0], [1.0], (3, 3),
                 field="complex")


# ------------------------------------------------------ property tests -----


@settings(max_examples=40, deadline=None)
@given(st.data())
def test_roundtrip_coo_writer_parser(data):
    """Random COO → save_mtx → loads_mtx → identical canonical COO."""
    m = data.draw(st.integers(1, 40), label="m")
    k = data.draw(st.integers(1, 40), label="k")
    nnz = data.draw(st.integers(0, 60), label="nnz")
    rows = data.draw(st.lists(st.integers(0, m - 1), min_size=nnz,
                              max_size=nnz), label="rows")
    cols = data.draw(st.lists(st.integers(0, k - 1), min_size=nnz,
                              max_size=nnz), label="cols")
    vals = data.draw(st.lists(
        st.floats(-1e6, 1e6, allow_nan=False, width=32),
        min_size=nnz, max_size=nnz), label="vals")
    import io

    buf = io.StringIO()
    save_mtx(buf, rows, cols, vals, (m, k))
    back = loads_mtx(buf.getvalue())
    assert back.shape == (m, k)
    r1, c1, v1 = canonical(rows, cols, np.float32(vals), (m, k))
    r2, c2, v2 = canonical(back.rows, back.cols, back.vals, (m, k))
    np.testing.assert_array_equal(r1, r2)
    np.testing.assert_array_equal(c1, c2)
    np.testing.assert_allclose(v1, v2, rtol=1e-6, atol=1e-30)


_BAD_HEADERS = [
    "",                                                    # empty file
    "%%MatrixMarket matrix coordinate complex general",    # unsupported field
    "%%MatrixMarket matrix coordinate real hermitian",     # unsupported sym
    "%%MatrixMarket matrix ellpack real general",          # unsupported fmt
    "%%MatrixMarket vector coordinate real general",       # not a matrix
    "%MatrixMarket matrix coordinate real general",        # bad magic
    "%%MatrixMarket matrix array pattern general",         # array+pattern
]


@pytest.mark.parametrize("header", _BAD_HEADERS)
def test_malformed_headers_raise(header):
    with pytest.raises(ValueError, match="line 1"):
        loads_mtx(header + "\n2 2 1\n1 1 1\n")


_BAD_BODIES = [
    "%%MatrixMarket matrix coordinate real general\n",             # no size
    "%%MatrixMarket matrix coordinate real general\n2 2\n",        # short size
    "%%MatrixMarket matrix coordinate real general\n2 x 1\n1 1 1\n",
    "%%MatrixMarket matrix coordinate real general\n2 2 -1\n",     # neg size
    "%%MatrixMarket matrix coordinate real general\n2 2 2\n1 1 1\n",
    "%%MatrixMarket matrix coordinate real general\n2 2 1\n1 1 1\n2 2 2\n",
    "%%MatrixMarket matrix coordinate real general\n2 2 1\n1 1\n",
    "%%MatrixMarket matrix coordinate real general\n2 2 1\n1 1 abc\n",
    "%%MatrixMarket matrix coordinate real general\n2 2 1\n3 1 1\n",  # OOB
    "%%MatrixMarket matrix coordinate real general\n2 2 1\n0 1 1\n",  # 0-based
    "%%MatrixMarket matrix array real general\n2 2\n1\n2\n3\n",    # truncated
    "%%MatrixMarket matrix array real general\n2 2\n1\n2\n3\n4\n5\n",
    "%%MatrixMarket matrix array real general\n2 2\n1\n2\nxx\n4\n",
    "%%MatrixMarket matrix array real symmetric\n2 3\n1\n2\n3\n",  # not square
]


@pytest.mark.parametrize("text", _BAD_BODIES)
def test_malformed_bodies_raise_with_line_numbers(text):
    with pytest.raises(ValueError, match="MatrixMarket line"):
        loads_mtx(text)


@settings(max_examples=40, deadline=None)
@given(st.data())
def test_fuzz_corrupted_text_never_silent(data):
    """Random corruption of a valid file either parses to *some* sample
    or raises a clear ValueError — never crashes with an internal error
    and never returns out-of-bounds triplets."""
    base = ("%%MatrixMarket matrix coordinate real general\n"
            "4 5 3\n1 2 1.5\n4 5 -2\n2 2 9\n")
    pos = data.draw(st.integers(0, len(base) - 1), label="pos")
    ch = data.draw(st.sampled_from("\n %x-9."), label="ch")
    corrupted = base[:pos] + ch + base[pos + 1:]
    try:
        s = loads_mtx(corrupted)
    except ValueError as e:
        assert "line" in str(e)
    else:
        m, k = s.shape
        if s.nnz:
            assert s.rows.min() >= 0 and s.rows.max() < m
            assert s.cols.min() >= 0 and s.cols.max() < k


# ------------------------------------------------------------ taxonomy -----


def test_classify_structure_rules():
    base = dict(nnz=100.0, density=0.01, avg_row_len=2.0, row_cv=0.1,
                window_skew=1.0, bandwidth_ratio=0.5, band_fill=0.1,
                diag_frac=0.0)
    assert classify_structure({**base, "nnz": 0.0}) == "empty"
    assert classify_structure({**base, "density": 0.3}) == "dense"
    assert classify_structure({**base, "row_cv": 1.5}) == "hub"
    assert classify_structure({**base, "window_skew": 5.0}) == "hub"
    assert classify_structure({**base, "bandwidth_ratio": 0.01}) == "banded"
    assert classify_structure({**base, "bandwidth_ratio": 0.2,
                               "band_fill": 0.5}) == "block"
    assert classify_structure({**base, "bandwidth_ratio": 0.2}) == "mesh"
    assert classify_structure(base) == "uniform"
    for cls in ("empty", "dense", "hub", "banded", "block", "mesh",
                "uniform"):
        assert cls in STRUCTURE_CLASSES


def test_structure_stats_features_and_validation():
    # tridiagonal: tight band, uniform rows, full diagonal
    n = 32
    rows = np.concatenate([np.arange(n), np.arange(1, n), np.arange(n - 1)])
    cols = np.concatenate([np.arange(n), np.arange(n - 1), np.arange(1, n)])
    stats = structure_stats(rows, cols, (n, n))
    assert stats["nnz"] == 3 * n - 2
    assert stats["bandwidth_ratio"] <= 0.05
    assert stats["diag_frac"] == 1.0
    assert stats["row_cv"] < 0.5
    assert classify_structure(stats) == "banded"
    with pytest.raises(ValueError, match="shape"):
        structure_stats([0], [0], (0, 4))
    with pytest.raises(ValueError, match="equal length"):
        structure_stats([0, 1], [0], (4, 4))
    empty = structure_stats([], [], (8, 8))
    assert classify_structure(empty) == "empty"


def test_classify_format_memoized():
    rng = np.random.default_rng(0)
    rows = np.repeat(np.arange(16), 3)
    cols = rng.integers(0, 16, rows.size)
    lin = np.unique(rows * 16 + cols)
    fmt = from_coo(lin // 16, lin % 16, np.ones(lin.size, np.float32),
                   (16, 16))
    cls = classify_format(fmt)
    assert cls in STRUCTURE_CLASSES
    assert fmt._structure_class == cls
    assert classify_format(fmt) is cls


def test_stats_key_has_structure_class_bucket():
    """``classify_format`` puts a banded and a uniform vendored matrix in
    their own classes (the buckets ``class_winners`` reports by)."""
    samples = {s.name: s for s in load_vendored(["tridiag_64",
                                                 "uniform_80"])}
    assert classify_format(samples["tridiag_64"].to_format()) == "banded"
    assert classify_format(samples["uniform_80"].to_format()) == "uniform"


def test_matrix_sample_helpers():
    s = MatrixSample("t", np.array([0, 9]), np.array([1, 3]),
                     np.array([2.0, 4.0], np.float32), (10, 5))
    assert not s.is_square and s.nnz == 2
    assert s.dense()[9, 3] == 4.0
    fmt = s.to_format()
    assert fmt.shape == (10, 5)
