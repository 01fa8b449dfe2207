"""Registry-driven conformance on real matrices (repro.testing.conformance).

Parametrized straight over the dispatch registry: every registered
``(op, impl)`` pair runs its fp32 base case against the dense oracle on
a vendored real matrix — a newly registered impl is covered here the day
it lands, with no test edit.  Precision expansion and the split/overlap
variants run in the ``real-matrix-conformance`` CI job
(``python -m repro.testing.conformance``); this module keeps tier-1
bounded by pinning one matrix per op.

All tests carry the ``real_data`` marker (deselect with
``-m "not real_data"``); the self-test proves a deliberately broken impl
is reported failing (the PR-8 ``FaultNotDetected`` convention).
"""

import numpy as np
import pytest

from repro.core import dispatch as _dispatch
from repro.data.datasets import load_vendored
from repro.testing.conformance import (
    ConformanceCase,
    enumerate_cases,
    format_report,
    run_case,
    run_conformance,
    self_test,
    summarize,
    tolerance,
)
from repro.testing.faults import FaultNotDetected

pytestmark = pytest.mark.real_data

# One square matrix serves all three ops; rectangular coverage rides on
# the spmm/sddmm runs of the CI job's full sweep.
_MATRIX = "mesh3d_4"


@pytest.fixture(scope="module")
def sample():
    (s,) = load_vendored([_MATRIX])
    return s


@pytest.fixture(scope="module")
def operands(sample):
    from repro.testing.conformance import _operands_for

    return _operands_for(sample, np.random.default_rng(0))


def _pairs():
    return [(op, impl)
            for op in ("spmm", "sddmm", "attention")
            for impl in _dispatch.impls(op)]


@pytest.mark.parametrize("op,impl", _pairs())
def test_registry_impl_conforms_on_real_matrix(op, impl, sample, operands):
    case = ConformanceCase(op, impl, "fp32")
    record = run_case(case, sample, operands)
    assert record.status in ("pass", "skip"), \
        f"{op}/{impl} failed on {sample.name}: {record.detail}"
    if record.status == "pass":
        assert np.isfinite(record.max_err)


def test_enumeration_covers_whole_registry():
    cases = enumerate_cases()
    covered = {(c.op, c.impl) for c in cases}
    for op in ("spmm", "sddmm", "attention"):
        for impl in _dispatch.impls(op):
            assert (op, impl) in covered, f"{op}/{impl} not enumerated"
    # precision expansion: every registered precision appears
    for c in cases:
        assert c.precision in _dispatch.get(c.op, c.impl).precisions
    # capability variants exist where the flags allow them
    assert any(c.variant == "split" for c in cases
               if _dispatch.get(c.op, c.impl).load_balanced)
    assert any(c.variant == "overlap" for c in cases
               if _dispatch.get(c.op, c.impl).overlapped)


def test_tolerance_ladder_ordering():
    ref = np.ones((4, 4), np.float32)
    fp32 = tolerance("spmm", "fp32", ref)
    bf16 = tolerance("spmm", "bf16", ref)
    int8 = tolerance("spmm", "int8", ref)
    assert fp32[0] < bf16[0] <= int8[0]
    assert tolerance("attention", "fp32", ref)[0] > fp32[0]
    # atol scales with the oracle's magnitude
    big = tolerance("spmm", "fp32", 100.0 * ref)
    assert big[1] > fp32[1]


def test_report_and_summary_structure(sample, operands):
    records = [run_case(ConformanceCase("spmm", "blocked", "fp32"),
                        sample, operands)]
    s = summarize(records)
    assert s["total"] == 1 and s["pass"] == 1 and s["failures"] == []
    text = format_report(records)
    assert sample.name in text and "blocked[fp32]" in text


def test_rectangular_matrix_skips_attention(operands):
    (rect,) = load_vendored(["rect_120x40"])
    from repro.testing.conformance import _operands_for

    ops_rect = _operands_for(rect, np.random.default_rng(0))
    record = run_case(ConformanceCase("attention", "blocked", "fp32"),
                      rect, ops_rect)
    assert record.status == "skip"
    assert "square" in record.detail


def test_broken_impl_is_reported_failing(sample):
    """The harness's own fault-detection floor: a wrong kernel must show
    up as a failure, and self_test() must agree."""
    def wrong(fmt, b, **kwargs):
        import jax.numpy as jnp

        return jnp.ones((fmt.shape[0], b.shape[-1]), jnp.float32)

    name = "_test_broken"
    _dispatch.register("spmm", name, wrong)
    try:
        records = run_conformance([sample], ops=("spmm",),
                                  impl_names=[name])
        assert records and all(r.status == "fail" for r in records)
    finally:
        _dispatch._REGISTRY.pop(("spmm", name), None)
        _dispatch._sig_cache.pop(("spmm", name), None)

    # and the packaged self-test runs clean on the healthy registry
    self_test(sample)


def test_self_test_raises_when_harness_is_blinded(sample, monkeypatch):
    """If the harness stopped comparing (always-pass), self_test must
    raise FaultNotDetected rather than report green."""
    import repro.testing.conformance as conf

    def blinded(case, s, operands):
        return conf.ConformanceRecord(s.name, "mesh", case.op, case.impl,
                                      case.precision, case.variant, "pass")

    monkeypatch.setattr(conf, "run_case", blinded)
    with pytest.raises(FaultNotDetected):
        self_test(sample)
