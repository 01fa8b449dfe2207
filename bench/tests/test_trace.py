"""The trace reduction on hand-made device ops and host spans, named as a
GCN step on a v5e names them, and on a small trace recorded on a v5e
(``record_trace.py``)."""

import json
import pathlib
from types import SimpleNamespace

import pytest

from bench import harness, peaks, trace
from bench.trace import Event

RECORDED = pathlib.Path(__file__).parent / "data" / "gcn_small.xplane.pb"


def ev(name, a, b):
    return Event(name, float(a), float(b))


OPS = [ev("fusion.1", 10, 20), ev("fusion.2", 15, 30),
       ev("jvp_jit__spmm_call__.3", 40, 70), ev("copy.4", 65, 80),
       ev("jvp_jit__spmm_call__", 90, 95)]


def test_union_and_busy():
    assert trace.union(OPS) == [(10, 30), (40, 80), (90, 95)]
    assert trace.busy(OPS, 0, 100) == 20 + 40 + 5
    assert trace.busy(OPS, 25, 92) == 5 + 40 + 2


def test_per_op_merges_instances():
    assert trace.per_op(OPS) == {"fusion": 25, "jvp_jit__spmm_call__": 35,
                                 "copy": 15}


def test_idle_gaps_under_host_spans():
    gaps = trace.idle_gaps(OPS, 0, 100)
    assert gaps == [(0, 10), (30, 40), (80, 90), (95, 100)]
    spans = [ev("bench.window", 0, 100), ev("bench.dispatch", 0, 35),
             ev("bench.wait", 35, 100)]
    names = [trace.innermost(spans, (a + b) / 2) for a, b in gaps]
    assert names == ["bench.dispatch", "bench.dispatch", "bench.wait",
                     "bench.wait"]
    assert trace.innermost(spans, 150) == "outside"


def test_kernels_are_told_from_xla_ops():
    # Trace events carry the HLO instruction's text; an op that reads a
    # kernel's output names the kernel among its operands.
    texts = [
        "%fusion.28 = f32[403394,128]{1,0} fusion(f32[403400,128]{1,0} "
        "%transpose_jvp_jit__spmm_call___.1), kind=kLoop",
        "%jvp_jit__spmm_call__.2 = f32[403400,128]{1,0:T(8,128)} "
        "custom-call(s32[3168,128]{1,0} %copy-done.20), "
        'custom_call_target="tpu_custom_call"',
        "%copy.4 = f32[8]{0} copy(f32[8]{0} %jvp_jit__spmm_call__)",
    ]
    ops = [trace.device_op(t, 0, 1) for t in texts]
    assert [e.name for e in ops] == ["fusion.28", "jvp_jit__spmm_call__.2",
                                     "copy.4"]
    assert [trace.is_kernel(e) for e in ops] == [False, True, False]


def test_window_is_the_host_span_and_refuses_another_clock():
    spans = [ev("bench.window", 5, 100), ev("bench.wait", 60, 100)]
    assert trace.window(trace.Trace({0: OPS}, spans)) == (5, 100)
    shifted = [ev(e.name, e.start + 1e6, e.end + 1e6) for e in OPS]
    with pytest.raises(ValueError, match="one clock"):
        trace.window(trace.Trace({0: shifted}, spans))


def test_recorded_v5e_trace():
    meta = json.loads(RECORDED.with_suffix(".json").read_text())
    cell = harness.load_cell(meta["workload"])
    tr = trace.load(str(RECORDED))
    lo, hi = trace.window(tr)
    ops = trace.clip(tr.ops[0], lo, hi)
    assert 0 < trace.busy(ops, lo, hi) <= hi - lo
    assert all(" " not in name for name in trace.per_op(ops))
    kernels = [e for e in ops if trace.is_kernel(e)]
    counts = cell.model.counts(meta["graph"], cell.config)
    assert len(kernels) == counts["spmm"]["calls"] * meta["steps"]
    assert {trace.op_name(e.name) for e in kernels} == {
        "jvp_jit__spmm_call__", "transpose_jvp_jit__spmm_call___"}
    ctx = SimpleNamespace(counts=counts, ops=ops, steps=meta["steps"],
                          peak=peaks.peak(meta["device_kind"]))
    assert 0 < trace.roofline_share(ctx, "spmm", "_spmm_call") < 100
    names = {trace.innermost(tr.spans, (a + b) / 2)
             for a, b in trace.idle_gaps(ops, lo, hi)}
    assert names <= {"bench.window", "bench.dispatch", "bench.wait"}
