"""The program's marks on a trace (``bench/program_trace.py``): kernel
metadata and op tags parsed from HLO text as a v5e prints it, and the
six readers on a small trace recorded on a v5e with them
(``record_trace.py --out bench/tests/data/gcn_small_tagged.xplane.pb``)."""

import json
import math
import pathlib
from types import SimpleNamespace

import numpy as np
import pytest

from bench import harness, peaks, program_trace, trace

RECORDED = pathlib.Path(__file__).parent / "data" / "gcn_small_tagged.xplane.pb"
READERS = ("gather_ms", "spmm_ns_per_dma", "spmm_dma_redundancy",
           "spmm_mxu_redundancy", "plan_perm_s", "dispatch_ms")


@pytest.mark.parametrize("text,meta", [
    ('%jvp_jit__spmm_call__.2 = f32[808,128]{1,0} custom-call(%a), '
     'custom_call_target="tpu_custom_call", frontend_attributes='
     '{kernel_metadata={\n"dir":"fwd",\n"dmas":"296",\n"op":"spmm"\n}}',
     {"dir": "fwd", "dmas": "296", "op": "spmm"}),
    ('%k = f32[8]{0} custom-call(%a), frontend_attributes='
     '{kernel_metadata={}}', {}),
    ("%fusion.3 = f32[8]{0} fusion(%a), kind=kLoop", {}),
])
def test_kernel_metadata_from_hlo_text(text, meta):
    assert program_trace.kernel_metadata(text) == meta


def recorded_ctx(monkeypatch):
    meta = json.loads(RECORDED.with_suffix(".json").read_text())
    cell = harness.load_cell(meta["workload"])
    tr = trace.load(str(RECORDED))
    lo, hi = trace.window(tr)
    monkeypatch.setattr(program_trace, "xplane_of", lambda ctx: str(RECORDED))
    return SimpleNamespace(
        cell=cell, counts=cell.model.counts(meta["graph"], cell.config),
        steps=meta["steps"], window=(lo, hi),
        ops=trace.clip(tr.ops[0], lo, hi),
        peak=peaks.peak(meta["device_kind"]), spans={}, trace=tr)


def test_recorded_window_carries_the_marks(monkeypatch):
    ctx = recorded_ctx(monkeypatch)
    m = program_trace.marks(ctx)
    kernels = m.spmm()
    assert len(kernels) == ctx.counts["spmm"]["calls"] * ctx.steps
    assert all(o.meta["op"] == "spmm" for o in kernels)
    dirs = [o.meta["dir"] for o in kernels]
    assert (dirs.count("fwd"), dirs.count("bwd")) == (5 * ctx.steps,
                                                      4 * ctx.steps)
    tagged = m.tagged(program_trace.GATHER)
    assert tagged and not any(o.kernel for o in tagged)
    xla = sum(e.dur for e in ctx.ops if not trace.is_kernel(e))
    assert 0 < sum(o.dur for o in tagged) <= xla
    steps = [s for s in m.spans if s.name == program_trace.STEP]
    assert len(steps) == ctx.steps


def test_readers_on_the_recorded_window(monkeypatch):
    from repro.core import ad_plan, from_dense, metrics

    metrics.reset_spans()
    ad_plan(from_dense(np.eye(16, dtype=np.float32)), impl="pallas")
    ctx = recorded_ctx(monkeypatch)
    values = {name: harness.load_module(
        harness.BENCH / "metrics" / f"{name}.py").read(ctx)
        for name in READERS}
    assert all(v is not None and math.isfinite(v) and v > 0
               for v in values.values()), values
    assert values["spmm_dma_redundancy"] > 1
    assert values["spmm_mxu_redundancy"] > 1


def test_a_program_without_spans_reads_nothing(monkeypatch):
    """On a trace of a program that puts no ``fs.*`` span in the window,
    as before it had them, the readers return nothing and raise
    nothing."""
    old = RECORDED.parent / "gcn_small.xplane.pb"
    ctx = recorded_ctx(monkeypatch)
    tr = trace.load(str(old))
    ctx.window = trace.window(tr)
    monkeypatch.setattr(program_trace, "xplane_of", lambda ctx: str(old))
    for name in READERS:
        if name != "plan_perm_s":
            assert harness.load_module(
                harness.BENCH / "metrics" / f"{name}.py").read(ctx) is None


def test_spans_without_kernel_metadata_is_an_error(monkeypatch):
    bare = program_trace.Op("jvp_jit__spmm_call__", 0, 10, True, None, {})
    marks = program_trace.Marks([bare], [trace.Event("fs.step", 0, 1)])
    monkeypatch.setattr(program_trace, "xplane_of", lambda ctx: "x.pb")
    monkeypatch.setattr(program_trace, "load", lambda *a: marks)
    ctx = SimpleNamespace(window=(0, 10), counts={"spmm": {"calls": 1}})
    with pytest.raises(ValueError, match="kernel_metadata"):
        program_trace.marks(ctx)
