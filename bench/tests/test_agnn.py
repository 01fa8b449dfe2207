"""The ``agnn`` configuration's work counts, per-layer readers and check:
a hand count on the 16-node ring of ``test_counts.py``, independence from
the sparse format, the readers on hand-made marks of a program with and
without the attention kernels' metadata and tags, and whole runs at a
hundredth of the cell's graph whose optimizer keeps the wrong share of
its momentum."""

import time
from types import SimpleNamespace

import pytest

from bench import calibrate_faults, harness, program_trace
from bench.tests.test_counts import RING, module, ring, small

READERS = ("attn_ns_per_dma", "attn_recompute_ms", "softmax_ms")


def test_agnn_hand_count():
    got = module("agnn").counts({"nnz": 32, "m": RING, "n": RING},
                                small("agnn"))
    # per layer (d = 4): attention 4*32*4 = 512 ops, 4*(32 + 16 + 1)
    # + 4*4*(2*16 + 2*16) = 1220 bytes; sddmm and each spmm 2*32*4 = 256
    # ops, 4*(2*32 + 16 + 1) + 4*4*(16 + 16) = 836 bytes.
    # step: x W_in and dW_in 2*(2*16*4*4) = 1024, h W_out, dW_out and dh
    # 3*(2*16*4*2) = 768, per layer 6*256
    assert got == {"attention": {"calls": 2, "ops": 1024, "bytes": 2440},
                   "sddmm": {"calls": 2, "ops": 512, "bytes": 1672},
                   "spmm": {"calls": 6, "ops": 1536, "bytes": 6 * 836},
                   "step_flops": 1024 + 768 + 2 * 6 * 256}


def test_agnn_counts_ignore_the_format():
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
            seen.append(module("agnn").counts(graph.counts, small("agnn")))
    assert all(c == seen[0] for c in seen)


def op(name, dur, *, kernel=True, tag=None, meta=None):
    return program_trace.Op(name, 0.0, float(dur), kernel, tag, meta or {})


def read_with(monkeypatch, ops, readers=READERS, steps=2):
    from bench import trace

    marks = program_trace.Marks(ops, [trace.Event("fs.step", 0, 1)])
    monkeypatch.setattr(program_trace, "xplane_of", lambda ctx: "x.pb")
    monkeypatch.setattr(program_trace, "load", lambda *a: marks)
    ctx = SimpleNamespace(window=(0, 10), steps=steps, counts={})
    return {name: harness.load_module(
        harness.BENCH / "metrics" / f"{name}.py").read(ctx)
        for name in readers}


def test_readers_on_the_attention_marks(monkeypatch):
    meta = {"op": "attention", "dir": "fwd", "dmas": "400"}
    got = read_with(monkeypatch, [
        op("jvp_jit__attn_call__", 2e6, meta=meta),
        op("jvp_jit__attn_call__", 2e6, meta=meta),
        op("transpose_jvp_jit__sddmm_call___", 3e6, tag="fs.attn_recompute"),
        op("fusion.3", 1e6, kernel=False, tag="fs.attn_recompute"),
        op("fusion.4", 5e6, kernel=False, tag="fs.sparse_softmax"),
        op("gather.1", 7e6, kernel=False, tag="fs.transpose_vals")])
    assert got == {"attn_ns_per_dma": 4e6 / 800, "attn_recompute_ms": 2.0,
                   "softmax_ms": 2.5}


def test_a_program_without_the_attention_marks_reads_nothing(monkeypatch):
    """Before its attention launches carried metadata and its backward
    carried the two tags, the program's window reads nothing here."""
    got = read_with(monkeypatch, [
        op("jvp_jit__attn_call__", 2e6),
        op("transpose_jvp_jit__sddmm_call___", 3e6),
        op("fusion.4", 5e6, kernel=False)])
    assert got == dict.fromkeys(READERS)


@pytest.mark.parametrize("reader", READERS)
def test_marks_half_there_are_an_error(monkeypatch, reader):
    meta = {"op": "attention", "dir": "fwd", "dmas": "400"}
    ops = [op("jvp_jit__attn_call__", 2e6, meta=meta)]
    if reader == "attn_ns_per_dma":
        ops.append(op("jvp_jit__attn_call__", 2e6))
    with pytest.raises(ValueError, match="kernel_metadata"):
        read_with(monkeypatch, ops, (reader,))


@pytest.mark.parametrize("coefficient", [0.0, 0.5])
def test_a_step_with_the_wrong_momentum_fails(coefficient, monkeypatch,
                                              tmp_path_factory):
    """The momentum first moves the parameters in the second step and the
    loss in the third: the cell's three checked steps see it in ``loss``
    and ``change``, whatever its first gradient reads."""
    cache = tmp_path_factory.getbasetemp() / "graphs"
    cell = harness.load_cell("agnn.amazon")
    cell.traffic = dict(cell.traffic, scale=0.01)
    monkeypatch.setattr(cell.model, "program_step",
                        calibrate_faults.with_momentum(cell, coefficient))
    result, lines = harness.run(cell, 2**31 + 7, 0.2, False,
                                t_start=time.perf_counter(), interpret=True,
                                graph_cache=cache)
    check = result["check"]
    assert not result["correct"], lines
    assert check["loss"]["value"] > check["loss"]["limit"], lines
    assert check["change"]["value"] > check["change"]["limit"], lines
