"""The check that decides ``correct``, driven through whole runs on the
CPU at a small size (Pallas kernels interpreted), with the cell's own
limits: a sound run passes, and each fault a one-chip training cell can
have (a step that returns its state unchanged, half of the train nodes
left out) fails at least one number.

The control on the chip is the program traced at ``high`` matrix
precision; the CPU computes float32 products exactly at every precision,
so here the control is the plain reference with its products written out
as three bf16 passes, and it has to read three times the sound run or
more on one of the numbers compared (the cell's limits are set from
full-size readings, far above what either reads at this size)."""

import time

import pytest

from bench import calibrate, harness, reference

CELLS = [w["name"] for w in harness.load_json(
    harness.ROOT / "BENCHMARK.json")["workloads"]]

SCALE = 0.01           # a hundredth of the cell's own graph
SEED = 2**31 + 7


def tiny(workload):
    cell = harness.load_cell(workload)
    cell.traffic = dict(cell.traffic, scale=SCALE)
    return cell


def run_with(cell, factory, monkeypatch, cache):
    if factory is not None:
        monkeypatch.setattr(cell.model, "program_step", factory)
    return harness.run(cell, SEED, 0.2, False, t_start=time.perf_counter(),
                       interpret=True, graph_cache=cache)


PLANTS = {
    "sound": None,
    "frozen": lambda cell, cache: calibrate.frozen(cell),
    "half_batch": lambda cell, cache: calibrate.half_batch(cell),
}


@pytest.mark.parametrize("plant", list(PLANTS))
@pytest.mark.parametrize("workload", CELLS)
def test_run_checks(workload, plant, monkeypatch, tmp_path_factory):
    cache = tmp_path_factory.getbasetemp() / "graphs"
    cell = tiny(workload)
    factory = PLANTS[plant] and PLANTS[plant](cell, cache)
    result, lines = run_with(cell, factory, monkeypatch, cache)
    over = [k for k, v in result["check"].items() if v["value"] > v["limit"]]
    if plant == "sound":
        assert result["correct"] and not over, lines
    else:
        assert not result["correct"] and over, lines
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == {"step_ms", "peak_hbm_mib", "setup_s"}
    assert list(result)[-1] == "check"


@pytest.mark.parametrize("workload", CELLS)
def test_control_separates(workload, monkeypatch, tmp_path_factory):
    cache = tmp_path_factory.getbasetemp() / "graphs"
    sound, _ = run_with(tiny(workload), None, monkeypatch, cache)
    cell = tiny(workload)
    control, lines = run_with(cell, calibrate.reference_in_place(
        cell, reference.three_pass, cache), monkeypatch, cache)
    ratios = {k: control["check"][k]["value"] / sound["check"][k]["value"]
              for k in sound["check"]}
    assert max(ratios.values()) >= 3, (ratios, lines)
