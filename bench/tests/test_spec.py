"""``BENCHMARK.json`` and the files it names: every cell, configuration,
traffic mix and metric is found by its name."""

import json
import os
import re
import subprocess
import sys

from bench import harness

SPEC = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_every_name_finds_its_files():
    configs = {c["name"] for c in SPEC["configs"]}
    for c in SPEC["configs"]:
        assert NAME.match(c["name"]) and (harness.ROOT / c["file"]).is_file()
        assert c["reduced"] == harness.load_json(
            harness.ROOT / c["file"])["reduced"]
    for w in SPEC["workloads"]:
        assert NAME.match(w["name"]) and w["config"] in configs
        cell = harness.load_cell(w["name"])
        assert cell.limits["check_steps"] >= 2
        assert set(cell.limits["limits"]) | set(cell.limits.get(
            "not_compared", {})) == {"loss", "grad", "change", "out_grad"}
        assert hasattr(cell.model, "program_step")
        assert cell.end_to_end and cell.per_layer
    for m in SPEC["per_layer"]:
        assert (harness.BENCH / "metrics" / f"{m['name']}.py").is_file()
        for w in m.get("workloads", []):
            assert w in {x["name"] for x in SPEC["workloads"]}
    assert {"setup_s", "step_ms"} <= {m["name"] for m in SPEC["end_to_end"]}


def test_no_chip_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(harness.BENCH / "run.py"), "--workload",
         SPEC["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], env=env, capture_output=True, text=True,
        timeout=120)
    assert proc.returncode == 2 and proc.stdout == ""
