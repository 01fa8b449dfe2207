"""Record the small TPU trace that ``test_trace.py`` reads, on a TPU host:

    python3 bench/tests/record_trace.py --out bench/tests/data/gcn_small.xplane.pb

It runs the ``gcn.amazon`` cell's training step on its graph cut to a
five-hundredth, traces a window of three steps with the benchmark's own
host spans, and keeps the trace file with the step count beside it.
"""

import argparse
import json
import os
import pathlib
import shutil
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

WORKLOAD, SCALE, SEED, STEPS = "gcn.amazon", 0.002, 11, 3


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    args = ap.parse_args()

    from bench import harness

    import jax

    if jax.default_backend() != "tpu":
        sys.exit("record_trace: needs a TPU")
    cell = harness.load_cell(WORKLOAD)
    cell.traffic = dict(cell.traffic, scale=SCALE)
    jax.config.update("jax_default_matmul_precision",
                      cell.config["matmul_precision"])
    work = harness.WORK_DIR / "record_trace"
    spans = harness.Spans()
    graph = harness.build_graph(cell, spans, work / "graphs")
    warm = harness.train(cell, graph, SEED, 0, interpret=False, spans=spans)
    trained = harness.train(cell, graph, SEED, STEPS * warm.step_s,
                            interpret=False, spans=spans,
                            trace_dir=work / "trace")
    out = pathlib.Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    shutil.copyfile(trained.xplane, out)
    meta = {"workload": WORKLOAD, "scale": SCALE, "seed": SEED,
            "steps": trained.window_steps, "graph": graph.counts,
            "device_kind": jax.devices()[0].device_kind}
    out.with_suffix(".json").write_text(json.dumps(meta, indent=1) + "\n")
    print(json.dumps(meta))


if __name__ == "__main__":
    main()
