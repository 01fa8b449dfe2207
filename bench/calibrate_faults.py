"""Readings of a cell's check under faults and lower-precision references
that ``bench/calibrate.py`` does not plant, on the chip at the cell's own
size, in one process:

    python3 bench/calibrate_faults.py --workload agnn.amazon \
        --seeds 101-112 --fault-seeds 101-104 \
        --out experiments/bench/faults.jsonl

For every seed it runs the cell's checked steps through the program and
the float64 reference once, and prints the check's numbers.  For each
fault seed it also puts in the program's place

  * ``ref_high``: the plain reference with every matrix product at
    ``high`` (the TPU's three bf16 passes), the configuration's
    reference one precision below its ``highest``;
  * ``ref_three_pass``: the plain reference with its products written
    out as three bf16 passes (``reference.three_pass``);
  * ``no_momentum``: the program's step from zero momentum each step,
    plain SGD;
  * ``half_momentum``: the program's step with its momentum rescaled to
    a coefficient of 0.5;

and compares each with the same float64 reference.  On the CPU (tiny
graphs, ``--scale``) the kernels are interpreted.  The benchmark's own
runs never run these.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
# The TPU runtime's logs would go to a fixed path under /tmp.
os.environ.setdefault("TPU_LOG_DIR", "disabled")
_PLATFORMS = os.environ.get("JAX_PLATFORMS", "")
if _PLATFORMS and "cpu" not in _PLATFORMS.split(","):
    os.environ["JAX_PLATFORMS"] = _PLATFORMS + ",cpu"
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def with_momentum(cell, coefficient):
    """The program's step with the momentum it is handed rescaled, so that
    its update keeps ``coefficient`` of the last one in place of the
    configuration's momentum."""
    import jax

    program = cell.model.program_step
    scale = coefficient / cell.config["momentum"]

    def factory(cfg, interpret):
        step = program(cfg, interpret)

        def call(params, mom, adj, x, labels, mask):
            mom = jax.tree.map(lambda m: m * scale, mom)
            return step(params, mom, adj, x, labels, mask)

        return call

    return factory


def high(a, b):
    import jax
    import jax.numpy as jnp

    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGH)


def plants(cell):
    from bench import calibrate, reference

    return {"ref_high": calibrate.reference_in_place(cell, high),
            "ref_three_pass": calibrate.reference_in_place(
                cell, reference.three_pass),
            "no_momentum": with_momentum(cell, 0.0),
            "half_momentum": with_momentum(cell, 0.5)}


def main(argv=None) -> int:
    from bench.calibrate import seed_list

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seed_list, required=True)
    ap.add_argument("--fault-seeds", type=seed_list, default=[])
    ap.add_argument("--scale", type=float, default=None,
                    help="the traffic's scale, for a rehearsal on the CPU")
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    from bench import harness

    cell = harness.load_cell(args.workload)
    if args.scale is not None:
        cell.traffic = dict(cell.traffic, scale=args.scale)
    harness.enable_compile_cache()
    import jax

    interpret = jax.default_backend() == "cpu"
    if not interpret and jax.default_backend() != "tpu":
        print("calibrate_faults: needs a TPU, or the CPU", file=sys.stderr)
        return 2
    jax.config.update("jax_default_matmul_precision",
                      cell.config["matmul_precision"])
    spans = harness.Spans()
    graph = harness.build_graph(cell, spans)
    k = cell.limits["check_steps"]
    faults = plants(cell)
    out = pathlib.Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    with open(out, "a") as f:
        for seed in args.seeds:
            t = time.perf_counter()
            trained = harness.train(cell, graph, seed, 0,
                                    interpret=interpret, spans=spans)
            rows = [("program", trained, time.perf_counter() - t)]
            t = time.perf_counter()
            ref = harness.reference_run(cell, graph, trained.params0,
                                        *trained.data, k)
            t_ref = time.perf_counter() - t
            if seed in args.fault_seeds:
                for name, factory in faults.items():
                    t = time.perf_counter()
                    rows.append((name, harness.train(
                        cell, graph, seed, 0, interpret=interpret,
                        spans=spans, step_factory=factory),
                        time.perf_counter() - t))
            for name, tr, secs in rows:
                rec = {"workload": cell.name, "seed": seed, "run": name,
                       "check_steps": k,
                       "numbers": harness.numbers(tr, ref,
                                                  cell.model.out_leaf),
                       "losses": tr.losses.tolist(),
                       "ref_losses": ref.losses.tolist(),
                       "dispatch_ok": harness.dispatch_ok(tr.dispatch),
                       "seconds": secs, "reference_s": t_ref}
                f.write(json.dumps(rec) + "\n")
                f.flush()
                print(json.dumps(rec), flush=True)
    print(json.dumps({"spans": spans.s,
                      "total_s": time.perf_counter() - T0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
