"""Readings that a cell's limits are set from, on the chip at the cell's
own size, in one process:

    python3 bench/calibrate.py --workload gcn.amazon --seeds 101-112 \
        --control-seeds 101-104 --out experiments/bench/calib.jsonl

For every seed it runs the cell's checked steps through the program (no
window) and the float64 reference once, and prints the check's numbers.
For each control seed it also puts in the program's place

  * ``control``: the program itself traced at ``high`` matrix precision
    (three bf16 passes), its own path at the precision just below the
    configuration's ``highest``;
  * ``float32``: the plain reference in float32 at the configuration's
    own precision on the chip, a witness of what float32 reads at this
    size;
  * ``half_batch``: the program with half of the train nodes left out of
    the loss, its mean taken over the rest;
  * ``frozen``: the program's step returning the state it was given;

and compares each with the same float64 reference, and with the float32
witness (``vs_float32``).  The benchmark's own runs never run these.
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


def reference_in_place(cell, mm, cache_dir=None):
    """A step factory whose step is the plain reference (float32, matrix
    products ``mm``) with the program's call signature."""
    from bench import graphs, reference

    def factory(cfg, interpret):
        n, rows, cols, vals = graphs.load(cell.traffic,
                                          cache_dir or graphs.CACHE_DIR)
        edges = reference.make_edges(rows, cols, vals, n)
        step = reference.make_train(cell.model.forward, mm, cfg["lr"],
                                    cfg["momentum"])

        def call(params, mom, adj, x, labels, mask):
            params, mom, loss, _ = step(params, mom, edges, x, labels, mask)
            return params, mom, loss, None

        return call

    return factory


def half_batch(cell):
    """The program's step with every other train node left out."""
    import jax.numpy as jnp

    program = cell.model.program_step

    def factory(cfg, interpret):
        step = program(cfg, interpret)

        def call(params, mom, adj, x, labels, mask):
            keep = (jnp.arange(mask.shape[0]) % 2 == 0).astype(mask.dtype)
            return step(params, mom, adj, x, labels, mask * keep)

        return call

    return factory


def frozen(cell):
    """The program's step, returning the state it was given."""
    program = cell.model.program_step

    def factory(cfg, interpret):
        step = program(cfg, interpret)

        def call(params, mom, adj, x, labels, mask):
            _, _, loss, acc = step(params, mom, adj, x, labels, mask)
            return params, mom, loss, acc

        return call

    return factory


def at_precision(factory, precision):
    """``factory``'s step traced at another default matrix precision."""
    import jax

    def wrapped(cfg, interpret):
        step = factory(cfg, interpret)

        def call(*args):
            with jax.default_matmul_precision(precision):
                return step(*args)

        return call

    return wrapped


def seed_list(text):
    out = []
    for part in text.split(","):
        lo, _, hi = part.partition("-")
        out.extend(range(int(lo), int(hi or lo) + 1))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seed_list, required=True)
    ap.add_argument("--control-seeds", type=seed_list, default=[])
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    import numpy as np

    from bench import harness, reference

    cell = harness.load_cell(args.workload)
    harness.enable_compile_cache()
    import jax

    if jax.default_backend() != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 2
    jax.config.update("jax_default_matmul_precision",
                      cell.config["matmul_precision"])
    spans = harness.Spans()
    graph = harness.build_graph(cell, spans)
    k = cell.limits["check_steps"]
    plants = {"control": at_precision(cell.model.program_step, "high"),
              "float32": reference_in_place(cell, reference.exact),
              "half_batch": half_batch(cell),
              "frozen": frozen(cell)}
    out = pathlib.Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    with open(out, "a") as f:
        for seed in args.seeds:
            t = time.perf_counter()
            trained = harness.train(cell, graph, seed, 0, interpret=False,
                                    spans=spans)
            t_prog = time.perf_counter() - t
            t = time.perf_counter()
            ref = harness.reference_run(cell, graph, trained.params0,
                                        *trained.data, k)
            t_ref = time.perf_counter() - t
            rows = [("program", trained, t_prog)]
            if seed in args.control_seeds:
                for name, factory in plants.items():
                    t = time.perf_counter()
                    rows.append((name, harness.train(
                        cell, graph, seed, 0, interpret=False, spans=spans,
                        step_factory=factory), time.perf_counter() - t))
            f32 = dict((r[0], r[1]) for r in rows).get("float32")
            f32 = f32 and harness.RefRun(f32.losses, f32.grad0, f32.params)
            for name, tr, secs in rows:
                rec = {"workload": cell.name, "seed": seed, "run": name,
                       "numbers": harness.numbers(tr, ref,
                                                  cell.model.out_leaf),
                       "vs_float32": f32 and harness.numbers(
                           tr, f32, cell.model.out_leaf),
                       "grad_leaves": [harness.rel_diff(a, b) for a, b in zip(
                           jax.tree.leaves(tr.grad0),
                           jax.tree.leaves(ref.grad0))],
                       "grad_gaps": (np.abs(
                           harness.leaf_norms(tr.grad0)
                           - harness.leaf_norms(ref.grad0))
                           / harness.leaf_norms(ref.grad0)).tolist(),
                       "losses": tr.losses.tolist(),
                       "ref_losses": ref.losses.tolist(),
                       "dispatch_ok": harness.dispatch_ok(tr.dispatch),
                       "seconds": secs, "reference_s": t_ref,
                       "peak_bytes": tr.peak_bytes}
                f.write(json.dumps(rec) + "\n")
                f.flush()
                print(json.dumps(rec), flush=True)
    print(json.dumps({"spans": spans.s,
                      "total_s": time.perf_counter() - T0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
