"""Run one benchmark cell on the chip this process finds.

    python3 bench/run.py --workload gcn.amazon --seed 7 --seconds 10 --trace 0

The cell, its configuration, traffic, limits and metrics are found by name
from ``BENCHMARK.json`` (see ``bench/harness.py``).  Set-up loads the
graph, builds the program's format and plan, makes weights and inputs from
``--seed`` and runs the first training steps, which the plain reference
then checks; the window runs training steps back to back for about
``--seconds``.  ``--trace 1`` profiles the window and reports the
per-layer metrics instead of the end-to-end ones.

The check's numbers and limits are the last lines on standard error; the
last line on standard output is the result, as JSON.  Without a TPU, or
with fewer chips than the cell asks for, it exits with 2 and prints no
result.
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
# The float64 reference runs on the host's CPU backend, which a platform
# list without "cpu" would leave out.
_PLATFORMS = os.environ.get("JAX_PLATFORMS", "")
if _PLATFORMS and "cpu" not in _PLATFORMS.split(","):
    os.environ["JAX_PLATFORMS"] = _PLATFORMS + ",cpu"
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness

    cell = harness.load_cell(args.workload)
    harness.enable_compile_cache()
    import jax

    if jax.default_backend() != "tpu" or len(jax.devices()) < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} TPU chip(s); JAX "
              f"finds {len(jax.devices())} {jax.default_backend()} "
              f"device(s)", file=sys.stderr)
        return 2
    result, lines = harness.run(cell, args.seed, args.seconds,
                                bool(args.trace), t_start=T0)
    sys.stdout.flush()
    print("\n".join(lines), file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
