"""Benchmark orchestrator: one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run            # full suite
  PYTHONPATH=src python -m benchmarks.run --quick    # reduced scale
  PYTHONPATH=src python -m benchmarks.run --only fig1,table7
  PYTHONPATH=src python -m benchmarks.run --op grad_spmm  # fwd+bwd timing

Artifacts land in experiments/bench/*.csv; the summary block printed at
the end is the cross-check against the paper's headline numbers.  The
fig11/fig13 benches additionally emit machine-readable BENCH_spmm.json /
BENCH_sddmm.json (op, impl, shape, sparsity, median ms, modeled HBM bytes
per record) so future PRs have a perf trajectory to regress against.
"""

from __future__ import annotations

import argparse
import json
import os
import time

BENCHES = {
    "fig1": ("mma_counts", "Fig. 1 — MMA invocations 16x1 vs 8x1"),
    "table2": ("zeros_in_vectors", "Table 2 — zeros in nonzero vectors"),
    "fig11": ("spmm_bench", "Fig. 11/Table 5 — SpMM throughput"),
    "fig12": ("data_access", "Fig. 12 — data access cost"),
    "fig13": ("sddmm_bench", "Fig. 13/Table 6 — SDDMM throughput"),
    "fig14": ("ablation_vector_size", "Fig. 14 — vector-size ablation"),
    "fig15": ("ablation_coalescing", "Fig. 15 — coalescing ablation"),
    "table7": ("format_memory", "Table 7 — ME-BCRS memory footprint"),
    "fig16": ("gnn_e2e", "Fig. 16/Table 8 — end-to-end GNN"),
}

# --op modes, not part of the default figure suite — select explicitly:
#   grad_spmm / grad_sddmm — gradient (fwd+bwd) trajectories through the
#     autodiff layer, incl. batched (H, ...) grids vs the per-slice loop,
#     emitting BENCH_grad.json (DESIGN.md §9);
#   attn — fused sparse-attention megakernel vs the staged 3-dispatch
#     pipeline, emitting BENCH_attn.json (DESIGN.md §10);
#   spmm — kernel-path records into BENCH_spmm.json; with --skewed, adds
#     the hub-row balanced-vs-window scheduling comparison (DESIGN.md §11)
#     whose ≥ 1.3× cost floor CI enforces.
GRAD_OPS = {
    "grad_spmm": "spmm",
    "grad_sddmm": "sddmm",
}
OP_MODES = sorted(GRAD_OPS) + ["attn", "spmm"]

_EPILOG = """\
op benchmark modes (--op NAME, not part of the default figure suite):
  grad_spmm    SpMM forward+backward timing per impl through the autodiff
               duality (DESIGN.md §9), incl. batched (H, ...) grids vs the
               per-slice loop; emits BENCH_grad.json
  grad_sddmm   same fwd+bwd trajectory for SDDMM; emits BENCH_grad.json
  attn         single-pass fused sparse-attention megakernel vs the staged
               3-dispatch pipeline (DESIGN.md §10); emits BENCH_attn.json
  spmm         SpMM kernel-path records (fused/staged/noncoalesced);
               emits BENCH_spmm.json

modifier flags:
  --skewed     with --op spmm: add the hub-row skewed suite — the
               balanced-vs-window scheduling comparison (DESIGN.md §11,
               >= 1.3x cost floor in CI) and the per-device partition
               balance records (DESIGN.md §12, max/mean <= 1.25 floor at
               8 devices)
  --datasets   with --op spmm: add the vendored real-matrix set
               (tests/data/, structure-taxonomy-tagged) — per-class impl
               winner records with a dense-oracle parity floor
               (summary key datasets_parity_ok must be true in CI)

examples:
  python -m benchmarks.run --op attn --scale 0.002
  python -m benchmarks.run --op spmm --skewed --scale 0.002
  python -m benchmarks.run --op spmm --datasets --scale 0.002
"""


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description=__doc__, epilog=_EPILOG,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument("--only", default=None,
                   help="comma-separated subset of: " + ",".join(BENCHES))
    p.add_argument("--op", default=None, choices=OP_MODES,
                   help="run an op benchmark mode instead of the figure "
                        "suite (writes BENCH_grad.json / BENCH_attn.json / "
                        "BENCH_spmm.json)")
    p.add_argument("--skewed", action="store_true",
                   help="with --op spmm: add hub-row skewed matrices and "
                        "the balanced-vs-window scheduling comparison")
    p.add_argument("--datasets", action="store_true",
                   help="with --op spmm: add the vendored real-matrix set "
                        "with per-structure-class winner records")
    p.add_argument("--quick", action="store_true")
    p.add_argument("--scale", type=float, default=None)
    args = p.parse_args(argv)
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()

    scale = args.scale or (0.005 if args.quick else 0.02)

    if args.op == "spmm":
        from benchmarks import spmm_bench

        print("\n=== §11 SpMM kernel paths"
              + (" + block-parallel scheduling (skewed)" if args.skewed
                 else "")
              + (" + real-matrix set (datasets)" if args.datasets
                 else "") + " ===")
        t0 = time.time()
        # interpret-mode kernel bodies run in Python → small scale
        out = spmm_bench.run_op(scale=min(scale, 0.002), skewed=args.skewed,
                                datasets=args.datasets)
        print(f"\n=== summary ({time.time() - t0:.0f}s) ===")
        print(json.dumps(out, indent=2, default=str))
        return 0

    if args.op == "attn":
        from benchmarks import attn_bench

        print("\n=== §10 fused attention — megakernel vs staged ===")
        t0 = time.time()
        out = attn_bench.run(scale=scale)
        out.pop("rows", None)
        print(f"\n=== summary ({time.time() - t0:.0f}s) ===")
        print(json.dumps(out, indent=2, default=str))
        return 0

    if args.op is not None:
        from benchmarks import grad_bench

        print(f"\n=== §9 backward duality — {args.op} fwd+bwd per impl ===")
        t0 = time.time()
        out = grad_bench.run(scale=scale, op=GRAD_OPS[args.op])
        out.pop("rows", None)
        print(f"\n=== summary ({time.time() - t0:.0f}s) ===")
        print(json.dumps(out, indent=2, default=str))
        return 0

    selected = list(BENCHES) if not args.only else args.only.split(",")

    summary = {}
    t_start = time.time()
    for key in selected:
        mod_name, title = BENCHES[key]
        mod = __import__(f"benchmarks.{mod_name}", fromlist=["run"])
        print(f"\n=== {title} ===")
        t0 = time.time()
        kwargs = {"scale": scale}
        if key == "fig14":
            kwargs["scale"] = min(scale, 0.01)
        if key == "fig16":
            kwargs["scale"] = min(scale, 0.01)
        if key == "fig15":
            # interpret-mode Pallas executes the kernel body in Python —
            # the non-coalesced ablation serializes one DMA round trip
            # per nonzero vector
            kwargs["scale"] = min(scale, 0.002)
        out = mod.run(**kwargs)
        out.pop("rows", None)
        summary[key] = {**out, "seconds": round(time.time() - t0, 1)}

    print(f"\n=== summary ({time.time() - t_start:.0f}s) ===")
    print(json.dumps(summary, indent=2, default=str))
    os.makedirs("experiments/bench", exist_ok=True)
    with open("experiments/bench/summary.json", "w") as f:
        json.dump(summary, f, indent=2, default=str)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
