"""Backward-duality benchmark (DESIGN.md §9): fwd vs fwd+bwd per impl.

Times the differentiable sparse ops — forward, and ``jax.grad`` w.r.t.
(vals, dense operand) whose backward is the dispatched transpose-SpMM +
masked SDDMM — for every differentiable registry impl, and emits the
machine-readable ``BENCH_grad.json`` perf record (median ms per op/impl/
matrix, fwd and fwd+bwd) so future PRs can regress the training-path
trajectory, like BENCH_spmm/BENCH_sddmm do for inference.

Multi-head shapes (H > 1) are benchmarked twice for the Pallas path:
``mode="batched"`` runs the native ``(H, ...)`` grids (one launch, the
path batched callers actually take since DESIGN.md §10) and
``mode="per_slice"`` forces the legacy one-grid-per-head loop, so
BENCH_grad.json records the batched-grid win explicitly
(``batched_speedup_geomean`` in the summary).

  PYTHONPATH=src python -m benchmarks.run --op grad_spmm [--scale 0.002]
"""

from __future__ import annotations

import json

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import from_coo
from repro.core.autodiff import ad_plan, sddmm_ad, spmm_ad

from .common import geomean, suite, time_fn, write_csv

IMPLS = ("blocked", "pallas")
N_FEAT = 32
H_BATCHED = 4  # multi-head shape: batched grid vs per-slice loop


def _time_pair(fwd, grad, args):
    fwd_ms = time_fn(fwd, *args, reps=3, warmup=1)
    fwdbwd_ms = time_fn(grad, *args, reps=3, warmup=1)
    return fwd_ms, fwdbwd_ms


def _record(g, op, impl, m, h, mode, fwd_ms, fwdbwd_ms):
    print(f"  {g.name:16s} {impl:14s} H={h} {mode:9s} "
          f"fwd {fwd_ms:8.2f} ms | fwd+bwd {fwdbwd_ms:8.2f} ms")
    return {
        "op": f"grad_{op}",
        "impl": impl,
        "matrix": g.name,
        "h": h,
        "mode": mode,
        "shape": [m, m, N_FEAT],
        "nnz": int(g.num_edges),
        "fwd_ms": round(fwd_ms, 3),
        "fwdbwd_ms": round(fwdbwd_ms, 3),
        "bwd_overhead": round(fwdbwd_ms / max(fwd_ms, 1e-9), 2),
    }


def _bench_matrix(g, op: str, impls) -> list:
    rng = np.random.default_rng(0)
    fmt = from_coo(g.rows, g.cols, g.vals, (g.num_nodes, g.num_nodes),
                   vector_size=8)
    m = g.num_nodes
    b = jnp.asarray(rng.standard_normal((m, N_FEAT)).astype(np.float32))
    q = jnp.asarray(rng.standard_normal((m, N_FEAT)).astype(np.float32))
    b3 = jnp.asarray(rng.standard_normal(
        (H_BATCHED, m, N_FEAT)).astype(np.float32))
    recs = []
    for impl in impls:
        plan = ad_plan(fmt, impl=impl)

        def run_op(vals_or_q, dense):
            if op == "spmm":
                return spmm_ad(plan, vals_or_q, dense, impl=impl,
                               interpret=True)
            return sddmm_ad(plan, vals_or_q, dense, impl=impl,
                            interpret=True)

        # squared-sum loss → a non-uniform cotangent (2·out): a plain
        # .sum() would make every head's backward identical (all-ones g)
        # and let XLA CSE the per-slice loop's H backward kernels into
        # one, faking the comparison
        args = (plan.vals, b) if op == "spmm" else (q, b)
        fwd = jax.jit(run_op)
        grad = jax.jit(jax.grad(lambda x, y: (run_op(x, y) ** 2).sum(),
                                argnums=(0, 1)))
        recs.append(_record(g, op, impl, m, 1, "single",
                            *_time_pair(fwd, grad, args)))

        if impl == "blocked":
            continue  # XLA vmap path: the per-slice comparison is a
            # Pallas-grid story (one launch vs H launches)
        # batched (H, ...) dense operand: native (H, ...) grid, one launch
        args_h = (args[0], b3)
        fwd_h = jax.jit(run_op)
        grad_h = jax.jit(jax.grad(lambda x, y: (run_op(x, y) ** 2).sum(),
                                  argnums=(0, 1)))
        recs.append(_record(g, op, impl, m, H_BATCHED, "batched",
                            *_time_pair(fwd_h, grad_h, args_h)))

        # forced per-slice loop: the pre-§10 path, one grid per head
        def run_loop(x, y3):
            return jnp.stack([run_op(x, y3[i]) for i in range(H_BATCHED)])

        fwd_l = jax.jit(run_loop)
        grad_l = jax.jit(jax.grad(lambda x, y: (run_loop(x, y) ** 2).sum(),
                                  argnums=(0, 1)))
        recs.append(_record(g, op, impl, m, H_BATCHED, "per_slice",
                            *_time_pair(fwd_l, grad_l, args_h)))
    return recs


def run(scale: float = 0.02, op: str = "spmm", impls=IMPLS):
    # interpret-mode Pallas executes kernel bodies in Python: keep the
    # matrix subset small (same reasoning as the fig15 ablation).
    graphs = suite(scale=min(scale, 0.005))[:3]
    recs = []
    for g in graphs:
        recs.extend(_bench_matrix(g, op, impls))

    per_impl = {
        impl: geomean([r["bwd_overhead"] for r in recs if r["impl"] == impl])
        for impl in impls
    }
    # batched-grid win: per-slice fwd+bwd ms / batched fwd+bwd ms at H > 1
    batched = {(r["impl"], r["matrix"]): r["fwdbwd_ms"] for r in recs
               if r["h"] > 1 and r["mode"] == "batched"}
    speedups = {}
    for impl in impls:
        ratios = [r["fwdbwd_ms"] / max(batched[(impl, r["matrix"])], 1e-9)
                  for r in recs if r["impl"] == impl and r["h"] > 1
                  and r["mode"] == "per_slice"]
        if ratios:
            speedups[impl] = round(geomean(ratios), 2)
    summary = {
        "bwd_overhead_geomean": {k: round(v, 2) for k, v in per_impl.items()},
        "batched_speedup_geomean": speedups,
        "num_records": len(recs),
    }
    path = "BENCH_grad.json"
    with open(path, "w") as f:
        json.dump({"op": f"grad_{op}", "summary": summary,
                   "records": recs}, f, indent=2)
    print(f"  wrote {path}: fwd+bwd/fwd geomean "
          + ", ".join(f"{k}={v:.2f}x" for k, v in per_impl.items()))
    write_csv(f"grad_{op}.csv", recs)
    return {"bench": {**summary, "path": path}, "rows": recs}
