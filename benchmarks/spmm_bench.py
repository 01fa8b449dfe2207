"""Paper Fig. 11 / Table 5: SpMM throughput across execution paths.

Baseline classes mapped to this framework (DESIGN.md §8):
  dense-XLA         cuSPARSE-class dense baseline (XLA dot on the dense A)
  coo-segment       CUDA-core-class (Sputnik/RoDe data flow: edge scatter)
  blocked-16x1      DTC-SpMM/TC-GNN-class (same pipeline, V=16 vectors)
  blocked-8x1       FlashSparse (swap-and-transpose V=8), XLA path
  pallas-8x1        FlashSparse Pallas kernel (interpret mode on CPU)

N ∈ {128, 256} per the paper.  GFLOPS = 2·nnz·N / time.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import block_format, from_coo, spmm_blocked, spmm_coo_segment
from repro.core.format import window_skew
from repro.core.spmm import spmm_dense_ref

from .common import attach_bench_json, balance_cost, dtype_bytes
from .common import emit_bench_json as common_emit
from .common import (
    geomean,
    overlap_makespan,
    overlap_suite,
    skewed_suite,
    suite,
    time_fn,
    write_csv,
)

# precision levels recorded per shape for the fused kernel: dtype tag →
# (precision kwarg, dense/out element bytes, sparse-value element bytes)
DTYPE_LEVELS = (
    ("float32", None, 4, 4),
    ("bfloat16", "bf16", 2, 2),
    ("int8", "int8", 2, 1),   # values int8 + fp32/blk scale, B/out bf16
)


def bench_records(scale: float = 0.002, n_values=(128,),
                  verbose: bool = True):
    """Machine-readable per-impl records (op, impl, shape, sparsity, dtype,
    median_ms, hbm_bytes) for the perf trajectory (BENCH_spmm.json).

    Timed in interpret mode (kernel bodies run in Python), so ``scale`` is
    kept small; the modeled HBM bytes are exact structural counts either
    way.  ``pallas_staged`` is the pre-fusion staged-gather baseline the
    fused kernel is regressed against.  The fused kernel is additionally
    recorded per precision level (:data:`DTYPE_LEVELS`) with element-size-
    aware HBM bytes — the bf16/fp32 modeled-traffic ratio is the CI floor
    of the mixed-precision path (DESIGN.md §13).
    """
    from repro.kernels import ops

    recs = []
    for g in suite(scale):
        shape = (g.num_nodes, g.num_nodes)
        fmt = from_coo(g.rows, g.cols, g.vals, shape, vector_size=8)
        blocked = block_format(fmt, k_blk=8)
        sparsity = 1.0 - g.num_edges / float(shape[0] * shape[1])
        for n in n_values:
            b = jnp.asarray(np.random.default_rng(0).standard_normal(
                (g.num_nodes, n)).astype(np.float32))
            n_blk_eff = min(128, max(n, 1))
            impls = [
                ("pallas_staged", "staged", 8,
                 lambda: ops.spmm_staged(blocked, b, interpret=True)),
                ("pallas_noncoalesced", "noncoalesced", 8,
                 lambda: ops.spmm_noncoalesced(blocked, b, interpret=True)),
            ]
            for impl, model, k_blk, fn in impls:
                recs.append({
                    "op": "spmm", "impl": impl, "matrix": g.name,
                    "shape": [shape[0], shape[1], n], "sparsity": sparsity,
                    "dtype": "float32",
                    "vector_size": 8, "k_blk": k_blk, "n_blk": n_blk_eff,
                    "median_ms": time_fn(fn, reps=3, warmup=1),
                    "hbm_bytes": ops.spmm_hbm_bytes(
                        blocked, n, n_blk=n_blk_eff, impl=model),
                })
            for dt, prec, vb, vvb in DTYPE_LEVELS:
                fn = lambda: ops.spmm(blocked, b, interpret=True,
                                      precision=prec)
                recs.append({
                    "op": "spmm", "impl": "pallas_fused", "matrix": g.name,
                    "shape": [shape[0], shape[1], n], "sparsity": sparsity,
                    "dtype": dt,
                    "vector_size": 8, "k_blk": 8, "n_blk": n_blk_eff,
                    "median_ms": time_fn(fn, reps=3, warmup=1),
                    "hbm_bytes": ops.spmm_hbm_bytes(
                        blocked, n, n_blk=n_blk_eff, impl="fused",
                        value_bytes=vb, vals_value_bytes=vvb),
                })
            if verbose:
                by = {r["impl"]: r for r in recs
                      if r["matrix"] == g.name and r["shape"][2] == n
                      and r["dtype"] == "float32"}
                fused32 = max(by["pallas_fused"]["hbm_bytes"], 1)
                red = by["pallas_staged"]["hbm_bytes"] / fused32
                bf16 = next(r["hbm_bytes"] for r in recs
                            if r["matrix"] == g.name and r["shape"][2] == n
                            and r["impl"] == "pallas_fused"
                            and r["dtype"] == "bfloat16")
                print(f"  {g.name:16s} N={n:3d} HBM staged/fused {red:.2f}x | "
                      f"fp32/bf16 {fused32 / max(bf16, 1):.2f}x")
    return recs


def emit_bench_json(recs, path: str = "BENCH_spmm.json") -> dict:
    """Write BENCH_spmm.json and return the aggregate summary."""
    return common_emit(recs, path, op="spmm", fused_impl="pallas_fused",
                       baseline_impl="pallas_staged")


def skewed_records(scale: float = 0.002, n_values=(128,),
                   split_blk: int = 1, verbose: bool = True):
    """Balanced-vs-window records on the hub-row skewed suite.

    Per (matrix, N): the window-parallel fused kernel and the
    block-parallel balanced kernel, each with measured median ms, modeled
    HBM bytes, and the idle-cell-adjusted :func:`balance_cost` — the
    metric the CI floor checks (the HBM byte counts are near-identical by
    construction; the schedule buys critical-path, not traffic).  Also
    asserts the two kernels agree bitwise on every matrix, so the perf
    record can never drift from a broken kernel.
    """
    from repro.kernels import ops

    recs = []
    for g, skew in skewed_suite(scale):
        shape = (g.num_nodes, g.num_nodes)
        fmt = from_coo(g.rows, g.cols, g.vals, shape, vector_size=8)
        blocked = block_format(fmt, k_blk=8)
        schedule = blocked.schedule(split_blk)
        sparsity = 1.0 - g.num_edges / float(shape[0] * shape[1])
        wskew = window_skew(fmt)
        for n in n_values:
            b = jnp.asarray(np.random.default_rng(0).standard_normal(
                (g.num_nodes, n)).astype(np.float32))
            n_blk_eff = min(128, max(n, 1))
            out_f = ops.spmm(blocked, b, n_blk=n_blk_eff, interpret=True)
            out_b = ops.spmm_balanced(blocked, b, schedule=schedule,
                                      n_blk=n_blk_eff, interpret=True)
            assert np.array_equal(np.asarray(out_f), np.asarray(out_b)), \
                f"balanced/fused mismatch on {g.name}"
            impls = [
                ("pallas_fused", "fused", "window",
                 lambda: ops.spmm(blocked, b, n_blk=n_blk_eff,
                                  interpret=True)),
                ("pallas_balanced", "balanced", "balanced",
                 lambda: ops.spmm_balanced(blocked, b, schedule=schedule,
                                           n_blk=n_blk_eff, interpret=True)),
            ]
            for impl, model, cost_model, fn in impls:
                recs.append({
                    "op": "spmm", "impl": impl, "matrix": g.name,
                    "shape": [shape[0], shape[1], n], "sparsity": sparsity,
                    "dtype": "float32",
                    "skew_exponent": skew, "window_skew": round(wskew, 2),
                    "vector_size": 8, "k_blk": 8, "n_blk": n_blk_eff,
                    "split_blk": split_blk if impl == "pallas_balanced" else 0,
                    "median_ms": time_fn(fn, reps=3, warmup=1),
                    "hbm_bytes": ops.spmm_hbm_bytes(
                        blocked, n, n_blk=n_blk_eff, impl=model,
                        schedule=schedule),
                    "balance_cost": balance_cost(
                        blocked, n, impl=cost_model, schedule=schedule,
                        n_blk=n_blk_eff),
                })
            if verbose:
                by = {r["impl"]: r for r in recs
                      if r["matrix"] == g.name and r["shape"][2] == n}
                red = (by["pallas_fused"]["balance_cost"]
                       / max(by["pallas_balanced"]["balance_cost"], 1))
                print(f"  {g.name:16s} N={n:3d} skew={wskew:6.1f} "
                      f"window/balanced cost {red:.2f}x")
    return recs


def device_balance_records(scale: float = 0.002, num_devices=(2, 4, 8),
                           split_blk: int = 1, verbose: bool = True):
    """Inter-device partition-balance records on the skewed suite
    (DESIGN.md §12).

    For each hub-row matrix and device count, partitions the block-
    parallel schedule with :func:`repro.distributed.sparse_shard
    .device_balance` — the same cost model and cut selection the sharded
    ops run — in **both** partition modes: ``window_split=True`` (hub
    windows may straddle a cut; the SpMM/SDDMM execution path, incl.
    ``ad_plan``'s ``fwd_part``/``bwd_part``) and ``window_split=False``
    (window-aligned, the fused-attention path, where a hub window larger
    than a device's fair share structurally pins the balance — recorded
    so the gap stays visible).  The CI floor asserts ``max/mean <= 1.25``
    at 8 devices on every skew >= 1.5 matrix for the straddling
    partitioner.  Host-side only: no multi-device runtime is needed to
    audit partition quality.
    """
    from repro.distributed.sparse_shard import device_balance

    recs = []
    for g, skew in skewed_suite(scale):
        shape = (g.num_nodes, g.num_nodes)
        fmt = from_coo(g.rows, g.cols, g.vals, shape, vector_size=8)
        blocked = block_format(fmt, k_blk=8)
        wskew = window_skew(fmt)
        for ndev in num_devices:
            for window_split in (True, False):
                bal = device_balance(blocked, ndev, split_blk=split_blk,
                                     window_split=window_split)
                recs.append({
                    "op": "spmm", "impl": "pallas_sharded",
                    "matrix": g.name, "shape": [shape[0], shape[1], 128],
                    "dtype": "float32",
                    "skew_exponent": skew, "window_skew": round(wskew, 2),
                    "vector_size": 8, "k_blk": 8, "split_blk": split_blk,
                    "num_devices": ndev, "window_split": window_split,
                    "device_costs": bal["costs"],
                    "device_balance_max_over_mean": bal["max_over_mean"],
                })
                if verbose:
                    tag = "straddle" if window_split else "aligned "
                    print(f"  {g.name:16s} D={ndev} {tag} device balance "
                          f"max/mean {bal['max_over_mean']:.3f}")
    return recs


def overlap_records(scale: float = 0.002, num_devices=(4, 8),
                    n_batches=(1, 2, 4), n: int = 128,
                    verbose: bool = True):
    """Overlapped-ring vs. bulk-psum makespan records (DESIGN.md §14).

    For each matrix × device count × batch count, prices both reassembly
    strategies of the sharded SpMM with :func:`benchmarks.common
    .overlap_makespan` — the same ``batch_costs`` partition the
    ``pallas_sharded_overlap`` ops execute.  Two matrix classes:

      * :func:`overlap_suite` (``floored=True``) — degree-uniform /
        power-law matrices whose cost-balanced cuts are row-balanced;
        the ring's padded messages stay compact and the CI floor
        asserts best-over-``n_batches`` improvement ≥ 1.15× at 8
        devices on every one.
      * :func:`skewed_suite` (``floored=False``) — hub matrices where
        the tail device owns most rows, the padded buffer blows up and
        the model honestly reports < 1; recorded so the regime boundary
        stays visible in the artifact.

    Host-side only (cost model on the partition), like
    :func:`device_balance_records`.
    """
    recs = []
    mats = [(g, kind, True) for g, kind in overlap_suite(scale)]
    mats += [(g, f"hub-{skew}", False) for g, skew in skewed_suite(scale)]
    for g, kind, floored in mats:
        shape = (g.num_nodes, g.num_nodes)
        fmt = from_coo(g.rows, g.cols, g.vals, shape, vector_size=8)
        blocked = block_format(fmt, k_blk=8)
        for ndev in num_devices:
            best = 0.0
            for nb in n_batches:
                ms = overlap_makespan(blocked, n, num_devices=ndev,
                                      n_batches=nb)
                best = max(best, ms["improvement"])
                recs.append({
                    "op": "spmm", "impl": "pallas_sharded_overlap",
                    "matrix": g.name, "shape": [shape[0], shape[1], n],
                    "dtype": "float32", "matrix_kind": kind,
                    "floored": floored, "vector_size": 8, "k_blk": 8,
                    "num_devices": ndev, "n_batches": nb,
                    "makespan_bulk": ms["bulk"],
                    "makespan_overlapped": ms["overlapped"],
                    "makespan_improvement": ms["improvement"],
                    "compute_cost": ms["compute"],
                    "comm_bulk": ms["comm_bulk"],
                    "comm_ring": ms["comm_ring"],
                    "pad_rows": ms["pad_rows"],
                })
            if verbose:
                tag = "floor" if floored else "info "
                print(f"  {g.name:16s} D={ndev} {tag} overlap/bulk "
                      f"best {best:.2f}x")
    return recs


def _overlap_summary(recs) -> dict:
    """Best-over-``n_batches`` overlap improvement per (matrix, D); the
    floored statistic is the minimum over the row-balanced suite at 8
    devices (CI asserts ≥ 1.15×)."""
    best: dict = {}
    for r in recs:
        key = (r["matrix"], r["num_devices"], r["floored"])
        best[key] = max(best.get(key, 0.0), r["makespan_improvement"])

    def stats(ndev, floored):
        vals = [v for (m, d, f), v in best.items()
                if d == ndev and f is floored]
        return vals

    floored8 = stats(8, True)
    return {
        "overlap_makespan_improvement_min_8dev":
            min(floored8) if floored8 else 0.0,
        "overlap_makespan_improvement_geomean_8dev": geomean(floored8),
        "overlap_makespan_improvement_geomean_4dev": geomean(stats(4, True)),
        "overlap_makespan_improvement_hub_geomean_8dev":
            geomean(stats(8, False)),
        "num_overlap_records": len(recs),
    }


def _device_balance_summary(recs) -> dict:
    """Worst-case partition skew at 8 devices over the sharded records.

    The floored statistic is the straddling partitioner (the SpMM/SDDMM
    execution path); the window-aligned figure is informational — it is
    structurally pinned by the largest hub window."""
    def worst(window_split):
        vals = [r["device_balance_max_over_mean"] for r in recs
                if r.get("num_devices") == 8
                and r.get("window_split") is window_split]
        return max(vals) if vals else 1.0

    return {
        "device_balance_max_over_mean_8dev": worst(True),
        "device_balance_max_over_mean_8dev_window_aligned": worst(False),
        "num_device_balance_records": len(recs),
    }


def _skew_summary(recs) -> dict:
    """Balanced-vs-window cost reduction over the skewed records."""
    bal = {(r["matrix"], tuple(r["shape"])): r["balance_cost"]
           for r in recs if r["impl"] == "pallas_balanced"}
    ratios = [r["balance_cost"] / max(bal[(r["matrix"], tuple(r["shape"]))], 1)
              for r in recs if r["impl"] == "pallas_fused"
              and (r["matrix"], tuple(r["shape"])) in bal]
    return {
        "balanced_cost_reduction_geomean": geomean(ratios),
        "balanced_cost_reduction_min": min(ratios) if ratios else 0.0,
        "num_skewed_records": len(ratios) * 2,
    }


def run_op(scale: float = 0.002, skewed: bool = False,
           datasets: bool = False, verbose: bool = True,
           bench_json: str = "BENCH_spmm.json"):
    """``benchmarks.run --op spmm [--skewed] [--datasets]``: emit
    BENCH_spmm.json.

    Always contains the standard fused/staged/noncoalesced records
    (so the staged-vs-fused HBM floor stays checkable from the same
    artifact); ``skewed=True`` appends the hub-row balanced-vs-window
    records (the ≥ 1.3× CI floor on skew ≥ 1.5 matrices), the device-
    partition balance records, and the §14 overlapped-ring makespan
    records (the ≥ 1.15× floor at 8 devices on the row-balanced suite),
    folding all their summaries in.  ``datasets=True`` appends the
    vendored real-matrix records (:mod:`benchmarks.datasets_bench`) —
    per-structure-class impl winners with a dense-oracle parity floor.
    """
    recs = bench_records(scale=scale, verbose=verbose)
    extra = {}
    if skewed:
        skew_recs = skewed_records(scale=scale, verbose=verbose)
        dev_recs = device_balance_records(scale=scale, verbose=verbose)
        ovl_recs = overlap_records(scale=scale, verbose=verbose)
        recs = recs + skew_recs + dev_recs + ovl_recs
        extra = {**_skew_summary(skew_recs),
                 **_device_balance_summary(dev_recs),
                 **_overlap_summary(ovl_recs)}
    if datasets:
        from .datasets_bench import dataset_records, datasets_summary

        ds_recs = dataset_records(verbose=verbose)
        recs = recs + ds_recs
        extra = {**extra, **datasets_summary(ds_recs)}
    result = {}
    attach_bench_json(result, recs, bench_json, op="spmm",
                      fused_impl="pallas_fused",
                      baseline_impl="pallas_staged", extra_summary=extra,
                      verbose=verbose)
    if skewed and verbose:
        print(f"  skewed: window/balanced cost geomean "
              f"{extra['balanced_cost_reduction_geomean']:.2f}x "
              f"(min {extra['balanced_cost_reduction_min']:.2f}x)")
        print(f"  overlap: ring/bulk makespan 8dev geomean "
              f"{extra['overlap_makespan_improvement_geomean_8dev']:.2f}x "
              f"(min {extra['overlap_makespan_improvement_min_8dev']:.2f}x)")
    return result


def run(scale: float = 0.02, n_values=(128, 256), include_pallas: bool = False,
        verbose: bool = True, bench_json: str | None = "BENCH_spmm.json"):
    rows = []
    for g in suite(scale):
        shape = (g.num_nodes, g.num_nodes)
        nnz = g.num_edges
        f8 = from_coo(g.rows, g.cols, g.vals, shape, vector_size=8)
        f16 = from_coo(g.rows, g.cols, g.vals, shape, vector_size=16)
        b8 = block_format(f8, k_blk=8)
        b16 = block_format(f16, k_blk=8)
        rows_d = jnp.asarray(g.rows)
        cols_d = jnp.asarray(g.cols)
        vals_d = jnp.asarray(g.vals)

        dense_a = None
        if g.num_nodes <= 60_000:
            dense_a = jnp.asarray(
                np.zeros(shape, np.float32)) if False else None
        for n in n_values:
            b = jnp.asarray(
                np.random.default_rng(0).standard_normal(
                    (g.num_nodes, n)).astype(np.float32))
            flops = 2.0 * nnz * n

            t_coo = time_fn(lambda: spmm_coo_segment(
                rows_d, cols_d, vals_d, b, num_rows=g.num_nodes))
            t8 = time_fn(lambda: spmm_blocked(b8, b))
            t16 = time_fn(lambda: spmm_blocked(b16, b))
            entry = {
                "matrix": g.name, "nnz": nnz, "N": n,
                "gflops_coo": flops / t_coo / 1e6,
                "gflops_blocked8": flops / t8 / 1e6,
                "gflops_blocked16": flops / t16 / 1e6,
                "speedup_8_vs_coo": t_coo / t8,
                "speedup_8_vs_16": t16 / t8,
            }
            if include_pallas:
                from repro.kernels import ops
                t_pl = time_fn(lambda: ops.spmm(b8, b))
                entry["gflops_pallas8"] = flops / t_pl / 1e6
            rows.append(entry)
            if verbose:
                print(f"  {g.name:16s} N={n:3d} "
                      f"coo {entry['gflops_coo']:7.2f} | "
                      f"16x1 {entry['gflops_blocked16']:7.2f} | "
                      f"8x1 {entry['gflops_blocked8']:7.2f} GFLOPS | "
                      f"8v16 {entry['speedup_8_vs_16']:.2f}x")
    gm = geomean([r["speedup_8_vs_16"] for r in rows])
    gm_coo = geomean([r["speedup_8_vs_coo"] for r in rows])
    if verbose:
        print(f"  geomean speedup 8x1 vs 16x1: {gm:.2f}x | vs coo: {gm_coo:.2f}x")
    write_csv("fig11_spmm.csv", rows)
    result = {"geomean_8_vs_16": gm, "geomean_8_vs_coo": gm_coo, "rows": rows}
    if bench_json:
        # interpret-mode kernels run their bodies in Python → small scale
        attach_bench_json(
            result, bench_records(scale=min(scale, 0.002), verbose=verbose),
            bench_json, op="spmm", fused_impl="pallas_fused",
            baseline_impl="pallas_staged", verbose=verbose)
    return result


if __name__ == "__main__":
    run()
