"""Cross-impl conformance harness: every registered impl vs the dense oracle.

The dispatch registry (:mod:`repro.core.dispatch`) is the single table of
every ``(op, impl)`` path in the system; this module is the single
harness that proves the *whole* table correct on real matrices, not just
the synthetic generators the unit tests use.  For each loaded
:class:`~repro.data.datasets.MatrixSample` it:

  1. enumerates every registered ``(op, impl, precision)`` combination
     (:func:`enumerate_cases`) plus ``split_blk`` and overlap variants
     where the capability flags allow them — nothing is hand-listed, so
     a newly registered impl is covered the day it lands;
  2. runs each against the dense numpy oracle under the per-
     ``(op, precision)`` tolerance ladder (PR-6 / DESIGN.md §13):
     fp32 ≈ 2e-4, bf16 ≈ 2e-2, int8 ≈ 5e-2 with max-scaled atol;
  3. reports a structured pass/fail matrix (:class:`ConformanceRecord`
     rows; :func:`summarize` / :func:`format_report` for humans).

Output contracts are normalized per impl: blocked-layout SDDMM values
are scattered back through the format, and the edge-value ``coo`` impl
is compared in ``to_coo`` order.

:func:`self_test` proves the harness can actually catch a wrong kernel:
it registers a deliberately broken impl and raises
:class:`~repro.testing.faults.FaultNotDetected` unless the run reports
it failing (the PR-8 convention — a green harness that cannot go red is
not a harness).

CLI (fully offline; the CI ``real-matrix-conformance`` job runs it on
the vendored set)::

    python -m repro.testing.conformance                    # full matrix
    python -m repro.testing.conformance --datasets tridiag_64,hub_96
    python -m repro.testing.conformance --op spmm --precision fp32
    python -m repro.testing.conformance --self-test
"""

from __future__ import annotations

import argparse
import dataclasses
import sys
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core import dispatch as _dispatch

__all__ = [
    "ConformanceCase",
    "ConformanceRecord",
    "enumerate_cases",
    "tolerance",
    "run_case",
    "run_conformance",
    "summarize",
    "format_report",
    "self_test",
]

OPS = ("spmm", "sddmm", "attention")

# Feature dims for the dense operands (small: the matrices carry the
# structure, the operands only need to be wide enough to exercise tiling).
N_FEAT = 16


@dataclasses.dataclass(frozen=True)
class ConformanceCase:
    """One (op, impl, precision, variant) combination to execute.

    ``variant``: ``"base"`` (plain call), ``"split"`` (``split_blk=2``
    on load-balanced impls), ``"overlap"`` (``n_batches=2`` on overlapped
    impls).
    Variants run at fp32 only — precision expansion happens on the base
    variant, variants probe scheduling/communication paths.
    """

    op: str
    impl: str
    precision: str
    variant: str = "base"

    @property
    def label(self) -> str:
        tag = f"{self.impl}[{self.precision}]"
        return tag if self.variant == "base" else f"{tag}+{self.variant}"


@dataclasses.dataclass(frozen=True)
class ConformanceRecord:
    """Outcome of one case on one matrix."""

    matrix: str
    structure_class: str
    op: str
    impl: str
    precision: str
    variant: str
    status: str            # "pass" | "fail" | "skip"
    max_err: float = 0.0   # max |out - ref| over the compared values
    detail: str = ""       # failure exception / skip reason


def enumerate_cases(ops: Sequence[str] = OPS,
                    impl_names: Optional[Sequence[str]] = None,
                    precisions: Optional[Sequence[str]] = None,
                    ) -> List[ConformanceCase]:
    """Every registered combination, straight from the dispatch registry."""
    cases: List[ConformanceCase] = []
    for op in ops:
        for name in _dispatch.impls(op):
            if impl_names is not None and name not in impl_names:
                continue
            entry = _dispatch.get(op, name)
            for prec in entry.precisions:
                if precisions is not None and prec not in precisions:
                    continue
                cases.append(ConformanceCase(op, name, prec))
            if precisions is not None and "fp32" not in precisions:
                continue
            if entry.load_balanced:
                cases.append(ConformanceCase(op, name, "fp32", "split"))
            if entry.overlapped:
                cases.append(ConformanceCase(op, name, "fp32", "overlap"))
    return cases


def tolerance(op: str, precision: str, ref: np.ndarray
              ) -> Tuple[float, float]:
    """(rtol, atol) of the PR-6 ladder for this op/precision, atol scaled
    by the oracle's magnitude (real matrices are not unit-scale)."""
    scale = max(float(np.max(np.abs(ref))) if ref.size else 0.0, 1.0)
    if precision == "int8":
        return 5e-2, 5e-2 * scale
    if precision == "bf16":
        r = 5e-2 if op == "attention" else 2e-2
        return r, r * scale
    if op == "attention":
        return 2e-3, 2e-3 * scale
    return 2e-4, 2e-4 * scale


# ---------------------------------------------------------------------------
# Oracles + output normalization
# ---------------------------------------------------------------------------


_MESH = None


def _conformance_mesh():
    """Single-device ``(data=1, model=1)`` mesh for the multi_device impls.

    One device suffices for conformance — the D∈{2,4,8} parity runs live
    in the forced-host-device child-process tests (tests/test_sparse_
    shard*.py); here the sharded code path itself must agree with the
    oracle on real matrices.
    """
    global _MESH
    if _MESH is None:
        from repro.launch.mesh import make_host_mesh

        _MESH = make_host_mesh(1, 1)
    return _MESH


def _attention_oracle(mask: np.ndarray, q: np.ndarray, k: np.ndarray,
                      v: np.ndarray, scale: float) -> np.ndarray:
    """Masked-softmax dense reference; rows with no pattern entries → 0."""
    scores = (q @ k.T) * scale
    scores = np.where(mask, scores, -1e30)
    mx = scores.max(axis=-1, keepdims=True)
    e = np.exp(scores - mx) * mask
    denom = e.sum(axis=-1, keepdims=True)
    p = np.where(denom > 0, e / np.maximum(denom, 1e-30), 0.0)
    return (p @ v).astype(np.float32)


def _scatter_blocked(blocked, vals: np.ndarray, shape) -> np.ndarray:
    """Blocked-layout (NNZP, V) values → dense (masked positions only)."""
    from repro.core.format import to_coo
    from repro.core.sddmm import with_values

    rows, cols, v = to_coo(with_values(blocked, vals))
    out = np.zeros(shape, np.float32)
    out[rows, cols] = v
    return out


def run_case(case: ConformanceCase, sample, operands) -> ConformanceRecord:
    """Execute one case on one sample; never raises (failures become
    ``status="fail"`` records — the CI contract is *zero unexplained
    failures*, so an exception is an explained failure, not a crash)."""
    from repro.core.format import to_coo
    from repro.core.sddmm import attention, sddmm
    from repro.core.spmm import spmm

    cls = operands["structure_class"]

    def rec(status, max_err=0.0, detail=""):
        return ConformanceRecord(sample.name, cls, case.op, case.impl,
                                 case.precision, case.variant, status,
                                 max_err, detail)

    entry = _dispatch.get(case.op, case.impl)
    if case.op == "attention" and not sample.is_square:
        return rec("skip", detail="attention needs a square pattern")

    fmt = operands["fmt"]
    dense = operands["dense"]
    mask = operands["mask"]
    q, k, v, b = (operands[x] for x in ("q", "k", "v", "b"))

    kw: Dict[str, object] = {"impl": case.impl}
    if case.precision != "fp32":
        kw["precision"] = case.precision
    if entry.multi_device:
        kw["mesh"] = _conformance_mesh()
    if case.variant == "split":
        kw["split_blk"] = 2
    if case.variant == "overlap":
        kw["n_batches"] = 2

    try:
        if case.op == "spmm":
            out = np.asarray(spmm(fmt, b, **kw), np.float32)
            ref = dense @ np.asarray(b)
        elif case.op == "sddmm":
            dense_scores = (np.asarray(q) @ np.asarray(k).T) * mask
            if case.impl == "coo":  # edge values in to_coo(fmt) order
                rows, cols, _ = to_coo(fmt)
                out = np.asarray(sddmm(fmt, q, k, **kw), np.float32)
                ref = dense_scores[rows, cols]
            else:  # blocked-layout (NNZP, V) for the entry's k_blk=8
                from repro.core.format import block_format

                raw = sddmm(fmt, q, k, **kw)
                blocked = operands.setdefault(
                    "blocked", block_format(fmt, k_blk=8))
                out = _scatter_blocked(blocked, np.asarray(raw, np.float32),
                                       sample.shape)
                ref = dense_scores
        else:  # attention
            scale = 1.0 / np.sqrt(N_FEAT)
            out = np.asarray(attention(fmt, q, k, v, scale=scale, **kw),
                             np.float32)
            ref = _attention_oracle(mask, np.asarray(q), np.asarray(k),
                                    np.asarray(v), scale)
    except Exception as e:  # noqa: BLE001 — recorded, not raised
        return rec("fail", detail=f"{type(e).__name__}: {str(e)[:200]}")

    rtol, atol = tolerance(case.op, case.precision, ref)
    err = np.abs(out - ref)
    bound = atol + rtol * np.abs(ref)
    max_err = float(err.max()) if err.size else 0.0
    if out.shape != ref.shape:
        return rec("fail", detail=f"shape {out.shape} != ref {ref.shape}")
    if not np.all(np.isfinite(out)):
        return rec("fail", max_err=float("inf"), detail="non-finite output")
    if np.any(err > bound):
        worst = float((err - bound).max())
        return rec("fail", max_err=max_err,
                   detail=f"tolerance exceeded by {worst:.3g} "
                          f"(rtol={rtol:g}, atol={atol:.3g})")
    return rec("pass", max_err=max_err)


def _operands_for(sample, rng: np.random.Generator) -> Dict[str, object]:
    """Shared per-matrix operands (one format build per matrix)."""
    import jax.numpy as jnp

    m, kd = sample.shape
    fmt = sample.to_format()
    mask = np.zeros(sample.shape, bool)
    mask[sample.rows, sample.cols] = True
    return {
        "fmt": fmt,
        "dense": sample.dense(),
        "mask": mask,
        "structure_class": sample.meta.get("structure_class")
        or sample.structure_class(),
        "b": jnp.asarray(rng.standard_normal((kd, N_FEAT)), jnp.float32),
        "q": jnp.asarray(rng.standard_normal((m, N_FEAT)), jnp.float32),
        "k": jnp.asarray(rng.standard_normal((kd, N_FEAT)), jnp.float32),
        "v": jnp.asarray(rng.standard_normal((kd, N_FEAT)), jnp.float32),
    }


def run_conformance(samples=None, ops: Sequence[str] = OPS,
                    impl_names: Optional[Sequence[str]] = None,
                    precisions: Optional[Sequence[str]] = None,
                    seed: int = 0, verbose: bool = False,
                    ) -> List[ConformanceRecord]:
    """The harness: every enumerated case on every sample.

    ``samples=None`` loads the full vendored set (plus any fetched
    downloads).  Returns the flat record list; see :func:`summarize` /
    :func:`format_report`.
    """
    if samples is None:
        from repro.data.datasets import load_vendored

        samples = load_vendored()
    cases = enumerate_cases(ops, impl_names, precisions)
    records: List[ConformanceRecord] = []
    for sample in samples:
        operands = _operands_for(sample, np.random.default_rng(seed))
        for case in cases:
            record = run_case(case, sample, operands)
            records.append(record)
            if verbose:
                mark = {"pass": ".", "skip": "s", "fail": "F"}[record.status]
                print(f"  {mark} {sample.name:16s} {case.op:9s} "
                      f"{case.label:28s} {record.detail}", flush=True)
    return records


def summarize(records: Sequence[ConformanceRecord]) -> Dict[str, object]:
    """Counts + the full failure list (empty ⇔ the registry conforms)."""
    counts = {"pass": 0, "fail": 0, "skip": 0}
    for r in records:
        counts[r.status] += 1
    failures = [dataclasses.asdict(r) for r in records if r.status == "fail"]
    impls_covered = sorted({(r.op, r.impl) for r in records})
    return {
        "total": len(records),
        **counts,
        "matrices": sorted({r.matrix for r in records}),
        "impl_pairs_covered": len(impls_covered),
        "failures": failures,
    }


def format_report(records: Sequence[ConformanceRecord]) -> str:
    """Human-readable pass/fail matrix: one row per (op, impl, precision,
    variant), one column per matrix."""
    matrices = sorted({r.matrix for r in records})
    by_key: Dict[Tuple[str, str, str, str], Dict[str, ConformanceRecord]] = {}
    for r in records:
        by_key.setdefault((r.op, r.impl, r.precision, r.variant),
                          {})[r.matrix] = r
    width = max((len(m) for m in matrices), default=8)
    lines = []
    header = " " * 44 + "".join(f"{m:>{width + 1}}" for m in matrices)
    lines.append(header)
    glyph = {"pass": "ok", "fail": "FAIL", "skip": "-"}
    for (op, impl, prec, variant) in sorted(by_key):
        tag = f"{impl}[{prec}]" + ("" if variant == "base" else f"+{variant}")
        row = f"{op:10s}{tag:34s}"
        for m in matrices:
            r = by_key[(op, impl, prec, variant)].get(m)
            cell = glyph[r.status] if r else ""
            row += f"{cell:>{width + 1}}"
        lines.append(row)
    s = summarize(records)
    lines.append(f"\n{s['pass']} pass, {s['fail']} fail, {s['skip']} skip "
                 f"over {len(matrices)} matrices x "
                 f"{s['impl_pairs_covered']} (op, impl) pairs")
    for f in s["failures"]:
        lines.append(f"  FAIL {f['matrix']} {f['op']}/{f['impl']}"
                     f"[{f['precision']}]+{f['variant']}: {f['detail']}")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# Harness self-test
# ---------------------------------------------------------------------------


def self_test(sample=None) -> None:
    """Prove the harness catches a wrong kernel (PR-8 convention).

    Registers a deliberately broken SpMM impl (correct shape, wrong
    values), runs the harness over it, and raises
    :class:`~repro.testing.faults.FaultNotDetected` unless the run
    reports it as failing.  Always deregisters the broken impl.
    """
    from repro.testing.faults import FaultNotDetected

    if sample is None:
        from repro.data.datasets import load_vendored

        sample = load_vendored(["tridiag_64"])[0]

    def broken_spmm(fmt, b, **kwargs):
        import jax.numpy as jnp

        return jnp.zeros((fmt.shape[0], b.shape[-1]), jnp.float32) + 0.125

    name = "_conformance_broken"
    _dispatch.register("spmm", name, broken_spmm)
    try:
        records = run_conformance([sample], ops=("spmm",),
                                  impl_names=[name])
        if not records:
            raise FaultNotDetected(
                "conformance harness enumerated no cases for a freshly "
                "registered impl")
        if not all(r.status == "fail" for r in records):
            raise FaultNotDetected(
                "conformance harness passed a deliberately broken SpMM "
                f"impl: {[dataclasses.asdict(r) for r in records]}")
    finally:
        _dispatch._REGISTRY.pop(("spmm", name), None)
        _dispatch._sig_cache.pop(("spmm", name), None)


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.testing.conformance",
        description="Run every registered (op, impl, precision) against "
                    "the dense oracle on the vendored real matrices.")
    ap.add_argument("--datasets", default=None,
                    help="comma-separated sample names (default: all "
                         "vendored + fetched)")
    ap.add_argument("--op", choices=OPS, action="append", default=None,
                    help="restrict to an op (repeatable; default: all)")
    ap.add_argument("--impl", action="append", default=None,
                    help="restrict to an impl name (repeatable)")
    ap.add_argument("--precision", choices=("fp32", "bf16", "int8"),
                    action="append", default=None,
                    help="restrict precisions (repeatable; default: all)")
    ap.add_argument("--self-test", action="store_true",
                    help="verify the harness flags a broken impl, then exit")
    ap.add_argument("--verbose", action="store_true",
                    help="print one line per case as it runs")
    args = ap.parse_args(argv)

    if args.self_test:
        self_test()
        print("conformance self-test ok: broken impl reported as failing")
        return 0

    from repro.data.datasets import load_vendored

    names = args.datasets.split(",") if args.datasets else None
    samples = load_vendored(names)
    records = run_conformance(
        samples, ops=tuple(args.op) if args.op else OPS,
        impl_names=args.impl, precisions=args.precision,
        verbose=args.verbose)
    print(format_report(records))
    return 1 if any(r.status == "fail" for r in records) else 0


if __name__ == "__main__":
    raise SystemExit(main())
