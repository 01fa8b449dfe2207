"""Fault-injection harness: corrupt on purpose, assert raise-or-recover.

Every corruption class below maps to one concrete failure a deployed
sparse runtime meets — a bad converter writing out-of-bounds columns, a
checkpoint truncating a leaf, NaNs leaking in from a diverged training
run, an int8 scale that saturates, a tile config the kernel cannot
launch.  For each class the harness asserts the
hardened runtime (DESIGN.md §15) does exactly one of:

* **raise** — ``check="full"`` validation rejects the object with a
  :class:`~repro.core.validate.ValidationError` naming the violated
  invariant (never a shape error from deep inside a kernel);
* **recover** — the op degrades down the fallback ladder
  (``strict=False``) and still matches the dense oracle;
* **count** — the event is absorbed by design (int8 saturation clips)
  and surfaces in :func:`repro.core.metrics.counters`.

Use from tests (:func:`run_fault`, :func:`run_fault_suite`) or as a CLI
for CI::

    python -m repro.testing.faults --op spmm --impl pallas --no-strict
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Dict, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core import dispatch as _dispatch
from repro.core import metrics as _metrics
from repro.core import validate as _validate
from repro.core.format import block_format, from_coo, to_dense
from repro.core.sddmm import attention as _attention
from repro.core.sddmm import sddmm as _sddmm
from repro.core.spmm import spmm as _spmm
from repro.core.spmm import spmm_dense_ref
from repro.core.validate import ValidationError

__all__ = [
    "FAULTS",
    "FaultNotDetected",
    "corrupt_blocked",
    "run_fault",
    "run_fault_suite",
]


class FaultNotDetected(AssertionError):
    """An injected fault sailed through: no named error, no recovery."""


# fault name -> (kind, invariants the validator may name for it)
FAULTS: Dict[str, Tuple[str, Tuple[str, ...]]] = {
    "oob_col": ("format", ("col-in-bounds",)),
    "swapped_win_ptr": ("format", ("win-ptr-monotone", "win-ptr-bounds")),
    "truncated_leaf": ("format", ("leaf-length",)),
    "nonfinite_values": ("format", ("values-finite",)),
    "dtype_mismatch": ("format", ("dtype-mismatch",)),
    "duplicate_coo": ("input", ("duplicate-coords",)),
    "oversized_block_config": ("config", ("block-config",)),
    "kernel_launch_failure": ("runtime", ()),
    "int8_saturation": ("counter", ()),
}


def _example(m: int = 64, k: int = 64, n: int = 16, density: float = 0.15,
             seed: int = 0):
    rng = np.random.default_rng(seed)
    dense = ((rng.random((m, k)) < density)
             * rng.standard_normal((m, k))).astype(np.float32)
    dense[3] = (rng.standard_normal(k)
                * (rng.random(k) < 0.6)).astype(np.float32)  # hub row
    rows, cols = np.nonzero(dense)
    fmt = from_coo(rows, cols, dense[rows, cols], (m, k))
    b = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((m, n)), jnp.float32)
    kk = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)
    return dense, fmt, b, q, kk, v


def corrupt_blocked(blocked, fault: str):
    """Return a copy of ``blocked`` with ``fault`` injected (host-side)."""
    vals = np.asarray(blocked.vals).copy()
    cols = np.asarray(blocked.cols).copy()
    mask = np.asarray(blocked.mask).copy()
    wptr = np.asarray(blocked.win_ptr).copy()
    if fault == "oob_col":
        cols[0] = blocked.shape[1] + 7
        return dataclasses.replace(blocked, cols=jnp.asarray(cols))
    if fault == "swapped_win_ptr":
        if wptr[-1] <= wptr[1]:
            raise ValueError("matrix too empty to break win_ptr monotonicity")
        wptr[1], wptr[-1] = wptr[-1], wptr[1]
        return dataclasses.replace(blocked, win_ptr=jnp.asarray(wptr))
    if fault == "truncated_leaf":
        return dataclasses.replace(
            blocked, vals=jnp.asarray(vals[:-blocked.k_blk]))
    if fault == "nonfinite_values":
        pos = np.argwhere(mask)
        if pos.size == 0:
            raise ValueError("no owned nonzero to poison")
        r, c = pos[0]
        vals[r, c] = np.nan
        return dataclasses.replace(blocked, vals=jnp.asarray(vals))
    if fault == "dtype_mismatch":
        return dataclasses.replace(
            blocked, win_ptr=jnp.asarray(wptr, jnp.float32))
    raise KeyError(f"not a format-level fault: {fault!r}")


def _call_op(op: str, impl: str, fmt, b, q, k, v, **kw):
    if op == "spmm":
        return _spmm(fmt, b, impl=impl, **kw)
    if op == "sddmm":
        return _sddmm(fmt, q, k, impl=impl, **kw)
    if op == "attention":
        return _attention(fmt, q, k, v, impl=impl, **kw)
    raise KeyError(f"unknown op {op!r}")


def _oracle(op: str, dense, b, q, k, v, blocked):
    if op == "spmm":
        return spmm_dense_ref(jnp.asarray(dense), b)
    if op == "sddmm":
        # blocked-layout scores: the pure-XLA rung is itself the oracle
        # (bitwise-checked against sddmm_dense_ref in tier-1 tests)
        from repro.core.sddmm import _sddmm_blocked_impl

        return _sddmm_blocked_impl(blocked, q, k)
    if op == "attention":
        return _attention(blocked, q, k, v, impl="blocked")
    raise KeyError(f"unknown op {op!r}")


def _record(fault, op, impl, mode, detail, ok=True):
    return {"fault": fault, "op": op, "impl": impl, "mode": mode,
            "detail": detail, "ok": ok}


def run_fault(fault: str, *, op: str = "spmm", impl: str = "blocked",
              strict: bool = True, interpret: Optional[bool] = None,
              seed: int = 0) -> Dict:
    """Inject ``fault`` against ``op``/``impl``; assert raise-or-recover.

    Returns a record dict (``mode`` is ``"raise"``, ``"recover"``, or
    ``"counter"``); raises :class:`FaultNotDetected` if the corruption
    goes unnoticed, and re-raises any *unnamed* error (the whole point is
    that failures are named or absorbed, never a bare IndexError from a
    kernel body).
    """
    kind, invariants = FAULTS[fault]
    dense, fmt, b, q, k, v = _example(seed=seed)
    blocked = block_format(fmt, 8)

    if kind == "format":
        bad = corrupt_blocked(blocked, fault)
        try:
            with warnings.catch_warnings():
                warnings.simplefilter("ignore")
                _call_op(op, impl, bad, b, q, k, v, check="full",
                         interpret=interpret)
        except ValidationError as e:
            if e.invariant not in invariants:
                raise FaultNotDetected(
                    f"{fault}: wrong invariant {e.invariant!r}, "
                    f"expected one of {invariants}") from e
            return _record(fault, op, impl, "raise", e.invariant)
        raise FaultNotDetected(f"{fault}: check='full' accepted the "
                               f"corrupted format")

    if fault == "duplicate_coo":
        rows, cols_np = np.nonzero(dense)
        vals_np = dense[rows, cols_np]
        rows2 = np.concatenate([rows, rows[:3]])
        cols2 = np.concatenate([cols_np, cols_np[:3]])
        vals2 = np.concatenate([vals_np, vals_np[:3]])
        try:
            from_coo(rows2, cols2, vals2, dense.shape, duplicates="error")
        except ValidationError as e:
            if e.invariant not in invariants:
                raise FaultNotDetected(
                    f"{fault}: wrong invariant {e.invariant!r}") from e
            # the coalescing mode must also recover to the summed oracle
            f2 = from_coo(rows2, cols2, vals2, dense.shape,
                          duplicates="sum")
            summed = dense.copy()
            summed[rows[:3], cols_np[:3]] += vals_np[:3]
            if not np.allclose(np.asarray(to_dense(f2)), summed,
                               atol=1e-6):
                raise FaultNotDetected(
                    f"{fault}: duplicates='sum' did not coalesce")
            return _record(fault, op, impl, "raise", e.invariant)
        raise FaultNotDetected(f"{fault}: duplicates='error' accepted "
                               f"duplicate coordinates")

    if fault == "oversized_block_config":
        try:
            block_format(fmt, k_blk=2 ** 20)
        except ValidationError as e:
            if e.invariant not in invariants:
                raise FaultNotDetected(
                    f"{fault}: wrong invariant {e.invariant!r}") from e
            return _record(fault, op, impl, "raise", e.invariant)
        raise FaultNotDetected(f"{fault}: block_format accepted k_blk=2**20")

    if fault == "kernel_launch_failure":
        # n_blk=0 cannot tile any output: the Pallas wrappers die at grid
        # construction.  strict=True must surface that; strict=False must
        # degrade down the ladder and still match the oracle.
        run_impl = impl if impl.startswith("pallas") else "pallas"
        kw = dict(n_blk=0, interpret=interpret)
        if op == "sddmm":
            kw = dict(f_blk=0, interpret=interpret)
        if op == "attention":
            # fused attention has no free output tile; stage the failure
            # through the staged pipeline's n_blk instead
            run_impl = "pallas_staged"
            kw = dict(interpret=interpret)
            kw["n_blk"] = 0
        if strict:
            try:
                _call_op(op, run_impl, blocked, b, q, k, v, strict=True,
                         **kw)
            except ValidationError:
                raise
            except Exception as e:
                return _record(fault, op, run_impl, "raise",
                               type(e).__name__)
            raise FaultNotDetected(f"{fault}: zero tile launched?")
        with warnings.catch_warnings(record=True) as wlog:
            warnings.simplefilter("always")
            with _dispatch.record_calls() as calls:
                out = _call_op(op, run_impl, blocked, b, q, k, v,
                               strict=False, **kw)
        oracle = _oracle(op, dense, b, q, k, v, blocked)
        if not np.allclose(np.asarray(out, np.float32),
                           np.asarray(oracle, np.float32), atol=1e-4):
            raise FaultNotDetected(f"{fault}: fallback result does not "
                                   f"match the oracle")
        fb = [c for c in calls if c[1].startswith("fallback:")]
        warned = [w for w in wlog
                  if issubclass(w.category, _dispatch.FallbackWarning)]
        if not fb or not warned:
            raise FaultNotDetected(f"{fault}: recovery left no fallback "
                                   f"record/warning (calls={calls})")
        return _record(fault, op, run_impl, "recover", fb[-1][1])

    if fault == "int8_saturation":
        from repro.core.quantize import quantize_blocked

        _metrics.reset_counters("int8_clip")
        x = jnp.asarray(np.linspace(-300.0, 300.0, 256, dtype=np.float32)
                        .reshape(32, 8))
        qv, sc = quantize_blocked(x, 8, scale=1.0)   # |x| > 127 saturates
        n_clip = _metrics.counters().get("int8_clip", 0)
        if n_clip <= 0:
            raise FaultNotDetected(f"{fault}: clip counter did not fire")
        arr = np.asarray(qv)
        if arr.min() < -127 or arr.max() > 127:
            raise FaultNotDetected(f"{fault}: quantize overflowed int8")
        del sc
        return _record(fault, op, impl, "counter", f"int8_clip={n_clip}")

    raise KeyError(f"unknown fault {fault!r}")


def run_fault_suite(op: str = "spmm", impl: str = "blocked", *,
                    strict: bool = True,
                    interpret: Optional[bool] = None) -> List[Dict]:
    """Run every fault class against ``op``/``impl``; return the records."""
    return [run_fault(name, op=op, impl=impl, strict=strict,
                      interpret=interpret)
            for name in FAULTS]


def _main(argv=None) -> int:
    import argparse

    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--op", default="spmm",
                   choices=("spmm", "sddmm", "attention"))
    p.add_argument("--impl", default="blocked")
    p.add_argument("--strict", dest="strict", action="store_true",
                   default=True)
    p.add_argument("--no-strict", dest="strict", action="store_false")
    p.add_argument("--interpret", action="store_true", default=None)
    p.add_argument("--fault", default=None, choices=sorted(FAULTS),
                   help="run one fault class instead of the full suite")
    a = p.parse_args(argv)
    names = [a.fault] if a.fault else list(FAULTS)
    failed = 0
    for name in names:
        try:
            rec = run_fault(name, op=a.op, impl=a.impl, strict=a.strict,
                            interpret=a.interpret)
            print(f"  ok  {name:<24} {rec['mode']:<8} {rec['detail']}")
        except FaultNotDetected as e:
            failed += 1
            print(f"FAIL  {name:<24} {e}")
    print(f"{len(names) - failed}/{len(names)} fault classes handled "
          f"(op={a.op}, impl={a.impl}, strict={a.strict})")
    return 1 if failed else 0


if __name__ == "__main__":
    raise SystemExit(_main())
