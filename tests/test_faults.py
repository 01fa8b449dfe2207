"""Fault-injection matrix (DESIGN.md §15): every corruption class either
raises a named-invariant error or recovers to the oracle, across ops,
impls, strictness modes, and (in child processes) sharded/overlap runs."""

import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
sys.path.insert(0, SRC)

from repro.testing.faults import (  # noqa: E402
    FAULTS,
    FaultNotDetected,
    run_fault,
    run_fault_suite,
)

from _child import run_child  # noqa: E402


# ---------------------------------------------------------------------------
# Single-device matrix
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_handled_strict(fault):
    rec = run_fault(fault, op="spmm", impl="blocked", strict=True)
    assert rec["ok"] and rec["mode"] in ("raise", "recover", "counter")


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_handled_no_strict(fault):
    rec = run_fault(fault, op="spmm", impl="pallas", strict=False,
                    interpret=True)
    assert rec["ok"]
    if fault == "kernel_launch_failure":
        assert rec["mode"] == "recover"
        assert rec["detail"].startswith("fallback:")


@pytest.mark.parametrize("op,impl", [
    ("spmm", "pallas"),
    ("sddmm", "pallas"),
    ("attention", "pallas_staged"),
])
def test_fault_suite_per_op(op, impl):
    recs = run_fault_suite(op, impl, strict=False, interpret=True)
    assert len(recs) == len(FAULTS)
    assert all(r["ok"] for r in recs)
    modes = {r["fault"]: r["mode"] for r in recs}
    assert modes["kernel_launch_failure"] == "recover"
    assert modes["oob_col"] == "raise"
    assert modes["int8_saturation"] == "counter"


def test_undetected_fault_is_an_error(monkeypatch):
    """The harness itself must fail loudly if a corruption slips through:
    silence validation and the format faults become FaultNotDetected."""
    import repro.testing.faults as faults_mod

    def call_without_check(op, impl, fmt, b, q, k, v, **kw):
        kw.pop("check", None)
        from repro.core.spmm import spmm

        return spmm(fmt, b, impl=impl, check="none")

    monkeypatch.setattr(faults_mod, "_call_op", call_without_check)
    with pytest.raises(FaultNotDetected):
        run_fault("oob_col", op="spmm", impl="blocked")


def test_cli_entry_point():
    out = subprocess.run(
        [sys.executable, "-m", "repro.testing.faults", "--op", "spmm",
         "--impl", "blocked", "--strict", "--fault", "oob_col"],
        capture_output=True, text=True,
        env=dict(os.environ, PYTHONPATH=SRC), timeout=60)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "1/1 fault classes handled" in out.stdout


# ---------------------------------------------------------------------------
# Sharded / overlapped paths (child processes: forced host devices)
# ---------------------------------------------------------------------------


def test_sharded_validation_and_fallback_child():
    run_child("""
    import dataclasses
    import warnings
    import numpy as np, jax.numpy as jnp
    import pytest
    from repro.core import block_format, from_dense, spmm, dispatch
    from repro.core.spmm import spmm_dense_ref
    from repro.core.validate import ValidationError, validate_sharded
    from repro.distributed.sparse_shard import sharded_schedule
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh(2, 1)
    rng = np.random.default_rng(0)
    m = 64
    a = ((rng.random((m, m)) < 0.12)
         * rng.standard_normal((m, m))).astype(np.float32)
    a[5, :] = rng.standard_normal(m) * (rng.random(m) < 0.8)
    blocked = block_format(from_dense(a), 8)
    b = jnp.asarray(rng.standard_normal((m, 32)).astype(np.float32))

    # 1. tampered sharded partition is rejected with a named invariant
    part = sharded_schedule(blocked, 2, split_blk=1)
    validate_sharded(part, blocked=blocked, check="full")
    ro = np.asarray(part.row_own).copy(); ro[0, :] = False
    try:
        validate_sharded(dataclasses.replace(part, row_own=jnp.asarray(ro)),
                         blocked=blocked, check="full")
        raise SystemExit("tampered row_own accepted")
    except ValidationError as e:
        assert e.invariant in ("row-own-consistent", "row-own-cover"), e

    # 2. sharded kernel-launch failure (n_blk=0) degrades to the oracle
    with warnings.catch_warnings(record=True) as wlog:
        warnings.simplefilter("always")
        with dispatch.record_calls() as calls:
            out = spmm(blocked, b, impl="pallas_sharded", mesh=mesh,
                       n_blk=0, strict=False)
    ref = spmm_dense_ref(jnp.asarray(a), b)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
    fb = [c for c in calls if c[1].startswith("fallback:pallas_sharded->")]
    assert fb, calls
    assert any(issubclass(w.category, dispatch.FallbackWarning)
               for w in wlog)

    # 3. strict mode surfaces the failure instead
    try:
        spmm(blocked, b, impl="pallas_sharded", mesh=mesh, n_blk=0,
             strict=True)
        raise SystemExit("strict=True swallowed the launch failure")
    except ValidationError:
        raise
    except Exception:
        pass
    print("SHARDED_FAULTS_OK")
    """, devices=2, timeout=60)


def test_overlap_validation_and_fallback_child():
    run_child("""
    import warnings
    import numpy as np, jax.numpy as jnp
    from repro.core import block_format, from_dense, spmm, dispatch
    from repro.core.spmm import spmm_dense_ref
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh(2, 1)
    rng = np.random.default_rng(1)
    m = 64
    a = ((rng.random((m, m)) < 0.12)
         * rng.standard_normal((m, m))).astype(np.float32)
    blocked = block_format(from_dense(a), 8)
    b = jnp.asarray(rng.standard_normal((m, 32)).astype(np.float32))

    # overlapped impl with an impossible tile: ladder walks
    # pallas_sharded_overlap -> pallas_sharded -> ... -> blocked
    with warnings.catch_warnings(record=True) as wlog:
        warnings.simplefilter("always")
        with dispatch.record_calls() as calls:
            out = spmm(blocked, b, impl="pallas_sharded_overlap", mesh=mesh,
                       n_batches=2, n_blk=0, strict=False)
    ref = spmm_dense_ref(jnp.asarray(a), b)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
    fb = [c for c in calls
          if c[1].startswith("fallback:pallas_sharded_overlap->")]
    assert fb, calls
    assert any(issubclass(w.category, dispatch.FallbackWarning)
               for w in wlog)
    print("OVERLAP_FAULTS_OK")
    """, devices=2, timeout=60)
