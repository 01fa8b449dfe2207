"""Fault-injection tooling for the hardened sparse runtime (DESIGN.md §15).

:mod:`repro.testing.faults` corrupts formats, inputs and kernel configs
on purpose and asserts the runtime either *names the violated invariant*
(:class:`repro.core.validate.ValidationError`) or *recovers* — falls back
down the capability ladder to the oracle answer — or counts the event.
Importable from tests and runnable as a CLI for CI::

    python -m repro.testing.faults --op spmm --impl blocked --strict

:mod:`repro.testing.conformance` is the complementary positive gate: it
runs every registered ``(op, impl, precision)`` combination against the
dense oracle on the vendored real matrices (tests/data/) and reports a
pass/fail matrix::

    python -m repro.testing.conformance --datasets tridiag_64 --precision fp32
"""

from .conformance import (
    ConformanceCase,
    ConformanceRecord,
    enumerate_cases,
    format_report,
    run_conformance,
    summarize,
)
from .conformance import self_test as conformance_self_test
from .faults import (
    FAULTS,
    FaultNotDetected,
    corrupt_blocked,
    run_fault,
    run_fault_suite,
)

__all__ = [
    "FAULTS",
    "ConformanceCase",
    "ConformanceRecord",
    "FaultNotDetected",
    "conformance_self_test",
    "corrupt_blocked",
    "enumerate_cases",
    "format_report",
    "run_conformance",
    "run_fault",
    "run_fault_suite",
    "summarize",
]
