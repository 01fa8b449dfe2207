"""Redundancy metrics reproducing the paper's analysis artifacts, plus
the runtime's own tracing: counters, host spans and device-op tags
(DESIGN.md §15).

Paper metrics (host numpy, derived from the ME-BCRS structure alone, so
exact, not sampled):

  * :func:`zeros_in_nonzero_vectors` — Table 2
  * :func:`mma_count`                — Fig. 1
  * :func:`data_access_bytes`        — Fig. 12 cost model
  * :func:`padded_flops`             — MXU-side redundancy (TPU translation)

Runtime counters (process-global, thread-safe) surface the hardened
runtime's degradation events — int8 saturation clips
(:func:`repro.core.quantize.quantize_blocked` with an external scale),
dispatch fallbacks, fp32 nonfinite re-runs — without a metrics server:
:func:`record_counter` accepts concrete ints *or traced arrays* (the
latter land through ``jax.debug.callback`` at run time, so jitted
quantization still counts), :func:`counters` snapshots,
:func:`reset_counters` clears.

Host spans and device-op tags put the program's own names on a profiler
trace.  :func:`span` times a block on the host (a
``jax.profiler.TraceAnnotation`` on the device trace's clock, plus a
bounded in-memory record per name: :func:`spans`, :func:`reset_spans`);
:func:`op_tag` stamps every XLA op lowered inside it with the frontend
attribute ``flashsparse_op``, so its device ops can be found on the
trace.  Program span and tag names start with ``fs.``.
"""

from __future__ import annotations

import contextlib
import threading
import time
from functools import partial
from typing import TYPE_CHECKING, Dict, Optional

import numpy as np

if TYPE_CHECKING:
    from .format import MEBCRS

__all__ = [
    "zeros_in_nonzero_vectors",
    "mma_count",
    "data_access_bytes",
    "padded_flops",
    "summarize",
    "record_counter",
    "counters",
    "reset_counters",
    "span",
    "spans",
    "reset_spans",
    "op_tag",
]


# ------------------------------------------------------ runtime counters --

_counters: Dict[str, int] = {}
_counters_lock = threading.Lock()


def _add_counter(name: str, n) -> None:
    with _counters_lock:
        _counters[name] = _counters.get(name, 0) + int(n)


def record_counter(name: str, n=1) -> None:
    """Add ``n`` to the process-global counter ``name``.

    ``n`` may be a concrete number or a traced 0-d array: under a tracer
    the increment is attached via ``jax.debug.callback`` and lands when
    the compiled computation actually runs (once per execution, not per
    trace).
    """
    import jax

    if isinstance(n, jax.core.Tracer):
        jax.debug.callback(partial(_add_counter, name), n)
    else:
        _add_counter(name, n)


def counters() -> Dict[str, int]:
    """Snapshot of all runtime counters."""
    with _counters_lock:
        return dict(_counters)


def reset_counters(name: Optional[str] = None) -> None:
    """Clear one counter, or all of them (``name=None``)."""
    with _counters_lock:
        if name is None:
            _counters.clear()
        else:
            _counters.pop(name, None)


# ------------------------------------------------- host spans, op tags --

PREFIX = "fs."

_spans: Dict[str, Dict] = {}
_spans_lock = threading.Lock()
_open = threading.local()       # this thread's stack of open span names


def _check_name(name: str) -> None:
    if not name.startswith(PREFIX):
        raise ValueError(f"program span and tag names start with "
                         f"{PREFIX!r}; got {name!r}")


@contextlib.contextmanager
def span(name: str):
    """Time the enclosed block on the host as span ``name``.

    The block runs under ``jax.profiler.TraceAnnotation(name)``, so a
    profiler trace shows it on the host plane, on the device ops' clock.
    The record of ``name`` (:func:`spans`) keeps the call count, the
    total, longest and last duration in seconds, and the span open
    around its last call on this thread (``parent``, ``None`` at top
    level): one entry per name, whatever the number of calls.  Also a
    decorator.
    """
    import jax

    _check_name(name)
    stack = getattr(_open, "stack", None)
    if stack is None:
        stack = _open.stack = []
    parent = stack[-1] if stack else None
    stack.append(name)
    t0 = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(name):
            yield
    finally:
        dt = time.perf_counter() - t0
        stack.pop()
        with _spans_lock:
            rec = _spans.get(name)
            if rec is None:
                rec = _spans[name] = {"count": 0, "total_s": 0.0,
                                      "max_s": 0.0}
            rec["count"] += 1
            rec["total_s"] += dt
            rec["max_s"] = max(rec["max_s"], dt)
            rec["last_s"] = dt
            rec["parent"] = parent


def spans() -> Dict[str, Dict]:
    """Snapshot of the span records: name -> ``{count, total_s, max_s,
    last_s, parent}``."""
    with _spans_lock:
        return {k: dict(v) for k, v in _spans.items()}


def reset_spans(name: Optional[str] = None) -> None:
    """Clear one span record, or all of them (``name=None``)."""
    with _spans_lock:
        if name is None:
            _spans.clear()
        else:
            _spans.pop(name, None)


def op_tag(name: str):
    """Context manager: every XLA op lowered inside it carries the
    frontend attribute ``flashsparse_op="<name>"``, which fusions that
    absorb a tagged op keep and a profiler trace prints with the op's
    HLO text.  It acts while a function is traced and lowered; the
    compiled program runs as it would untagged."""
    from jax.experimental.xla_metadata import set_xla_metadata

    _check_name(name)
    return set_xla_metadata(flashsparse_op=name)


# MMA operand shapes (paper Table 1): (m, n, k)
MMA_SHAPES = {
    ("fp16", "flashsparse"): (16, 8, 8),   # sparse block on the k×n side → vector = n = 8
    ("tf32", "flashsparse"): (16, 8, 4),
    ("fp16", "sota16"): (16, 8, 8),        # sparse block on the m×k side → vector = m = 16
    ("tf32", "sota16"): (16, 8, 8),
}


def _window_counts(fmt: MEBCRS) -> np.ndarray:
    return np.diff(np.asarray(fmt.row_pointers))


def zeros_in_nonzero_vectors(fmt: MEBCRS) -> int:
    """Explicit zeros carried inside nonzero vectors (paper Table 2)."""
    mask = np.asarray(fmt.mask)
    return int(mask.size - mask.sum())


def mma_count(fmt: MEBCRS, n_cols: int, precision: str = "fp16") -> int:
    """Number of MMA invocations to complete one SpMM (paper Fig. 1).

    FlashSparse (V = 8): the sparse TC block is the k×n operand, so each MMA
    covers k vectors of one window and m dense-output columns:
        Σ_w ceil(nnzv_w / k) · ceil(N / m)
    16×1 SOTA (V = 16): sparse block is the m×k operand:
        Σ_w ceil(nnzv_w / k) · ceil(N / n)
    """
    v = fmt.vector_size
    scheme = "flashsparse" if v == 8 else "sota16"
    m, n, k = MMA_SHAPES[(precision, scheme)]
    counts = _window_counts(fmt)
    kblocks = -(-counts // k)
    ntiles = -(-n_cols // (m if scheme == "flashsparse" else n))
    return int(kblocks.sum()) * ntiles


def data_access_bytes(fmt: MEBCRS, n_cols: int, value_bytes: int = 2,
                      precision: str = "fp16") -> Dict[str, int]:
    """Cost model of global data movement for one SpMM (paper Fig. 12).

    The paper's access cost follows the MMA schedule: every MMA loads its
    two operand blocks (the sparse TC block and the dense TC block) from
    the memory hierarchy and the win comes from issuing *fewer MMAs* —
    per-MMA traffic is identical between the 16×1 and 8×1 schemes
    (16·k + 8·k elements either way, §3.3 / Fig. 6: "the data access cost
    is also proportionally reduced by 50%" when MMAs halve).
    """
    v = fmt.vector_size
    scheme = "flashsparse" if v == 8 else "sota16"
    m, n, k = MMA_SHAPES[(precision, scheme)]
    counts = _window_counts(fmt)
    kblocks = int((-(-counts // k)).sum())
    m_rows = fmt.shape[0]

    if scheme == "flashsparse":
        n_tiles = -(-n_cols // m)
        mmas = kblocks * n_tiles
        a_block, b_block = k * n, m * k     # sparse = k×n, dense = m×k
    else:
        n_tiles = -(-n_cols // n)
        mmas = kblocks * n_tiles
        a_block, b_block = m * k, k * n     # sparse = m×k, dense = k×n

    a_bytes = mmas * a_block * value_bytes + 4 * fmt.nnzv + 4 * (fmt.num_windows + 1)
    b_bytes = mmas * b_block * value_bytes
    c_bytes = m_rows * n_cols * value_bytes  # final result write-back
    return {
        "A": a_bytes,
        "B": b_bytes,
        "C": c_bytes,
        "mmas": mmas,
        "total": a_bytes + b_bytes + c_bytes,
    }


def padded_flops(fmt: MEBCRS, n_cols: int, k_blk: int = 8) -> Dict[str, float]:
    """MXU-executed vs useful FLOPs (TPU-side redundancy accounting)."""
    counts = _window_counts(fmt)
    padded_vecs = int((-(-counts // k_blk) * k_blk).sum())
    executed = 2.0 * padded_vecs * fmt.vector_size * n_cols
    useful = 2.0 * fmt.nnz * n_cols
    return {
        "executed_flops": executed,
        "useful_flops": useful,
        "efficiency": useful / max(executed, 1.0),
    }


def summarize(fmt: MEBCRS, n_cols: int, precision: str = "fp16") -> Dict[str, float]:
    """One-dict redundancy summary of a format at feature width ``n_cols``:
    vector/window counts, carried zeros, MMA invocations, padded FLOPs and
    modeled access bytes — the paper's §2 motivation metrics in one call."""
    return {
        "V": fmt.vector_size,
        "windows": fmt.num_windows,
        "nnzv": fmt.nnzv,
        "nnz": fmt.nnz,
        "zeros_in_vectors": zeros_in_nonzero_vectors(fmt),
        "mma_count": mma_count(fmt, n_cols, precision),
        "access_bytes": data_access_bytes(fmt, n_cols, precision=precision)["total"],
        **padded_flops(fmt, n_cols),
    }
