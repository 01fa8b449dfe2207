"""Structural validation for the sparse format stack (DESIGN.md §15).

Every layer between a COO matrix and a kernel launch — ``MEBCRS`` →
``BlockedMEBCRS`` → ``Schedule`` → ``ShardedSchedule`` — is index/metadata
driven: a single out-of-bounds ``cols`` entry or a non-monotone ``win_ptr``
produces a silent wrong answer or an opaque Pallas crash, never a clean
error.  This module concentrates the invariants in one place with three
check levels:

  ``"none"``   no work at all — the default; hot paths stay bitwise
               identical to an unvalidated build.
  ``"cheap"``  jit-safe guards only: non-finite values and out-of-range
               indices, expressed as reductions that run eagerly (raising
               :class:`ValidationError`) or under a tracer (emitting a
               :class:`ValidationWarning` through ``jax.debug.callback``).
  ``"full"``   a host-side NumPy audit of every structural invariant.
               Requires concrete arrays; callers inside ``jit`` are
               downgraded to ``"cheap"`` automatically by
               :func:`effective_check`.

Errors carry the violated invariant's name (``err.invariant``) and render
as ``[invariant-name] human explanation`` so the fault-injection harness
(:mod:`repro.testing.faults`) and operators reading logs can classify
failures without parsing prose.

The level is resolved per call: an explicit ``check=`` argument wins, then
a :func:`checking` context override, then the ``REPRO_CHECK`` environment
variable, then ``"none"``.
"""

from __future__ import annotations

import contextlib
import os
import threading
import warnings
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "CHECK_LEVELS",
    "ValidationError",
    "ValidationWarning",
    "check_level",
    "checking",
    "resolve_check",
    "effective_check",
    "validate",
    "validate_format",
    "validate_blocked",
    "validate_schedule",
    "validate_sharded",
    "cheap_guard",
    "guard_operand",
]

CHECK_LEVELS = ("none", "cheap", "full")
_CHECK_ENV = "REPRO_CHECK"
_local = threading.local()


class ValidationError(ValueError):
    """A named structural invariant was violated.

    ``invariant`` is a stable kebab-case identifier (e.g. ``col-in-bounds``)
    that the fault-injection harness matches on; the message always starts
    with ``[invariant]`` so plain-text logs stay classifiable.
    """

    def __init__(self, invariant: str, message: str):
        self.invariant = invariant
        super().__init__(f"[{invariant}] {message}")


class ValidationWarning(UserWarning):
    """A cheap guard tripped inside a traced computation (where raising is
    impossible) — the same condition raises :class:`ValidationError` when
    it is evaluated eagerly."""


def check_level() -> str:
    """The ambient check level: :func:`checking` override, else the
    ``REPRO_CHECK`` environment variable, else ``"none"``."""
    override = getattr(_local, "override", None)
    if override is not None:
        return override
    env = os.environ.get(_CHECK_ENV, "none").strip().lower()
    return env if env in CHECK_LEVELS else "none"


@contextlib.contextmanager
def checking(level: str):
    """Scoped override of the ambient check level (thread-local)."""
    if level not in CHECK_LEVELS:
        raise ValueError(f"check must be one of {CHECK_LEVELS}, got {level!r}")
    prev = getattr(_local, "override", None)
    _local.override = level
    try:
        yield
    finally:
        _local.override = prev


def resolve_check(check: Optional[str]) -> str:
    """An explicit ``check=`` argument, validated; ``None`` → ambient."""
    if check is None:
        return check_level()
    if check not in CHECK_LEVELS:
        raise ValueError(f"check must be one of {CHECK_LEVELS}, got {check!r}")
    return check


def _is_traced(*arrays) -> bool:
    return any(isinstance(a, jax.core.Tracer) for a in arrays if a is not None)


def effective_check(check: Optional[str], *arrays) -> str:
    """Resolve ``check`` and downgrade ``full`` → ``cheap`` when any of the
    arrays is a tracer (a full audit needs concrete values; an entry point
    called inside ``jit`` with ``REPRO_CHECK=full`` must still work)."""
    level = resolve_check(check)
    if level == "full" and _is_traced(*arrays):
        return "cheap"
    return level


def _fail(invariant: str, message: str):
    raise ValidationError(invariant, message)


def _require(ok: bool, invariant: str, message: str) -> None:
    if not ok:
        _fail(invariant, message)


# ---------------------------------------------------------------------------
# Cheap (jit-safe) guards
# ---------------------------------------------------------------------------


def _warn_cb(ok, *, invariant: str, message: str) -> None:
    if not bool(ok):
        warnings.warn(ValidationWarning(f"[{invariant}] {message}"),
                      stacklevel=2)


def cheap_guard(ok, invariant: str, message: str) -> None:
    """Enforce a boolean predicate in a jit-compatible way.

    Eager ``ok`` (a concrete bool / 0-d array): raise
    :class:`ValidationError` when false.  Traced ``ok``: attach a
    ``jax.debug.callback`` that emits :class:`ValidationWarning` at run
    time — tracing cannot raise data-dependent errors, but the signal
    still reaches logs/tests.
    """
    if isinstance(ok, jax.core.Tracer):
        jax.debug.callback(partial(_warn_cb, invariant=invariant,
                                   message=message), ok)
    else:
        _require(bool(ok), invariant, message)


def _finite_ok(x) -> jax.Array:
    if not jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating):
        return jnp.asarray(True)
    return jnp.all(jnp.isfinite(x))


def guard_operand(x, name: str = "operand") -> None:
    """Cheap non-finite guard on a dense operand (jit-safe)."""
    cheap_guard(_finite_ok(x), "values-finite",
                f"{name} contains NaN/Inf values")


# ---------------------------------------------------------------------------
# Full host-side audits
# ---------------------------------------------------------------------------


def _np(x):
    if isinstance(x, jax.core.Tracer):
        raise ValidationError(
            "traced-arrays",
            "check='full' needs concrete arrays; call outside jit or use "
            "check='cheap' (entry points downgrade automatically)")
    return np.asarray(x)


def validate_format(fmt, check: Optional[str] = "full"):
    """Audit a canonical :class:`~repro.core.format.MEBCRS`.

    Returns ``fmt`` so construction sites can validate-and-pass-through.
    """
    level = resolve_check(check)
    if level == "none":
        return fmt
    m, k = fmt.shape
    if level == "cheap":
        ci = fmt.column_indices
        if ci.shape[0]:
            cheap_guard(jnp.logical_and(jnp.min(ci) >= 0, jnp.max(ci) < k),
                        "col-in-bounds",
                        f"column_indices outside [0, {k})")
        cheap_guard(_finite_ok(fmt.values), "values-finite",
                    "values contain NaN/Inf")
        return fmt

    v = fmt.vector_size
    w = -(-m // v)
    rp = _np(fmt.row_pointers)
    ci = _np(fmt.column_indices)
    vals = _np(fmt.values)
    mask = _np(fmt.mask)
    _require(rp.ndim == 1 and rp.shape[0] == w + 1, "row-ptr-shape",
             f"row_pointers shape {rp.shape} != ({w + 1},) for "
             f"shape={fmt.shape}, vector_size={v}")
    _require(np.issubdtype(rp.dtype, np.integer), "dtype-mismatch",
             f"row_pointers dtype {rp.dtype} is not integer")
    _require(np.issubdtype(ci.dtype, np.integer), "dtype-mismatch",
             f"column_indices dtype {ci.dtype} is not integer")
    _require(rp[0] == 0 and np.all(np.diff(rp) >= 0), "row-ptr-monotone",
             "row_pointers must start at 0 and be non-decreasing")
    nnzv = vals.shape[0] if vals.ndim else 0
    _require(int(rp[-1]) == nnzv, "row-ptr-bounds",
             f"row_pointers[-1]={int(rp[-1])} != nnzv={nnzv}")
    _require(ci.shape == (nnzv,), "leaf-length",
             f"column_indices shape {ci.shape} != ({nnzv},)")
    _require(nnzv == 0 or (ci.min() >= 0 and ci.max() < k), "col-in-bounds",
             f"column_indices outside [0, {k})")
    _require(vals.ndim == 2 and vals.shape == (nnzv, v), "values-shape",
             f"values shape {vals.shape} != ({nnzv}, {v})")
    _require(mask.shape == (nnzv, v) and mask.dtype == np.bool_,
             "mask-dtype", f"mask shape/dtype {mask.shape}/{mask.dtype} "
             f"!= ({nnzv}, {v})/bool")
    if np.issubdtype(vals.dtype, np.floating):
        _require(bool(np.isfinite(vals).all()), "values-finite",
                 "values contain NaN/Inf")
    # Masked-off lanes must hold zeros: the kernels contract raw ``values``
    # (the mask is only consulted by SDDMM write-back and the metrics), so
    # garbage under mask=False silently changes every product.
    _require(nnzv == 0 or not np.any(vals[~mask]), "masked-zeros",
             "values under mask=False must be zero")
    # Each (window, column) vector appears at most once — a duplicate
    # double-counts its lanes in every contraction.
    if nnzv:
        win_of_vec = np.repeat(np.arange(w, dtype=np.int64), np.diff(rp))
        keys = win_of_vec * int(k) + ci.astype(np.int64)
        _require(np.unique(keys).shape[0] == nnzv, "vector-unique",
                 "duplicate (window, column) vector in format")
    return fmt


def validate_blocked(blocked, check: Optional[str] = "full"):
    """Audit a :class:`~repro.core.format.BlockedMEBCRS` execution view."""
    level = resolve_check(check)
    if level == "none":
        return blocked
    m, k = blocked.shape
    if level == "cheap":
        if blocked.cols.shape[0]:
            cheap_guard(jnp.logical_and(jnp.min(blocked.cols) >= 0,
                                        jnp.max(blocked.cols) < k),
                        "col-in-bounds", f"cols outside [0, {k})")
        cheap_guard(_finite_ok(blocked.vals), "values-finite",
                    "vals contain NaN/Inf")
        if blocked.scales is not None:
            cheap_guard(_finite_ok(blocked.scales), "scales-finite",
                        "scales contain NaN/Inf")
        return blocked

    v = blocked.vector_size
    kb = blocked.k_blk
    w = blocked.num_windows
    _require(isinstance(kb, int) and 1 <= kb <= 4096, "block-config",
             f"k_blk={kb!r} outside the sane range [1, 4096]")
    vals = _np(blocked.vals)
    cols = _np(blocked.cols)
    mask = _np(blocked.mask)
    bwin = _np(blocked.block_win)
    wptr = _np(blocked.win_ptr)
    nb = bwin.shape[0]
    nnzp = nb * kb
    _require(wptr.ndim == 1 and wptr.shape[0] == w + 1, "win-ptr-shape",
             f"win_ptr shape {wptr.shape} != ({w + 1},)")
    _require(np.issubdtype(wptr.dtype, np.integer)
             and np.issubdtype(bwin.dtype, np.integer)
             and np.issubdtype(cols.dtype, np.integer), "dtype-mismatch",
             "win_ptr/block_win/cols must be integer dtypes")
    _require(wptr[0] == 0 and np.all(np.diff(wptr) >= 0), "win-ptr-monotone",
             "win_ptr must start at 0 and be non-decreasing")
    # The dummy block of an all-empty matrix sits outside every window
    # range, hence <= rather than ==.
    _require(int(wptr[-1]) <= nb, "win-ptr-bounds",
             f"win_ptr[-1]={int(wptr[-1])} > num_blocks={nb}")
    _require(vals.shape == (nnzp, v) and cols.shape == (nnzp,)
             and mask.shape == (nnzp, v), "leaf-length",
             f"vals/cols/mask shapes {vals.shape}/{cols.shape}/{mask.shape} "
             f"inconsistent with num_blocks={nb}, k_blk={kb}, V={v}")
    _require(mask.dtype == np.bool_, "mask-dtype",
             f"mask dtype {mask.dtype} != bool")
    _require(nnzp == 0 or (cols.min() >= 0 and cols.max() < k),
             "col-in-bounds", f"cols outside [0, {k})")
    # Owned blocks must agree between the gather (win_ptr) and scatter
    # (block_win) views.
    owned = int(wptr[-1])
    expect = np.repeat(np.arange(w, dtype=bwin.dtype), np.diff(wptr))
    _require(np.array_equal(bwin[:owned], expect), "block-win-consistent",
             "block_win disagrees with win_ptr block ranges")
    if np.issubdtype(vals.dtype, np.floating):
        _require(bool(np.isfinite(vals).all()), "values-finite",
                 "vals contain NaN/Inf")
    _require(nnzp == 0 or not np.any(vals[~mask]), "masked-zeros",
             "vals under mask=False (incl. block padding) must be zero")
    if blocked.scales is not None:
        sc = _np(blocked.scales)
        _require(sc.shape == (nb,), "scales-shape",
                 f"scales shape {sc.shape} != ({nb},)")
        _require(bool(np.isfinite(sc).all()) and bool((sc > 0).all()),
                 "scales-finite", "scales must be finite and positive")
        _require(vals.dtype == np.int8, "dtype-mismatch",
                 f"scales present but vals dtype is {vals.dtype}, not int8")
    elif vals.dtype == np.int8:
        _fail("dtype-mismatch", "int8 vals without per-block scales")
    return blocked


def validate_schedule(sched, blocked=None, check: Optional[str] = "full"):
    """Audit a :class:`~repro.core.format.Schedule`.

    With ``blocked`` given, additionally proves the segments cover each
    window's block range exactly once, in ascending order, with correct
    first/last flags (the balanced kernels' accumulate/epilogue contract).
    """
    level = resolve_check(check)
    if level == "none":
        return sched
    if level == "cheap":
        cheap_guard(jnp.all(sched.seg_meta[:, 1] >= 0), "seg-flags",
                    "segment lengths must be >= 0")
        return sched

    sw = _np(sched.seg_win)
    sm = _np(sched.seg_meta)
    ns = sw.shape[0]
    _require(sm.ndim == 2 and sm.shape == (ns, 4), "schedule-shape",
             f"seg_meta shape {sm.shape} != ({ns}, 4)")
    lo, ln, first, last = sm[:, 0], sm[:, 1], sm[:, 2], sm[:, 3]
    _require(bool(np.all(ln >= 0)), "seg-flags",
             "segment lengths must be >= 0")
    _require(bool(np.isin(first, (0, 1)).all()
                  and np.isin(last, (0, 1)).all()), "seg-flags",
             "seg first/last flags must be 0/1")
    nb = sched.num_blocks
    if blocked is None:
        return sched
    wptr = _np(blocked.win_ptr)
    w = blocked.num_windows
    _require(ns == 0 or (sw.min() >= 0 and sw.max() < w), "seg-coverage",
             f"seg_win outside [0, {w})")
    _require(int(wptr[-1]) == nb, "seg-coverage",
             f"schedule num_blocks={nb} != owned blocks {int(wptr[-1])}")
    # Per window: segments contiguous in the seg list, ascending block
    # ranges tiling [win_ptr[w], win_ptr[w+1]) exactly once, first on the
    # first and last on the last.
    for wi in range(w):
        idx = np.nonzero(sw == wi)[0]
        _require(idx.size >= 1, "seg-coverage",
                 f"window {wi} has no segment (empty windows keep one "
                 "zero-length store-only segment)")
        _require(bool(np.all(np.diff(idx) == 1)), "seg-coverage",
                 f"window {wi}'s segments are not contiguous")
        _require(first[idx[0]] == 1 and last[idx[-1]] == 1
                 and bool(np.all(first[idx[1:]] == 0))
                 and bool(np.all(last[idx[:-1]] == 0)), "seg-flags",
                 f"window {wi}'s first/last segment flags are wrong")
        span = np.concatenate([np.arange(lo[i], lo[i] + ln[i])
                               for i in idx]) if idx.size else np.array([])
        want = np.arange(int(wptr[wi]), int(wptr[wi + 1]))
        _require(np.array_equal(span, want), "seg-coverage",
                 f"window {wi}'s segments cover blocks {span.tolist()[:8]}…"
                 f" instead of [{int(wptr[wi])}, {int(wptr[wi + 1])})")
    return sched


def validate_sharded(part, blocked=None, check: Optional[str] = "full"):
    """Audit a :class:`~repro.distributed.sparse_shard.ShardedSchedule`."""
    level = resolve_check(check)
    if level == "none":
        return part
    if level == "cheap":
        cheap_guard(jnp.all(part.seg_meta[:, :, 1] >= 0), "seg-flags",
                    "sharded segment lengths must be >= 0")
        return part

    d = part.num_devices
    sw = _np(part.seg_win)
    sm = _np(part.seg_meta)
    row_own = _np(part.row_own)
    blk_own = _np(part.blk_own)
    _require(sw.ndim == 2 and sw.shape[0] == d and sm.shape[:2] == sw.shape
             and sm.shape[2] == 4, "shard-shape",
             f"seg_win/seg_meta shapes {sw.shape}/{sm.shape} inconsistent "
             f"with num_devices={d}")
    _require(row_own.shape[0] == d and blk_own.shape[0] == d, "shard-shape",
             f"ownership masks must lead with num_devices={d}")
    _require(bool(np.all(sm[:, :, 1] >= 0)), "seg-flags",
             "sharded segment lengths must be >= 0")
    if blocked is not None:
        w = blocked.num_windows
        # Padding segments carry seg_win == W (one past the last window).
        _require(bool(sw.min() >= 0 and sw.max() <= w), "seg-coverage",
                 f"sharded seg_win outside [0, {w}]")
        m = blocked.shape[0]
        v = blocked.vector_size
        wptr = _np(blocked.win_ptr)
        # row_own[dev] must be exactly the rows of the windows dev holds
        # segments for (a straddled window is legitimately owned by every
        # device holding one of its segments — the psum / ppermute ring
        # recombines the partials).
        for dev in range(d):
            wins = np.unique(sw[dev][sw[dev] < w])
            rows = (wins[:, None] * v + np.arange(v)).reshape(-1)
            expect = np.zeros(m, bool)
            expect[rows[rows < m]] = True
            _require(np.array_equal(row_own[dev], expect),
                     "row-own-consistent",
                     f"device {dev}'s row_own disagrees with its segments")
        # Every window has >= 1 segment somewhere, so the union covers
        # every output row — dropped rows silently vanish from the psum.
        _require(bool(row_own.any(axis=0).all()), "row-own-cover",
                 "some output rows are owned by no device")
        # Every scheduled value row is owned exactly once (block ranges
        # never straddle: the partitioner cuts between segments and
        # segment block ranges are disjoint).
        owned_rows = int(wptr[-1]) * blocked.k_blk
        blk_count = blk_own[:, :owned_rows].astype(np.int64).sum(axis=0)
        _require(bool(np.all(blk_count == 1)), "blk-own-unique",
                 "each scheduled K-block value row must be owned by "
                 "exactly one device")
    return part


def validate(obj, blocked=None, check: Optional[str] = "full"):
    """Type-dispatching audit: accepts any of the four format-stack types."""
    from .format import BlockedMEBCRS, MEBCRS, Schedule

    if isinstance(obj, MEBCRS):
        return validate_format(obj, check=check)
    if isinstance(obj, BlockedMEBCRS):
        return validate_blocked(obj, check=check)
    if isinstance(obj, Schedule):
        return validate_schedule(obj, blocked=blocked, check=check)
    try:
        from ..distributed.sparse_shard import ShardedSchedule
    except Exception:  # pragma: no cover - distributed layer optional
        ShardedSchedule = ()
    if ShardedSchedule and isinstance(obj, ShardedSchedule):
        return validate_sharded(obj, blocked=blocked, check=check)
    raise TypeError(f"cannot validate object of type {type(obj).__name__}")
