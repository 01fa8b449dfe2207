"""Unified (op, impl) dispatch registry for the sparse operators.

Before this module, four separate ``impl=`` string ladders resolved the
execution path — ``core/spmm.py``, ``core/sddmm.py``, ``kernels/ops.py``
and ``models/gnn.py`` each kept their own if/elif chain, and they drifted
(the GNN aggregation, for one, silently ignored ``impl="pallas_tuned"``).
Now every implementation of an op registers here exactly once, with
capability flags, and every layer — core dispatch, autodiff backward
passes, models, train steps, benchmarks — resolves ``(op, impl)`` through
the same table.

Registered ops: ``spmm``, ``sddmm``, and ``attention`` (the fused
SDDMM → sparse-softmax → SpMM pipeline — ``pallas_fused_attn`` is the
single-pass megakernel whose scores never touch HBM, ``pallas_staged``
the 3-dispatch baseline).

Capability flags:

  differentiable   the impl has a gradient path: either natively (XLA
                   blocked einsum) or via :mod:`repro.core.autodiff`'s
                   custom_vjp wrappers (Pallas paths)
  batched          handles a leading head/batch dim in ONE call: XLA
                   impls are safe under ``jax.vmap``; the ``*_batched``
                   Pallas impls and the attention megakernel run native
                   ``(H, ...)`` grids — one kernel launch for any head
                   count.  Unflagged impls get an unrolled per-slice
                   loop from the autodiff wrappers instead.
  tpu_only         compiled execution requires a TPU backend (no
                   interpret-mode fallback)
  needs_canonical  requires the canonical :class:`MEBCRS` (re-blocks it,
                   e.g. the autotuned paths sweep ``k_blk``)
  returns_format   returns a :class:`BlockedMEBCRS` with values bound
                   instead of a bare value array (tuned SDDMM: the value
                   layout depends on the tuned ``k_blk``)
  load_balanced    the impl maps work onto uniform schedule segments
                   (block-parallel grids, DESIGN.md §11) instead of
                   ragged per-window loops — accepts ``schedule=`` /
                   ``split_blk=`` kwargs and handles skewed matrices
                   without hub-window serialization
  multi_device     the impl runs one local launch per device under
                   ``shard_map`` over a partitioned Schedule
                   (DESIGN.md §12) — accepts ``mesh=`` / ``part=``
                   kwargs and produces outputs replicated over the
                   mesh's "data" axis
  overlapped       the impl pipelines communication behind compute: it
                   sub-splits each device's work into segment batches
                   and circulates compact partials on a ``ppermute``
                   ring instead of a trailing bulk ``psum``
                   (DESIGN.md §14) — accepts an ``n_batches=`` kwarg
                   (the ``ADPlan.overlap_batches`` knob)

plus the ``precisions`` capability tuple (DESIGN.md §13): the precision
levels the impl accepts via its ``precision=`` kwarg — a subset of
``("fp32", "bf16", "int8")``; every impl defaults to fp32-only.
``require(..., precision=...)`` enforces it.

Providers self-register at import; :func:`get` lazily imports them so the
table is complete no matter which layer touches the registry first.

A **call log** records every dispatch: ``record_calls()`` yields a list
that accumulates ``(op, impl)`` pairs for the duration of the context.
Tests use it to prove, e.g., that the backward pass of the Pallas SpMM
really executed the fused transpose-SpMM/SDDMM kernels rather than a
dense fallback.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib
import inspect
import threading
import warnings
from typing import Callable, Dict, List, Optional, Tuple

__all__ = [
    "OpImpl",
    "register",
    "get",
    "impls",
    "require",
    "dispatch",
    "record_calls",
    "FallbackWarning",
    "fallback_chain",
    "fallback_for",
    "robust_dispatch",
]


@dataclasses.dataclass(frozen=True)
class OpImpl:
    """One registered implementation of a sparse op."""

    op: str
    name: str
    fn: Callable
    differentiable: bool = False
    batched: bool = False
    tpu_only: bool = False
    needs_canonical: bool = False
    returns_format: bool = False
    load_balanced: bool = False
    multi_device: bool = False
    overlapped: bool = False
    precisions: Tuple[str, ...] = ("fp32",)


_REGISTRY: Dict[Tuple[str, str], OpImpl] = {}

# Modules that register implementations at import time.  ``get`` imports
# them lazily so the registry is fully populated regardless of entry point.
# A provider that fails to import raises: a broken kernels package must
# not leave a registry of XLA impls only.
_PROVIDERS = ("repro.core.spmm", "repro.core.sddmm", "repro.kernels.ops",
              "repro.distributed.sparse_shard",
              "repro.distributed.sparse_shard_overlap")
_loaded = False
_lock = threading.Lock()


def register(op: str, name: str, fn: Callable, **flags) -> OpImpl:
    """Register ``fn`` as implementation ``name`` of ``op``."""
    entry = OpImpl(op=op, name=name, fn=fn, **flags)
    _REGISTRY[(op, name)] = entry
    return entry


def _ensure_loaded() -> None:
    global _loaded
    if _loaded:
        return
    with _lock:
        if _loaded:
            return
        for mod in _PROVIDERS:
            importlib.import_module(mod)
        _loaded = True


def get(op: str, impl: str) -> OpImpl:
    """Resolve ``(op, impl)`` → :class:`OpImpl`, loading providers lazily."""
    _ensure_loaded()
    entry = _REGISTRY.get((op, impl))
    if entry is None:
        raise ValueError(f"unknown impl {impl!r} for op {op!r}; "
                         f"available: {', '.join(impls(op)) or '(none)'}")
    return entry


def impls(op: str) -> Tuple[str, ...]:
    """Registered implementation names for ``op`` (sorted)."""
    _ensure_loaded()
    return tuple(sorted(n for (o, n) in _REGISTRY if o == op))


def require(op: str, impl: str, *, differentiable: bool = False,
            batched: bool = False,
            precision: Optional[str] = None) -> OpImpl:
    """Resolve and enforce capability flags, with a targeted error."""
    entry = get(op, impl)
    if precision is not None and precision not in entry.precisions:
        ok = [n for n in impls(op)
              if precision in _REGISTRY[(op, n)].precisions]
        raise ValueError(
            f"impl {impl!r} of op {op!r} does not support precision "
            f"{precision!r} (supports: {', '.join(entry.precisions)}); "
            f"impls with {precision!r}: {', '.join(ok) or '(none)'}")
    if differentiable and not entry.differentiable:
        ok = [n for n in impls(op) if _REGISTRY[(op, n)].differentiable]
        raise ValueError(
            f"impl {impl!r} of op {op!r} is not differentiable; "
            f"differentiable impls: {', '.join(ok)}")
    if batched and not entry.batched:
        # Not fatal capability-wise — callers fall back to a per-slice
        # loop — but ``require(batched=True)`` asks for the native path.
        ok = [n for n in impls(op) if _REGISTRY[(op, n)].batched]
        raise ValueError(
            f"impl {impl!r} of op {op!r} has no native batched path; "
            f"batched impls: {', '.join(ok)}")
    return entry


# ---------------------------------------------------------------------------
# Call log
# ---------------------------------------------------------------------------

_local = threading.local()


def _recorders() -> List[List[Tuple[str, str]]]:
    recs = getattr(_local, "recorders", None)
    if recs is None:
        recs = _local.recorders = []
    return recs


@contextlib.contextmanager
def record_calls():
    """Context manager yielding a list that accumulates ``(op, impl)``
    pairs for every :func:`dispatch` made while the context is active.

    Dispatches happen at *trace* time, so a jitted function logs on its
    first (tracing) call; wrap the tracing call in the context.
    """
    log: List[Tuple[str, str]] = []
    _recorders().append(log)
    try:
        yield log
    finally:
        _recorders().remove(log)


def _log(op: str, impl: str) -> None:
    for rec in _recorders():
        rec.append((op, impl))


def dispatch(op: str, impl: str, *args, **kwargs):
    """Resolve ``(op, impl)`` and call it, recording in the call log."""
    entry = get(op, impl)
    _log(op, impl)
    return entry.fn(*args, **kwargs)


# ---------------------------------------------------------------------------
# Graceful degradation (DESIGN.md §15)
# ---------------------------------------------------------------------------


class FallbackWarning(UserWarning):
    """A requested impl failed and the op recovered on a lower ladder rung.

    One structured warning per recovered dispatch: ``op``/``requested``/
    ``used`` name the ladder walk, ``failures`` holds ``(impl, "Type:
    message")`` for every rung that failed before the one that served.
    Promoted to an error in tier-1 tests (pytest.ini) so silent
    degradation can never hide a kernel regression there.
    """

    def __init__(self, op: str, requested: str, used: str, failures):
        self.op = op
        self.requested = requested
        self.used = used
        self.failures = tuple(
            (n, f"{type(e).__name__}: {str(e)[:200]}") for n, e in failures)
        detail = "; ".join(f"{n} ({t})" for n, t in self.failures)
        super().__init__(
            f"op {op!r}: impl {requested!r} degraded to {used!r} after "
            f"{len(self.failures)} failed rung(s): {detail}")


# Capability ladders, fastest/most-specialized first.  ``robust_dispatch``
# enters at the requested impl and walks right; impls not on a ladder
# (ablation variants like pallas_staged/pallas_noncoalesced for SpMM)
# enter at the plain single-device tier.  The sddmm ladder ends at
# ``blocked`` — the ``coo`` impl returns edge values ``(NNZ,)``, a
# different output contract than the blocked-layout rungs (and
# ``returns_format`` impls like tuned SDDMM never degrade to bare-array
# rungs for the same reason).
_LADDERS: Dict[str, Tuple[str, ...]] = {
    "spmm": ("pallas_sharded_overlap", "pallas_sharded", "pallas_tuned",
             "pallas_balanced", "pallas_batched", "pallas", "blocked",
             "coo_segment"),
    "sddmm": ("pallas_sharded_overlap", "pallas_sharded", "pallas_tuned",
              "pallas_balanced", "pallas_batched", "pallas", "blocked"),
    "attention": ("pallas_sharded_overlap", "pallas_sharded",
                  "pallas_fused_attn_tuned", "pallas_balanced",
                  "pallas_fused_attn", "pallas_staged", "blocked"),
}
_DEFAULT_TIER = {"spmm": "pallas", "sddmm": "pallas",
                 "attention": "pallas_staged"}
# Impls whose output contract matches no other rung: never degrade.
# (sddmm "coo" returns edge values (NNZ,), not blocked-layout (NNZP, V).)
_NO_FALLBACK = {("sddmm", "coo")}
# Precision degradation when a rung lacks the requested level: narrow
# levels widen (never the reverse — a fallback must not lose accuracy).
_PRECISION_FALLBACK = {"int8": ("bf16", "fp32"), "bf16": ("fp32",)}


def fallback_chain(op: str, impl: str) -> Tuple[str, ...]:
    """The ladder rungs ``robust_dispatch`` tries after ``impl`` fails."""
    if (op, impl) in _NO_FALLBACK:
        return ()
    ladder = _LADDERS.get(op, ())
    if impl in ladder:
        return ladder[ladder.index(impl) + 1:]
    tier = _DEFAULT_TIER.get(op)
    if tier in ladder:
        return ladder[ladder.index(tier):]
    return ladder


def _static_compatible(entry: OpImpl, orig: OpImpl) -> bool:
    return entry.returns_format == orig.returns_format


def fallback_for(op: str, impl: str) -> Optional[str]:
    """The first registered, contract-compatible rung below ``impl`` —
    what the README impl matrix's ``fallback`` column shows."""
    try:
        orig = get(op, impl)
    except ValueError:
        return None
    for name in fallback_chain(op, impl):
        entry = _REGISTRY.get((op, name))
        if entry is not None and _static_compatible(entry, orig):
            return name
    return None


def _compatible(entry: OpImpl, orig: OpImpl, args) -> bool:
    """Can this rung serve the original request's contract and inputs?"""
    if not _static_compatible(entry, orig):
        return False
    if entry.tpu_only:
        import jax

        if jax.default_backend() != "tpu":
            return False
    if entry.needs_canonical and args:
        from .format import BlockedMEBCRS

        if isinstance(args[0], BlockedMEBCRS):
            return False
    return True


_sig_cache: Dict[Tuple[str, str], Optional[frozenset]] = {}


def _accepted_params(entry: OpImpl) -> Optional[frozenset]:
    """Keyword names ``entry.fn`` accepts; ``None`` = accepts anything."""
    key = (entry.op, entry.name)
    if key not in _sig_cache:
        try:
            params = inspect.signature(entry.fn).parameters.values()
        except (TypeError, ValueError):  # builtins / C callables
            _sig_cache[key] = None
        else:
            if any(p.kind == p.VAR_KEYWORD for p in params):
                _sig_cache[key] = None
            else:
                _sig_cache[key] = frozenset(
                    p.name for p in params
                    if p.kind in (p.POSITIONAL_OR_KEYWORD, p.KEYWORD_ONLY))
    return _sig_cache[key]


def _adapt_kwargs(entry: OpImpl, kwargs: Dict) -> Dict:
    """Project a request's kwargs onto what a ladder rung understands.

    Capability-specific knobs (schedule/mesh/n_batches/…) are dropped for
    rungs without the capability; a ``precision`` the rung lacks widens
    along ``_PRECISION_FALLBACK``; finally the rung's signature filters
    anything it cannot accept (e.g. ``coo`` adapters take no
    ``precision``).
    """
    kw = dict(kwargs)
    if not entry.load_balanced:
        kw.pop("schedule", None)
        kw.pop("split_blk", None)
    if not entry.multi_device:
        kw.pop("mesh", None)
        kw.pop("part", None)
    if not entry.overlapped:
        kw.pop("n_batches", None)
    prec = kw.get("precision")
    if prec is not None and prec not in entry.precisions:
        for cand in _PRECISION_FALLBACK.get(prec, ()):
            if cand in entry.precisions:
                kw["precision"] = cand
                break
        else:
            kw.pop("precision", None)
    allowed = _accepted_params(entry)
    if allowed is not None:
        kw = {k: v for k, v in kw.items() if k in allowed}
    return kw


def _extract_values(out):
    """(container-or-None, value array) of an impl result."""
    if hasattr(out, "vals") and hasattr(out, "win_ptr"):
        return out, out.vals
    return None, out


def _guard_nonfinite(entry: OpImpl, args, kw: Dict, out):
    """Re-run a narrow (bf16/int8) forward at fp32 when it produced
    NaN/Inf (DESIGN.md §15).  The guarded output is returned in fp32 —
    the two ``lax.cond`` branches must share a dtype, and a guard that
    casts the rescue back to the narrow dtype would re-overflow the very
    values it rescued.
    """
    import jax
    import jax.numpy as jnp

    if kw.get("precision") not in ("bf16", "int8"):
        return out
    if "fp32" not in entry.precisions:
        return out
    container, arr = _extract_values(out)
    if not jnp.issubdtype(arr.dtype, jnp.floating):
        return out
    kw32 = _adapt_kwargs(entry, {**kw, "precision": "fp32"})

    def rerun():
        _, a32 = _extract_values(entry.fn(*args, **kw32))
        return a32.astype(jnp.float32)

    ok = jnp.all(jnp.isfinite(arr))
    arr32 = arr.astype(jnp.float32)
    if isinstance(ok, jax.core.Tracer):
        fixed = jax.lax.cond(ok, lambda: arr32, rerun)
    elif bool(ok):
        fixed = arr32
    else:
        warnings.warn(FallbackWarning(
            entry.op, f"{entry.name}[{kw.get('precision')}]",
            f"{entry.name}[fp32]",
            [(entry.name, FloatingPointError("non-finite output"))]),
            stacklevel=3)
        _count("guard_nonfinite_rerun")
        _log(entry.op, f"guard:{entry.name}:fp32-rerun")
        fixed = rerun()
    if container is not None:
        return dataclasses.replace(container, vals=fixed, scales=None)
    return fixed


def _count(name: str) -> None:
    try:
        from .metrics import record_counter

        record_counter(name)
    except Exception:  # pragma: no cover - metrics stays optional here
        pass


def robust_dispatch(op: str, impl: str, *args, strict: bool = False,
                    guard_nonfinite: bool = False, **kwargs):
    """Dispatch with graceful degradation down the capability ladder.

    Tries ``impl`` first; on failure walks :func:`fallback_chain`, skipping
    rungs whose output contract or input requirements differ, adapting
    kwargs per rung via :func:`_adapt_kwargs`.  A recovery emits ONE
    structured :class:`FallbackWarning` plus a call-log record
    ``(op, "fallback:<requested>-><used>")``.  ``strict=True`` re-raises
    the requested impl's error instead of degrading.  Structural
    :class:`~repro.core.validate.ValidationError`\\ s always re-raise —
    a corrupted format computes the wrong answer on *every* rung, so
    retrying would only convert a named error into silent corruption.

    ``guard_nonfinite=True`` additionally re-runs a bf16/int8 forward at
    fp32 when the narrow path yields NaN/Inf (the guarded output is
    promoted to fp32; see :func:`_guard_nonfinite`).
    """
    from .validate import ValidationError

    orig = get(op, impl)
    failures: List[Tuple[str, Exception]] = []
    for name in (impl,) + fallback_chain(op, impl):
        entry = _REGISTRY.get((op, name))
        if entry is None:
            continue
        if name != impl and not _compatible(entry, orig, args):
            continue
        kw = _adapt_kwargs(entry, kwargs)
        _log(op, name)
        try:
            out = entry.fn(*args, **kw)
        except ValidationError:
            raise
        except Exception as e:  # noqa: BLE001 — ladder catches and retries
            if strict:
                raise
            failures.append((name, e))
            continue
        if guard_nonfinite:
            out = _guard_nonfinite(entry, args, kw, out)
        if failures:
            warnings.warn(FallbackWarning(op, impl, name, failures),
                          stacklevel=2)
            _log(op, f"fallback:{impl}->{name}")
            _count("dispatch_fallback")
        return out
    err = RuntimeError(
        f"op {op!r}: impl {impl!r} and every compatible fallback rung "
        f"failed: " + "; ".join(
            f"{n} ({type(e).__name__}: {str(e)[:200]})"
            for n, e in failures))
    raise err from (failures[-1][1] if failures else None)
