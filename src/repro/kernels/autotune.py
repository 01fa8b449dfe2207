"""(k_blk, n_blk/f_blk) autotuner for the fused Pallas kernels.

FlashSparse fixes the MMA granularity (8×1 vectors) but the TPU kernels
still expose two free tiling parameters: the K-block depth ``k_blk`` (how
many nonzero vectors one grid step contracts) and the output column tile
``n_blk`` (``f_blk`` for SDDMM).  The best point depends on the matrix's
sparsity structure and on N — Acc-SpMM / cuTeSpMM (PAPERS.md) make the
same observation for their GPU tile shapes.

This module sweeps a small candidate grid per *(matrix-stats, N) bucket*
and memoizes the winner in a persistent on-disk JSON cache, so repeated
runs (benchmarks, serving, training epochs) pay the sweep once.  Buckets
are deliberately coarse — log2 of the window count, of the mean vectors
per window, and of N — so structurally similar matrices share an entry.

Cache location: ``$REPRO_AUTOTUNE_CACHE`` if set, else
``~/.cache/repro/autotune_cache.json`` (CWD-independent, so library calls
from arbitrary directories reuse the same tuned configs).
"""

from __future__ import annotations

import dataclasses
import json
import logging
import os
import re
import time
from typing import Dict, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.core.format import MEBCRS, block_format, window_skew

logger = logging.getLogger(__name__)

__all__ = [
    "TuneConfig",
    "AutotuneCache",
    "matrix_stats_key",
    "tune_spmm",
    "tune_sddmm",
    "tune_attention",
    "default_cache",
]

DEFAULT_K_BLKS: Tuple[int, ...] = (8, 16, 32)
DEFAULT_N_BLKS: Tuple[int, ...] = (64, 128, 256)
# split_blk candidates: 0 = window-parallel kernel, >= 1 = the block-
# parallel balanced kernel with that segment cap.  The skew bucket in the
# stats key makes the balanced-vs-plain choice per matrix class (skewed
# and uniform matrices never share a cached winner).
DEFAULT_SPLIT_BLKS: Tuple[int, ...] = (0, 1)

_CACHE_ENV = "REPRO_AUTOTUNE_CACHE"
_DEFAULT_CACHE_PATH = os.path.join(
    os.path.expanduser("~"), ".cache", "repro", "autotune_cache.json")

# On-disk layout version.  v2: the stats key gained dtype + batch-size
# fields (fp32/bf16 and batched shapes previously collided on one tuned
# (k_blk, n_blk)) and the file became {"schema": N, "configs": {...}}.
# v3: configs gained ``split_blk`` (the block-parallel schedule's segment
# cap, 0 = window-parallel) and the stats key a window-skew bucket —
# winners tuned without the skew dimension must not satisfy skew-aware
# lookups, so files with any other/missing schema (v1 and v2 alike) are
# discarded wholesale.
# v4: configs gained ``precision`` (the mixed-precision level the winner
# was timed at, DESIGN.md §13) and the sweep key a ``|p...`` candidate
# suffix — a v3 winner carries no precision and must not satisfy a
# precision-swept lookup, so v3 files (and older) are discarded wholesale.
# v5: configs gained ``overlap_batches`` (the sharded-overlap pipeline
# depth the winner was timed at, DESIGN.md §14; 0 = no overlap axis) and
# the sweep key an ``|o...`` candidate suffix plus the mesh's data-axis
# size when the axis is swept — a v4 winner carries no pipeline depth and
# must not satisfy an overlap-swept lookup, so v4 files (and older) are
# discarded wholesale.
# v6: the stats key gained the structure-taxonomy class
# (repro.sparse.structure: banded/mesh/block/hub/uniform/dense) — two
# matrices with the same coarse size/skew buckets but different structure
# classes favour different winners (the real-matrix benchmarks record
# per-class winners), so a v5 winner tuned without the class dimension
# must not satisfy a class-aware lookup and v5 files (and older) are
# discarded wholesale.
SCHEMA_VERSION = 6


@dataclasses.dataclass(frozen=True)
class TuneConfig:
    """Winner of one sweep: the tiling triple and its measured median ms.

    ``split_blk = 0`` runs the window-parallel fused kernel; ``>= 1`` runs
    the block-parallel balanced kernel with that many K-blocks per segment
    (DESIGN.md §11).  ``precision`` is the mixed-precision level the
    winner was timed at (DESIGN.md §13); ``"fp32"`` — the default when the
    sweep has no precision axis — means the operands' native dtypes.
    ``overlap_batches`` is the sharded-overlap pipeline depth
    (DESIGN.md §14): 0 — the default when the sweep has no overlap axis —
    means the single-device kernels; ``>= 1`` means the winner ran
    ``pallas_sharded_overlap`` with that many segment batches per device.
    """

    k_blk: int
    n_blk: int
    median_ms: float
    split_blk: int = 0
    precision: str = "fp32"
    overlap_batches: int = 0

    def to_json(self) -> Dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_json(cls, d: Dict) -> "TuneConfig":
        return cls(k_blk=int(d["k_blk"]), n_blk=int(d["n_blk"]),
                   median_ms=float(d["median_ms"]),
                   split_blk=int(d.get("split_blk", 0)),
                   precision=str(d.get("precision", "fp32")),
                   overlap_batches=int(d.get("overlap_batches", 0)))


def _log2_bucket(x: float) -> int:
    return max(int(x), 1).bit_length()


def matrix_stats_key(fmt: MEBCRS, n: int, op: str, *, interpret: bool,
                     dtype=None, batch: int = 1) -> str:
    """Coarse bucket key: structurally similar (matrix, N) pairs collide.

    ``dtype`` (of the dense operand; defaults to the format's value dtype)
    and ``batch`` (product of leading batch/head dims, log2-bucketed) are
    part of the key — fp32 vs bf16 and single vs batched shapes favour
    different tiles and must not share a cached winner.  The window-skew
    statistic (p99/mean vectors-per-window, log2-bucketed) keys the
    balanced-vs-plain decision: a hub-heavy matrix and a uniform one with
    the same size/density land in different buckets, so the block-parallel
    schedule is chosen per matrix *class* (DESIGN.md §11).  The structure-
    taxonomy class (``cls...``, schema v6) sharpens that: real matrices
    with identical coarse buckets but different structure (banded vs mesh
    vs block-diagonal) get their own winners — the ``--datasets``
    benchmarks show the winning impl differs per class.
    """
    from repro.sparse.structure import classify_format

    w = fmt.num_windows
    nnzv = fmt.nnzv
    avg_vec = nnzv / max(w, 1)
    dt = jnp_dtype_name(dtype if dtype is not None else fmt.values.dtype)
    return "|".join([
        op,
        f"v{fmt.vector_size}",
        f"w{_log2_bucket(w)}",
        f"vec{_log2_bucket(avg_vec)}",
        f"sk{_log2_bucket(window_skew(fmt))}",
        f"cls{classify_format(fmt)}",
        f"n{_log2_bucket(n)}",
        f"dt{dt}",
        f"b{_log2_bucket(batch)}",
        jax.default_backend(),
        "interp" if interpret else "compiled",
    ])


def jnp_dtype_name(dtype) -> str:
    return np.dtype(dtype).name


def _salvage_configs(text: str) -> Dict[str, Dict]:
    """Recover per-key entries from a torn/corrupted cache file.

    A crash mid-``os.replace`` cannot tear the file, but external
    corruption (truncation, a stray editor, disk trouble) can.  The
    entries are flat JSON objects, so every ``"key": {...}`` pair whose
    object still parses — and survives :meth:`TuneConfig.from_json` — is
    kept; the rest of the file is dropped.  Only runs when the text still
    carries the current schema marker (``put`` writes it *first* so a
    tail-truncated file keeps it): a torn *old*-schema file must stay
    discarded wholesale.
    """
    m = re.search(r'"schema"\s*:\s*(\d+)', text)
    if m is None or int(m.group(1)) != SCHEMA_VERSION:
        return {}
    configs: Dict[str, Dict] = {}
    for em in re.finditer(r'"((?:[^"\\]|\\.)+)"\s*:\s*(\{[^{}]*\})', text):
        key = em.group(1)
        if key in ("schema", "configs"):
            continue
        try:
            entry = json.loads(em.group(2))
            TuneConfig.from_json(entry)   # reject malformed entries
        except (ValueError, KeyError, TypeError):
            continue
        configs[key] = entry
    return configs


class AutotuneCache:
    """Persistent JSON cache ``{stats_key: TuneConfig}`` with atomic saves.

    On disk: ``{"schema": SCHEMA_VERSION, "configs": {key: cfg}}``.  A file
    whose schema does not match (including the schema-less v1 layout) is
    treated as empty — stale keys from an older bucketing scheme must not
    satisfy new lookups.  A *corrupted* current-schema file (torn JSON,
    malformed entries) is salvaged entry-by-entry rather than discarded:
    each still-parseable config survives (DESIGN.md §15).
    """

    def __init__(self, path: Optional[str] = None):
        self.path = path or os.environ.get(_CACHE_ENV, _DEFAULT_CACHE_PATH)
        self._data: Optional[Dict[str, Dict]] = None

    def _load(self) -> Dict[str, Dict]:
        if self._data is None:
            text = None
            try:
                with open(self.path) as f:
                    text = f.read()
                raw = json.loads(text)
                if (isinstance(raw, dict)
                        and raw.get("schema") == SCHEMA_VERSION
                        and isinstance(raw.get("configs", {}), dict)):
                    self._data = raw.get("configs", {})
                else:
                    # Warn once per cache object — _load memoizes, so
                    # per-lookup calls never re-log the discard.
                    found = (raw.get("schema", "none (v1 layout)")
                             if isinstance(raw, dict) else "none (v1 layout)")
                    logger.warning(
                        "discarding autotune cache %s: schema %s != %d "
                        "(stale bucketing; re-tuning from scratch)",
                        self.path, found, SCHEMA_VERSION)
                    self._data = {}
            except OSError:
                self._data = {}
            except ValueError:
                self._data = _salvage_configs(text or "")
                logger.warning(
                    "autotune cache %s is corrupted JSON; salvaged %d "
                    "entr%s, re-tuning the rest", self.path,
                    len(self._data), "y" if len(self._data) == 1 else "ies")
        return self._data

    def get(self, key: str) -> Optional[TuneConfig]:
        entry = self._load().get(key)
        if not entry:
            return None
        try:
            return TuneConfig.from_json(entry)
        except (KeyError, TypeError, ValueError):
            logger.warning("autotune cache %s: dropping malformed entry "
                           "for %r", self.path, key)
            return None

    def put(self, key: str, cfg: TuneConfig) -> None:
        data = self._load()
        data[key] = cfg.to_json()
        try:
            os.makedirs(os.path.dirname(self.path) or ".", exist_ok=True)
            tmp = f"{self.path}.tmp.{os.getpid()}"
            with open(tmp, "w") as f:
                # "schema" first (no top-level sort_keys): a tail-torn
                # file keeps its schema marker, which gates salvage.
                json.dump({"schema": SCHEMA_VERSION,
                           "configs": dict(sorted(data.items()))},
                          f, indent=2)
            os.replace(tmp, self.path)
        except OSError as e:
            # An unwritable cache dir must not fail the run — the tuned
            # config is already memoized in-process.
            logger.warning("autotune cache %s is not writable (%s); "
                           "keeping tuned configs in memory only",
                           self.path, e)


_DEFAULT_CACHE: Optional[AutotuneCache] = None


def default_cache() -> AutotuneCache:
    global _DEFAULT_CACHE
    if _DEFAULT_CACHE is None:
        _DEFAULT_CACHE = AutotuneCache()
    return _DEFAULT_CACHE


def _median_ms(fn, reps: int, warmup: int = 1) -> float:
    for _ in range(warmup):
        jax.block_until_ready(fn())
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        times.append((time.perf_counter() - t0) * 1e3)
    return float(np.median(times))


def _sweep(fmt: MEBCRS, run_cfg, minor: int, key: str, *,
           k_blks: Sequence[int], n_blks: Sequence[int],
           split_blks: Sequence[int], precisions: Sequence[str],
           overlap_batches: Sequence[int] = (0,), reps: int,
           cache: Optional[AutotuneCache]) -> TuneConfig:
    from repro.core.quantize import validate_precision

    for prec in precisions:
        validate_precision(prec)
    cache = cache if cache is not None else default_cache()
    # The candidate grid is part of the key: a sweep over (8, 16) must not
    # satisfy a later request for (32,) — the winner would be a config the
    # caller explicitly excluded.  Ditto the precision candidates (v4) and
    # the overlap-pipeline candidates (v5).
    key = (f"{key}|k{','.join(map(str, sorted(k_blks)))}"
           f"|nb{','.join(map(str, sorted(n_blks)))}"
           f"|s{','.join(map(str, sorted(split_blks)))}"
           f"|p{','.join(sorted(precisions))}"
           f"|o{','.join(map(str, sorted(overlap_batches)))}")
    hit = cache.get(key)
    if hit is not None:
        return hit

    best: Optional[TuneConfig] = None
    n_failed = 0
    last_err: Optional[BaseException] = None
    for k_blk in k_blks:
        blocked = block_format(fmt, k_blk)
        for split in split_blks:
            for prec in precisions:
                for ob in overlap_batches:
                    seen = set()
                    for n_blk in n_blks:
                        eff = min(n_blk, max(minor, 1))
                        if eff in seen:
                            continue
                        seen.add(eff)
                        # Keep-alive (DESIGN.md §15): one candidate
                        # crashing (Mosaic lowering, VMEM overflow, an
                        # unsupported tile) must not kill the sweep — it
                        # gets inf cost and the sweep moves on.
                        try:
                            ms = _median_ms(
                                lambda: run_cfg(blocked, eff, split, prec,
                                                ob),
                                reps=reps)
                        except Exception as e:
                            n_failed += 1
                            last_err = e
                            logger.warning(
                                "autotune candidate (k_blk=%d, n_blk=%d, "
                                "split=%d, prec=%s, ob=%d) failed: %s: %s",
                                k_blk, eff, split, prec, ob,
                                type(e).__name__, str(e)[:200])
                            continue
                        if best is None or ms < best.median_ms:
                            best = TuneConfig(k_blk=k_blk, n_blk=eff,
                                              median_ms=ms, split_blk=split,
                                              precision=prec,
                                              overlap_batches=ob)
    if best is None:
        raise RuntimeError(
            f"autotune sweep for {key!r}: all {n_failed} candidates "
            f"failed") from last_err
    cache.put(key, best)
    return best


def tune_spmm(fmt: MEBCRS, b_dense: jax.Array, *,
              k_blks: Sequence[int] = DEFAULT_K_BLKS,
              n_blks: Sequence[int] = DEFAULT_N_BLKS,
              split_blks: Sequence[int] = DEFAULT_SPLIT_BLKS,
              precisions: Sequence[str] = ("fp32",),
              overlap_batches: Sequence[int] = (0,), mesh=None,
              interpret: bool = True, reps: int = 3,
              cache: Optional[AutotuneCache] = None) -> TuneConfig:
    """Pick (k_blk, n_blk, split_blk) for SpMM on this matrix class.

    ``split_blk`` candidates time the block-parallel balanced kernel
    (``split_blk >= 1``) against the window-parallel fused kernel
    (``split_blk = 0``); the window-skew bucket in the cache key makes
    that choice per matrix class.  ``b_dense`` may carry a leading
    batch/head dim (H, K, N): the sweep then times the **batched**
    ``(H, ...)`` grids on the full batch (one launch per candidate, the
    path batched callers actually run), and the batch size is part of the
    cache bucket so batched and unbatched shapes tune independently.
    ``precisions`` adds the dtype axis (DESIGN.md §13): each candidate is
    timed at each level and the winner's level rides in
    ``TuneConfig.precision`` (``"fp32"`` candidates run the operands'
    native dtypes, so a no-axis sweep behaves exactly as before v4).
    ``overlap_batches`` adds the sharded-overlap pipeline axis
    (DESIGN.md §14, v5): candidates ``>= 1`` time
    ``pallas_sharded_overlap`` at that depth over ``mesh`` (required for
    them; its data-axis size joins the cache key — a depth tuned on 4
    devices must not satisfy an 8-device lookup), while ``0`` keeps the
    single-device kernels, so a no-axis sweep behaves exactly as before.
    """
    from .spmm_pallas import spmm_pallas, spmm_pallas_balanced

    if any(ob > 0 for ob in overlap_batches):
        from repro.distributed.sparse_shard import _resolve_mesh

        mesh = _resolve_mesh(mesh)
    batch = b_dense.shape[0] if b_dense.ndim == 3 else 1

    def run(blocked, n_blk, split, prec, ob):
        prec = None if prec == "fp32" else prec   # fp32 = native dtypes
        if ob:
            from repro.distributed.sparse_shard_overlap import (
                spmm_sharded_overlap,
            )

            return spmm_sharded_overlap(blocked, b_dense, mesh=mesh,
                                        split_blk=split, n_blk=n_blk,
                                        n_batches=ob, interpret=interpret,
                                        precision=prec)
        if split:
            return spmm_pallas_balanced(blocked, b_dense, split_blk=split,
                                        n_blk=n_blk, interpret=interpret,
                                        precision=prec)
        return spmm_pallas(blocked, b_dense, n_blk=n_blk,
                           interpret=interpret, precision=prec)

    n = b_dense.shape[-1]
    key = matrix_stats_key(fmt, n, "spmm", interpret=interpret,
                           dtype=b_dense.dtype, batch=batch)
    if any(ob > 0 for ob in overlap_batches):
        key = f"{key}|d{mesh.shape['data']}"
    return _sweep(
        fmt, run, n, key, k_blks=k_blks, n_blks=n_blks,
        split_blks=split_blks, precisions=precisions,
        overlap_batches=overlap_batches, reps=reps, cache=cache,
    )


def tune_sddmm(fmt: MEBCRS, q: jax.Array, k: jax.Array, *,
               k_blks: Sequence[int] = DEFAULT_K_BLKS,
               f_blks: Sequence[int] = DEFAULT_N_BLKS,
               split_blks: Sequence[int] = (0,),
               precisions: Sequence[str] = ("fp32",),
               interpret: bool = True, reps: int = 3,
               cache: Optional[AutotuneCache] = None) -> TuneConfig:
    """Pick (k_blk, f_blk) for :func:`sddmm_pallas` on this matrix class.

    SDDMM's grid is already block-parallel (one uniform unit of work per
    K-block, DESIGN.md §11), so the split sweep defaults to the plain
    kernel only; pass ``split_blks`` explicitly to time the scheduled
    variant.  Like :func:`tune_spmm`, ``q``/``k`` may carry a leading
    batch/head dim; the batched ``(H, NB, F/F_BLK)`` grid is then timed
    on the full batch and the batch size keys the bucket.
    """
    from .sddmm_pallas import sddmm_pallas, sddmm_pallas_balanced

    batch = next((x.shape[0] for x in (q, k) if x.ndim == 3), 1)

    def run(blocked, f_blk, split, prec, _ob):
        prec = None if prec == "fp32" else prec
        if split:
            return sddmm_pallas_balanced(blocked, q, k, split_blk=split,
                                         f_blk=f_blk, interpret=interpret,
                                         precision=prec)
        return sddmm_pallas(blocked, q, k, f_blk=f_blk, interpret=interpret,
                            precision=prec)

    f = q.shape[-1]
    key = matrix_stats_key(fmt, f, "sddmm", interpret=interpret,
                           dtype=q.dtype, batch=batch)
    return _sweep(
        fmt, run, f, key, k_blks=k_blks, n_blks=f_blks,
        split_blks=split_blks, precisions=precisions, reps=reps, cache=cache,
    )


def tune_attention(fmt: MEBCRS, q: jax.Array, k: jax.Array, v: jax.Array, *,
                   k_blks: Sequence[int] = DEFAULT_K_BLKS,
                   split_blks: Sequence[int] = DEFAULT_SPLIT_BLKS,
                   precisions: Sequence[str] = ("fp32",),
                   interpret: bool = True, reps: int = 3,
                   cache: Optional[AutotuneCache] = None) -> TuneConfig:
    """Pick ``(k_blk, split_blk)`` for the fused sparse-attention kernel.

    The megakernel grids keep whole K/V rows resident per K-block, so the
    free parameters are the block depth and the schedule's segment cap
    (``split_blk = 0`` times the window-parallel ``(H, W)`` grid,
    ``>= 1`` the balanced ``(H, NS)`` grid); the returned
    ``TuneConfig.n_blk`` records the (fixed) value head dim for the cache
    record.  ``q``/``k``/``v`` may carry a leading head dim — the sweep
    times the single batched launch, and H keys the bucket.
    """
    from .attention_pallas import attention_pallas, attention_pallas_balanced

    batch = next((x.shape[0] for x in (q, k, v) if x.ndim == 3), 1)
    d = q.shape[-1]
    dv = v.shape[-1]
    key = matrix_stats_key(fmt, d, "attn", interpret=interpret,
                           dtype=q.dtype, batch=batch)

    def run(blocked, _dv, split, prec, _ob):
        prec = None if prec == "fp32" else prec
        if split:
            return attention_pallas_balanced(blocked, q, k, v,
                                             split_blk=split,
                                             interpret=interpret,
                                             precision=prec)
        return attention_pallas(blocked, q, k, v, interpret=interpret,
                                precision=prec)

    return _sweep(
        fmt, run, dv, key, k_blks=k_blks, n_blks=(dv,),
        split_blks=split_blks, precisions=precisions, reps=reps, cache=cache,
    )
