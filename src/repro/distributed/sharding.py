"""Sharding rules: logical axes → mesh axes, fitted to actual shapes.

The production mesh is ``("data", "model")`` per pod, with an optional
leading ``"pod"`` axis (launch/mesh.py).  Parallelism styles compose as:

  DP / FSDP   batch over ("pod", "data"); every weight's *non-TP* matrix
              dim over "data" (ZeRO-3: XLA inserts per-layer all-gathers
              inside the scan-over-layers, so resident weight memory is
              1/|data| of the model)
  TP          heads / ffn-hidden / vocab over "model"
  EP          MoE expert dim over "model" (expert-parallel grouped GEMM)
  SP          long-context decode (batch=1): KV/latent cache sequence dim
              over "data" — sequence-parallel attention; XLA turns the
              softmax normalization into small all-reduces

Rules are *logical*: each param leaf name maps to a tuple of logical axis
names; :data:`LOGICAL_AXIS_RULES` maps those to mesh axes.  A logical axis
is applied to a tensor dim only when the mesh-axis product divides the dim
(``fit_pspec``) — non-divisible cases (e.g. granite's vocab=49155 on a
16-way model axis) degrade to replication on that dim instead of failing,
which keeps every (arch × shape × mesh) cell compilable with one rule set.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "LOGICAL_AXIS_RULES",
    "logical_spec_for",
    "fit_pspec",
    "param_shardings",
    "shardings_like",
    "batch_pspec",
    "cache_shardings",
    "sparse_format_shardings",
    "sparse_operand_pspec",
]


# logical axis → mesh axes (a tuple means "shard over the product")
LOGICAL_AXIS_RULES: Dict[str, Tuple[str, ...]] = {
    "batch": ("pod", "data"),
    "embed": ("data",),      # FSDP dim of every weight
    "vocab": ("model",),     # TP: vocab-sharded embedding + lm head
    "heads": ("model",),     # TP: attention heads / fused head*dim
    "ffn": ("model",),       # TP: MLP hidden
    "expert": ("model",),    # EP: MoE expert dim
    # SP: decode-cache sequence dim takes every axis batch didn't claim
    "kv_seq": ("pod", "data", "model"),
    # SP variant when kv-heads already take the model axis (cheaper comm)
    "kv_seq_dp": ("pod", "data"),
    "layers": (),            # stacked-layer leading dim: never sharded
}


# param leaf name → logical axes of its *trailing* dims.  Leaves with more
# leading dims than the rule length (scan-stacked layers, MoE experts under
# a stack) get `None` prepended; 1-D leaves not listed here are replicated.
_PARAM_RULES: Dict[str, Tuple[Optional[str], ...]] = {
    # embedding / head
    "embed": ("vocab", "embed"),
    "lm_head": ("embed", "vocab"),
    # GQA attention
    "wq": ("embed", "heads"),
    "wk": ("embed", "heads"),
    "wv": ("embed", "heads"),
    "wo": ("heads", "embed"),
    # MLA (DeepSeek): low-rank downs are data-sharded, ups are head-sharded
    "w_dq": ("embed", None),
    "w_uq": (None, "heads"),
    "w_dkv": ("embed", None),
    "w_uk": (None, "heads"),
    "w_uv": (None, "heads"),
    # dense MLP
    "w_gate": ("embed", "ffn"),
    "w_up": ("embed", "ffn"),
    "w_down": ("ffn", "embed"),
    # MoE router: replicated — it is tiny (d·E f32) and the EP dispatch
    # path (layers.moe_ffn_ep) needs it whole on every device
    "router": (None, None),
    # Mamba-2
    "in_proj": ("embed", "ffn"),
    "out_proj": ("ffn", "embed"),
    "conv_w": (None, "ffn"),
    "conv_b": ("ffn",),
}

_MOE_RULES: Dict[str, Tuple[Optional[str], ...]] = {
    "w_gate": ("expert", "embed", "ffn"),
    "w_up": ("expert", "embed", "ffn"),
    "w_down": ("expert", "ffn", "embed"),
}


def logical_spec_for(path: str, ndim: int) -> Tuple[Optional[str], ...]:
    """Logical axes for a param leaf, from its tree path and rank.

    ``path`` is "/"-joined dict keys, e.g. ``"layers/attn/wq"``.

    MoE expert weights are rank-3 unstacked / rank-4 scan-stacked; a
    rank-3 w_gate under "layers/" is a *stacked dense* MLP weight and must
    NOT take the expert rule (that sharded dense layer dims over the model
    axis — an early framework bug caught by the dry-run, §Perf 0.10).
    """
    name = path.split("/")[-1]
    rule = _PARAM_RULES.get(name)
    if name in _MOE_RULES:
        stacked = path.startswith("layers") or "/layers/" in path
        if ndim >= 4 or (ndim == 3 and not stacked):
            rule = _MOE_RULES[name]
    if rule is None:
        return (None,) * ndim
    if ndim < len(rule):  # unstacked leaf smaller than rule (shouldn't happen)
        return (None,) * ndim
    return (None,) * (ndim - len(rule)) + tuple(rule)


def _mesh_axes_that_fit(dim: int, axes: Sequence[str], mesh: Mesh,
                        used: set) -> Tuple[str, ...]:
    """Greedy prefix of ``axes`` present in the mesh whose product divides dim."""
    picked = []
    prod = 1
    for a in axes:
        if a not in mesh.shape or a in used:
            continue
        size = mesh.shape[a]
        if dim % (prod * size) == 0:
            picked.append(a)
            prod *= size
    return tuple(picked)


def fit_pspec(logical: Sequence[Optional[str]], shape: Sequence[int],
              mesh: Mesh,
              rules: Optional[Dict[str, Tuple[str, ...]]] = None) -> P:
    """Resolve logical axes to a PartitionSpec valid for ``shape`` on ``mesh``.

    Drops any mesh axis that does not divide its dim, and never assigns one
    mesh axis to two dims of the same tensor.
    """
    rules = rules or LOGICAL_AXIS_RULES
    used: set = set()
    parts = []
    for dim, lax_name in zip(shape, logical):
        if lax_name is None:
            parts.append(None)
            continue
        axes = _mesh_axes_that_fit(dim, rules.get(lax_name, ()), mesh, used)
        used.update(axes)
        if not axes:
            parts.append(None)
        elif len(axes) == 1:
            parts.append(axes[0])
        else:
            parts.append(tuple(axes))
    # strip trailing Nones (canonical form)
    while parts and parts[-1] is None:
        parts.pop()
    return P(*parts)


def _tree_paths(tree: Any):
    """(path_string, leaf) pairs in jax tree order."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    out = []
    for kp, leaf in flat:
        parts = []
        for k in kp:
            if hasattr(k, "key"):
                parts.append(str(k.key))
            elif hasattr(k, "idx"):
                parts.append(str(k.idx))
            else:
                parts.append(str(k))
        out.append(("/".join(parts), leaf))
    return out


def serving_rules() -> Dict[str, Tuple[str, ...]]:
    """Weight rules for decode: TP only, NO FSDP dim.

    FSDP re-gathers every weight on every decode step (one token cannot
    amortize it — measured ~0.3 GB/layer on the 76B decode cell).  When
    params/|model| fits HBM, replicate the data dim instead: weight
    gathers disappear from the serving path entirely.
    """
    return dict(LOGICAL_AXIS_RULES, embed=())


def param_shardings(param_shapes: Any, mesh: Mesh,
                    rules: Optional[Dict[str, Tuple[str, ...]]] = None) -> Any:
    """NamedSharding pytree for a params pytree (of arrays or ShapeDtypeStructs)."""
    flat = _tree_paths(param_shapes)
    specs = [
        NamedSharding(mesh, fit_pspec(
            logical_spec_for(path, len(leaf.shape)), leaf.shape, mesh, rules))
        for path, leaf in flat
    ]
    treedef = jax.tree_util.tree_structure(param_shapes)
    return jax.tree_util.tree_unflatten(treedef, specs)


def shardings_like(shardings: Any, target_shapes: Any) -> Any:
    """Map param shardings onto a same-structure-per-leaf state (e.g. Adam
    moments quantized to int8 keep their param's sharding; scalars replicate).

    Every inherited axis is re-checked for divisibility against the *target*
    leaf's shape (quantized scales shrink the last dim), dropping axes that
    no longer fit.
    """

    def pick(s, leaf):
        shape = leaf.shape
        if len(shape) == 0:
            return NamedSharding(s.mesh, P())
        spec = tuple(s.spec[: len(shape)])
        spec = spec + (None,) * (len(shape) - len(spec))
        fitted = []
        for dim, entry in zip(shape, spec):
            if entry is None:
                fitted.append(None)
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            prod = 1
            keep = []
            for a in axes:
                sz = s.mesh.shape[a]
                if dim % (prod * sz) == 0:
                    keep.append(a)
                    prod *= sz
            fitted.append(tuple(keep) if len(keep) > 1
                          else (keep[0] if keep else None))
        while fitted and fitted[-1] is None:
            fitted.pop()
        return NamedSharding(s.mesh, P(*fitted))

    return jax.tree.map(pick, shardings, target_shapes)


def batch_pspec(mesh: Mesh, extra_dims: int = 1) -> P:
    """(B, ...) batch sharding: batch over every data-like axis present."""
    axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    if not axes:
        return P()
    bdim = axes[0] if len(axes) == 1 else axes
    return P(bdim, *(None,) * extra_dims)


# ---------------------------------------------------------------------------
# Sparse-op shardings (FlashSparse SpMM/SDDMM and their autodiff plans)
# ---------------------------------------------------------------------------


def sparse_format_shardings(fmt_tree: Any, mesh: Mesh) -> Any:
    """Shardings for a sparse-format pytree (``MEBCRS``, ``BlockedMEBCRS``,
    ``ADPlan``, or anything embedding a ``ShardedSchedule``).

    The pattern metadata (cols / win_ptr / mask / re-layout map) is tiny
    next to the dense operands — §6's footprint math puts ME-BCRS at
    ``4(W+NNZV) + 2·NNZV·V`` bytes, and the autodiff plan at ~2× that
    (DESIGN.md §9) — and the fused kernels read it from HBM by index, so
    every device keeps the full pattern **replicated** and parallelism
    comes from sharding the dense operands (:func:`sparse_operand_pspec`).
    This mirrors how the GNN baselines shard: graph replicated, feature
    matrices partitioned.

    The one exception is the per-device partition arrays of a
    :class:`~repro.distributed.sparse_shard.ShardedSchedule` (DESIGN.md
    §12): their leading dim *is* the device dim, so they shard
    ``P("data")`` — each device holds exactly its own sub-schedule and
    the ``shard_map`` in_spec becomes a no-op data movement.
    """
    from .sparse_shard import ShardedSchedule

    def node_shardings(node):
        if isinstance(node, ShardedSchedule):
            return jax.tree.map(
                lambda _: NamedSharding(mesh, P("data")), node)
        return jax.tree.map(lambda _: NamedSharding(mesh, P()), node)

    return jax.tree.map(node_shardings, fmt_tree,
                        is_leaf=lambda n: isinstance(n, ShardedSchedule))


def sparse_operand_pspec(mesh: Mesh, *, batched: bool = False,
                         heads_over_model: bool = False) -> P:
    """PartitionSpec for the dense operand of a sparse op.

    Rows (the contracted K dim) must stay whole per device — the kernel
    DMAs arbitrary rows by index — so the feature/N dim takes the "model"
    axis (TP) and an optional leading head/batch dim takes the data axes.

    ``heads_over_model=True`` is the placement for the **sharded** sparse
    ops (DESIGN.md §12), whose row parallelism lives *inside* the op (the
    "data" axis carries schedule segments, not operand rows): the leading
    head dim takes the "model" axis and everything else is replicated,
    matching ``spmm_sharded``'s head-parallel in_specs.
    """
    feat = "model" if "model" in mesh.shape else None
    if heads_over_model:
        return P(feat) if (batched and feat) else P()
    if not batched:
        return P(None, feat)
    axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    lead = axes[0] if len(axes) == 1 else (axes or None)
    return P(lead, None, feat)


# decode-cache leaf name → logical axes (per cache layout in models/lm.py).
# "kv_seq" spans every mesh axis the batch didn't claim, so the KV cache of
# a 32k/500k decode is spread over the whole pod even when batch or heads
# don't shard (sequence-parallel attention: XLA inserts the small
# softmax-stat collectives).
_CACHE_RULES: Dict[str, Tuple[Optional[str], ...]] = {
    "k": (None, "batch", "kv_seq", "heads", None),       # (L,B,S,Hkv,D)
    "v": (None, "batch", "kv_seq", "heads", None),
    "ckv": (None, "batch", "kv_seq", None),              # MLA latent (L,B,S,C)
    "k_rope": (None, "batch", "kv_seq", None),
    "conv": (None, "batch", None, "ffn"),                # (L,B,W-1,conv_dim)
    "ssm": (None, "batch", "heads", None, None),         # (L,B,H,P,N)
    "memory": ("batch", None, None),                     # (B,S_src,D) enc-dec
}


def cache_shardings(cache_shapes: Any, mesh: Mesh, *, batch: int) -> Any:
    """Shardings for a decode cache pytree (path-aware, divisibility-fitted).

    Batch gets the data axes when it divides; the sequence dim soaks up every
    remaining mesh axis ("kv_seq" → pod/data/model) — that is what makes the
    long_500k (batch=1) and small-kv-head caches fit (DESIGN.md §6 SP).
    """

    def leaf_sharding(path: str, leaf) -> NamedSharding:
        shape = leaf.shape
        name = path.split("/")[-1]
        # stacked caches are keyed by their innermost dict name ("k", "ssm", …)
        for part in reversed(path.split("/")):
            if part in _CACHE_RULES:
                name = part
                break
        rule = _CACHE_RULES.get(name)
        if rule is None or len(shape) < len(rule):
            return NamedSharding(mesh, P())
        logical = (None,) * (len(shape) - len(rule)) + rule
        # KV caches: if the head dim divides the model axis, give heads the
        # model axis (TP attention, no softmax collectives) and keep the
        # sequence on the data axes only.
        if name in ("k", "v") and "model" in mesh.shape:
            hkv = shape[len(shape) - 2]
            if hkv % mesh.shape["model"] == 0:
                logical = logical[:-3] + ("kv_seq_dp", "heads", None)
        return NamedSharding(mesh, fit_pspec(logical, shape, mesh))

    flat = _tree_paths(cache_shapes)
    specs = [leaf_sharding(p, l) for p, l in flat]
    treedef = jax.tree_util.tree_structure(cache_shapes)
    return jax.tree_util.tree_unflatten(treedef, specs)
