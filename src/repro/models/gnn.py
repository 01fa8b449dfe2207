"""GCN and AGNN on FlashSparse operators (paper §4.4 end-to-end case).

GCN layer:   H' = σ( Â @ H @ W )                         — SpMM
AGNN layer:  P = softmax_sparse( β · cos(h_i, h_j) )      — sparse attention
             H' = P @ H                                     (q=k=ĥ, v=h,
                                                             scale=β)

With an ADPlan adjacency the AGNN layer runs the sparse-attention
pipeline through :func:`repro.core.autodiff.attention_ad` — Pallas impls
execute the single-pass fused megakernel (scores never leave VMEM,
DESIGN.md §10), XLA impls the staged SDDMM → sparse softmax → SpMM
composition.

The adjacency arrives either as

  * an :class:`~repro.core.autodiff.ADPlan` (``ad_plan(fmt, impl=...)``) —
    the differentiable path: every sparse op runs through the custom_vjp
    wrappers, so ``jax.grad`` of the loss executes the dispatched kernels
    backward too (transpose-SpMM on the cached Aᵀ, masked SDDMM), for any
    registry impl including ``pallas``/``pallas_balanced``; or
  * a bare :class:`BlockedMEBCRS` — forward-only convenience: ops dispatch
    through the registry directly; training still works for the natively
    differentiable XLA ``blocked`` impl (plain tracing), which is the
    historical behavior.

``cfg.impl`` is honored by **both** SpMM and SDDMM via the unified
dispatch registry (:mod:`repro.core.dispatch`).

Multi-device training (DESIGN.md §12): build the plan with
``ad_plan(fmt, impl="pallas_sharded", mesh=make_host_mesh(data, model))``
and set ``cfg.impl="pallas_sharded"`` — every aggregation (and its
backward duality ops) then runs one local balanced launch per device
under ``shard_map``, row segments over the mesh's "data" axis and
heads/feature columns over "model".  The psum that reassembles each
layer's output is exactly the row all-gather the next layer's global
aggregation needs, so the model code here is unchanged.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Union

import jax
import jax.numpy as jnp

from repro.core import BlockedMEBCRS, with_values
from repro.core import dispatch as sparse_dispatch
from repro.core.autodiff import ADPlan, attention_ad, sddmm_ad, spmm_ad
from repro.core.softmax import sparse_softmax

__all__ = ["GNNConfig", "Adjacency", "init_gcn", "gcn_forward", "init_agnn",
           "agnn_forward", "gnn_loss", "make_train_step"]

Adjacency = Union[ADPlan, BlockedMEBCRS]


@dataclasses.dataclass(frozen=True)
class GNNConfig:
    model: str = "gcn"              # "gcn" | "agnn"
    in_dim: int = 128
    hidden_dim: int = 128           # paper: 128 (GCN), 32 (AGNN)
    num_classes: int = 16
    num_layers: int = 5             # paper: 5-layer GCN
    impl: str = "blocked"           # any registry impl: "blocked" | "pallas"
                                    # | "pallas_balanced" | ...
    interpret: Any = None           # None = auto (compile on TPU)
    dtype: Any = jnp.float32


def _dense_init(key, fan_in, fan_out, dtype):
    scale = (2.0 / (fan_in + fan_out)) ** 0.5
    return (jax.random.normal(key, (fan_in, fan_out)) * scale).astype(dtype)


def init_gcn(key: jax.Array, cfg: GNNConfig) -> Dict:
    dims = [cfg.in_dim] + [cfg.hidden_dim] * (cfg.num_layers - 1) + [cfg.num_classes]
    keys = jax.random.split(key, cfg.num_layers)
    return {"w": [_dense_init(k, dims[i], dims[i + 1], cfg.dtype)
                  for i, k in enumerate(keys)]}


def _aggregate(adj: Adjacency, h: jax.Array, cfg: GNNConfig,
               vals: jax.Array | None = None) -> jax.Array:
    """SpMM aggregation through the registry, honoring ``cfg.impl``.

    ``vals`` rebinds the sparse values (AGNN attention probabilities);
    ``None`` uses the adjacency's own values.
    """
    if isinstance(adj, ADPlan):
        v = adj.vals if vals is None else vals
        return spmm_ad(adj, v, h, impl=cfg.impl, interpret=cfg.interpret)
    blocked = adj if vals is None else with_values(adj, vals)
    return sparse_dispatch.dispatch("spmm", cfg.impl, blocked, h,
                                    k_blk=blocked.k_blk,
                                    interpret=cfg.interpret)


def _edge_scores(adj: Adjacency, q: jax.Array, k: jax.Array,
                 cfg: GNNConfig) -> jax.Array:
    """SDDMM through the registry, honoring ``cfg.impl``."""
    if isinstance(adj, ADPlan):
        return sddmm_ad(adj, q, k, impl=cfg.impl, interpret=cfg.interpret)
    return sparse_dispatch.dispatch("sddmm", cfg.impl, adj, q, k,
                                    k_blk=adj.k_blk, interpret=cfg.interpret)


def _pattern(adj: Adjacency) -> BlockedMEBCRS:
    return adj.fwd if isinstance(adj, ADPlan) else adj


def gcn_forward(params: Dict, adj: Adjacency, x: jax.Array,
                cfg: GNNConfig) -> jax.Array:
    h = x
    n_layers = len(params["w"])
    for i, w in enumerate(params["w"]):
        h = _aggregate(adj, h, cfg)             # feature aggregation (SpMM)
        h = h @ w                               # feature update (dense)
        if i < n_layers - 1:
            h = jax.nn.relu(h)
    return h


def init_agnn(key: jax.Array, cfg: GNNConfig) -> Dict:
    k_in, k_out, *keys = jax.random.split(key, cfg.num_layers + 2)
    return {
        "w_in": _dense_init(k_in, cfg.in_dim, cfg.hidden_dim, cfg.dtype),
        "beta": [jnp.ones((), cfg.dtype) for _ in range(cfg.num_layers)],
        "w_out": _dense_init(k_out, cfg.hidden_dim, cfg.num_classes, cfg.dtype),
    }


def agnn_forward(params: Dict, adj: Adjacency, x: jax.Array,
                 cfg: GNNConfig) -> jax.Array:
    h = jax.nn.relu(x @ params["w_in"])
    for beta in params["beta"]:
        hn = h / jnp.maximum(jnp.linalg.norm(h, axis=-1, keepdims=True), 1e-6)
        if isinstance(adj, ADPlan):
            # softmax(β·cos) aggregation is exactly the sparse-attention
            # pipeline with q = k = ĥ, v = h, scale = β: Pallas impls run
            # the single-pass fused megakernel (scores never touch HBM),
            # XLA impls the staged composition — one code path either way.
            h = attention_ad(adj, hn, hn, h, scale=beta, impl=cfg.impl,
                             interpret=cfg.interpret)
        else:
            scores = _edge_scores(adj, hn, hn, cfg)      # cosine via SDDMM
            p = sparse_softmax(_pattern(adj), beta * scores)
            h = _aggregate(adj, h, cfg, vals=p.astype(h.dtype))
    return h @ params["w_out"]


def gnn_loss(params, adj, x, labels, train_mask, cfg: GNNConfig):
    fwd = gcn_forward if cfg.model == "gcn" else agnn_forward
    logits = fwd(params, adj, x, cfg).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    loss = jnp.sum(nll * train_mask) / jnp.maximum(jnp.sum(train_mask), 1)
    acc = jnp.sum((jnp.argmax(logits, -1) == labels) * train_mask) / \
        jnp.maximum(jnp.sum(train_mask), 1)
    return loss, acc


def make_train_step(cfg: GNNConfig, lr: float = 1e-2):
    """GNN train step — delegates to :mod:`repro.train.train_step`, which
    validates ``cfg.impl``'s ``differentiable`` capability via the
    registry before tracing."""
    from repro.train.train_step import make_gnn_train_step

    return make_gnn_train_step(cfg, lr=lr)
