"""AGNN (Thekumparampil, Wang, Oh & Li, arXiv:1803.03735) as the benchmark
runs and counts it.

    h_0 = relu(x W_in)
    P_ij = softmax_j( beta_l cos(h_i, h_j) )   over row i's edges
    h_{l+1} = P h_l
    logits = h_L W_out

The program's side is ``repro.models.gnn.make_train_step`` on an
``ADPlan``: the fused attention kernel forward, the recompute backward
through SDDMM, the sparse softmax and SpMM.  The plain forward below is
the reference's and imports nothing of the program.  ``counts`` is the
work a step needs, from the graph's ``nnz``, ``m`` and ``n`` and the
widths alone: no format, block size, kernel layout or recomputation
enters it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.models.gcn import PROGRAM_OPTIMIZER, spmm_bytes

F32 = 4  # bytes of a float32 value or an int32 index


def program_step(cfg, interpret):
    """The program's train step for this configuration: SGD with
    momentum over an ``ADPlan``, the registry impl ``cfg["impl"]``."""
    from repro.models.gnn import GNNConfig, make_train_step

    if (cfg["optimizer"], cfg["momentum"]) != PROGRAM_OPTIMIZER:
        raise ValueError(f"the program trains with {PROGRAM_OPTIMIZER}; the "
                         f"configuration states {cfg['optimizer']!r}, "
                         f"momentum {cfg['momentum']}")
    gnn = GNNConfig(model="agnn", in_dim=cfg["in_dim"],
                    hidden_dim=cfg["hidden_dim"],
                    num_classes=cfg["num_classes"],
                    num_layers=cfg["num_layers"], impl=cfg["impl"],
                    interpret=interpret)
    return make_train_step(gnn, lr=cfg["lr"])


def _glorot(key, fan_in, fan_out):
    return (jax.random.normal(key, (fan_in, fan_out))
            * (2.0 / (fan_in + fan_out)) ** 0.5)


def init(key, cfg):
    """Weights in the program's pytree layout: Glorot-normal ``w_in`` and
    ``w_out`` from the keys the program's ``init_agnn`` uses, and one
    ``beta`` per layer, at 1."""
    k_in, k_out, *_ = jax.random.split(key, cfg["num_layers"] + 2)
    return {"w_in": _glorot(k_in, cfg["in_dim"], cfg["hidden_dim"]),
            "beta": [jnp.ones((), jnp.float32)
                     for _ in range(cfg["num_layers"])],
            "w_out": _glorot(k_out, cfg["hidden_dim"], cfg["num_classes"])}


def out_leaf(tree):
    """The output layer's weight: its gradient holds no ReLU derivative,
    so a unit within rounding of zero cannot flip it."""
    return tree["w_out"]


def _propagate(edges, h, beta, mm):
    """One attention layer over the de-duplicated edges: each edge's score
    is ``beta`` times the cosine of its two rows, the contraction of their
    product over the features (through ``mm``); the softmax is taken over
    each row's edges."""
    r, c = edges.r, edges.c
    hn = h / jnp.maximum(jnp.linalg.norm(h, axis=-1, keepdims=True), 1e-6)
    ones = jnp.ones((h.shape[-1], 1), h.dtype)
    cos = mm(hn[r] * hn[c], ones)[:, 0]
    s = beta * cos
    # the softmax does not depend on the shift: no gradient through it
    row_max = jax.lax.stop_gradient(
        jax.ops.segment_max(s, r, num_segments=edges.n))
    e = jnp.exp(s - row_max[r])
    p = e / jnp.maximum(edges.edge_sum(e), 1e-20)[r]
    return edges.edge_sum(p[:, None] * h[c])


def forward(edges, params, x, mm):
    """AGNN's logits.  Departures from arXiv:1803.03735: no dropout; the
    softmax runs over the graph's stored edges (the traffic's replicas
    carry a self-loop on every node, the paper's ``N(i) ∪ {i}``); each
    row's norm is floored at 1e-6 before the cosine; a row with no edges
    aggregates to zero; every ``beta`` is learned and starts at 1.  Each
    layer is recomputed in the backward (``jax.checkpoint``) rather than
    keep its per-edge tensors."""
    layer = jax.checkpoint(functools.partial(_propagate, mm=mm))
    h = jax.nn.relu(mm(x, params["w_in"]))
    for beta in params["beta"]:
        h = layer(edges, h, beta)
    return mm(h, params["w_out"])


def counts(graph, cfg):
    """Operations and bytes one training step needs.

    ``attention``: each layer's forward, scores and aggregation (two
    operations per multiply-add each), reading the pattern once (column
    ids and row pointers), the q, k and v rows once and writing the
    output once.  The backward of each layer: ``sddmm``, the
    probabilities' gradient ``dP = (G Vᵀ) ⊙ mask``, which moves what an
    SpMM of the same shape moves (``spmm_bytes``); ``spmm``, ``dV = Pᵀ
    G``, ``dQ = dS K`` and ``dK = dSᵀ Q``.  ``step_flops``: every
    contraction of the forward and the backward, nothing recomputed;
    elementwise work (norms, softmax) is left out.
    """
    nnz, m, n = graph["nnz"], graph["m"], graph["n"]
    layers, d = cfg["num_layers"], cfg["hidden_dim"]
    din, dout = cfg["in_dim"], cfg["num_classes"]
    edge = 2 * nnz * d                   # one contraction over the edges
    attn_bytes = F32 * (nnz + m + 1) + F32 * d * (2 * m + 2 * n)
    spmm_b = 2 * spmm_bytes(nnz, n, m, d) + spmm_bytes(nnz, m, n, d)
    # x W_in and dW_in; h W_out, dW_out and the gradient into h
    dense = 2 * m * din * d * 2 + 2 * m * d * dout * 3
    return {"attention": {"calls": layers, "ops": layers * 2 * edge,
                          "bytes": layers * attn_bytes},
            "sddmm": {"calls": layers, "ops": layers * edge,
                      "bytes": layers * spmm_bytes(nnz, m, n, d)},
            "spmm": {"calls": 3 * layers, "ops": layers * 3 * edge,
                     "bytes": layers * spmm_b},
            "step_flops": dense + layers * 6 * edge}
