"""GCN (Kipf & Welling, ICLR 2017) as the benchmark runs and counts it.

    h_{i+1} = relu(A h_i W_i),  logits = A h_{L-1} W_{L-1}

The program's side is ``repro.models.gnn.make_train_step`` on an
``ADPlan``; the plain forward below is the reference's (copied from the
program's smoke test).  ``counts`` is the work a step needs, from the
graph's ``nnz``, ``m`` and ``n`` and the widths alone: no format, block
size or kernel layout enters it.
"""

from __future__ import annotations

import jax

F32 = 4  # bytes of a float32 value or an int32 index

# ``make_train_step`` fixes its optimizer: SGD with this momentum.  The
# configuration states it for the reference, and a run of another is
# refused rather than compared with the wrong optimizer.
PROGRAM_OPTIMIZER = ("sgd_momentum", 0.9)


def widths(cfg):
    return ([cfg["in_dim"]] + [cfg["hidden_dim"]] * (cfg["num_layers"] - 1)
            + [cfg["num_classes"]])


def program_step(cfg, interpret):
    """The program's train step for this configuration: SGD with
    momentum over an ``ADPlan``, the registry impl ``cfg["impl"]``."""
    from repro.models.gnn import GNNConfig, make_train_step

    if (cfg["optimizer"], cfg["momentum"]) != PROGRAM_OPTIMIZER:
        raise ValueError(f"the program trains with {PROGRAM_OPTIMIZER}; the "
                         f"configuration states {cfg['optimizer']!r}, "
                         f"momentum {cfg['momentum']}")
    gnn = GNNConfig(model="gcn", in_dim=cfg["in_dim"],
                    hidden_dim=cfg["hidden_dim"],
                    num_classes=cfg["num_classes"],
                    num_layers=cfg["num_layers"], impl=cfg["impl"],
                    interpret=interpret)
    return make_train_step(gnn, lr=cfg["lr"])


def init(key, cfg):
    """Weights in the program's pytree layout, Glorot-normal."""
    dims = widths(cfg)
    keys = jax.random.split(key, cfg["num_layers"])
    return {"w": [jax.random.normal(k, (dims[i], dims[i + 1]))
                  * (2.0 / (dims[i] + dims[i + 1])) ** 0.5
                  for i, k in enumerate(keys)]}


def out_leaf(tree):
    """The output layer's weight: the one leaf whose gradient holds no
    ReLU derivative, so a unit within rounding of zero cannot flip it."""
    return tree["w"][-1]


def forward(edges, params, x, mm):
    h = x
    for i, w in enumerate(params["w"]):
        h = mm(edges.aggregate(h), w)
        if i < len(params["w"]) - 1:
            h = jax.nn.relu(h)
    return h


def spmm_bytes(nnz, rows, cols, width):
    """Least HBM traffic of one SpMM: values, column ids and row pointers
    of the sparse operand, the dense operand read once, the output
    written once."""
    return F32 * (2 * nnz + rows + 1) + F32 * width * (cols + rows)


def counts(graph, cfg):
    """Operations and bytes one training step needs.

    ``spmm``: the aggregations on the step's path, forward (one per layer)
    and transposed in the backward (every layer but the first, whose input
    is the features and has no gradient).  ``step_flops``: every
    contraction of the forward and the backward, two per multiply-add,
    nothing recomputed; elementwise work is left out.
    """
    nnz, m, n = graph["nnz"], graph["m"], graph["n"]
    dims = widths(cfg)
    spmm_ops = spmm_b = 0
    flops = 0
    for i in range(cfg["num_layers"]):
        d, d_out = dims[i], dims[i + 1]
        agg = 2 * nnz * d
        dense = 2 * m * d * d_out
        spmm_ops += agg
        spmm_b += spmm_bytes(nnz, m, n, d)
        flops += agg + dense            # forward
        flops += dense                  # dW
        if i > 0:                       # gradient into the layer's input
            spmm_ops += agg
            spmm_b += spmm_bytes(nnz, n, m, d)
            flops += dense + agg
    return {"spmm": {"calls": 2 * cfg["num_layers"] - 1, "ops": spmm_ops,
                     "bytes": spmm_b},
            "step_flops": flops}
