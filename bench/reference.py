"""The plain reference a run's training steps are checked against.

Copied from the program's on-chip smoke test (``chip_smoke.make_reference``)
and split in two: what every model shares lives here (edges, aggregation,
loss, the optimizer's update, the float64 runner); each model's forward
lives in ``bench/models/<config>.py`` beside its counts.  Nothing here
imports the program or takes anything it made: the reference starts from
the COO triplets of ``bench/graphs.py`` and from weights and features that
the benchmark makes from the seed.

``mm`` is the one matrix product of every forward: exact (``HIGHEST``) in
the reference, or ``three_pass``, what a TPU's ``high`` precision computes
(bf16x3, the a_lo*b_lo product dropped) written out for the CPU, where it
stands in for the control in the tests.  On a TPU it reads as coarse as
one bf16 pass (XLA's excess-precision rewrite likely folds the split), so
there the control is the program traced at ``high``.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Edges:
    """De-duplicated COO edges: row, column and value per edge."""

    r: jax.Array
    c: jax.Array
    v: jax.Array
    n: int = dataclasses.field(metadata=dict(static=True))

    def edge_sum(self, per_edge):
        return jax.ops.segment_sum(per_edge, self.r, num_segments=self.n)

    def aggregate(self, h):
        return self.edge_sum(self.v[:, None] * h[self.c])


def make_edges(rows, cols, vals, n) -> Edges:
    """Sum duplicate ``(row, col)`` entries, as the sparse format does."""
    key = rows.astype(np.int64) * n + cols
    uniq, inv = np.unique(key, return_inverse=True)
    v = np.bincount(inv, weights=vals).astype(np.float32)
    return Edges((uniq // n).astype(np.int32), (uniq % n).astype(np.int32),
                 v, n)


def exact(a, b):
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


@jax.custom_vjp
def three_pass(a, b):
    """``a @ b`` from bf16 halves, three products (TPU ``high``); its
    gradients are three-pass products too, as ``high`` computes them."""
    def split(x):
        hi = x.astype(jnp.bfloat16)
        return hi, (x - hi.astype(x.dtype)).astype(jnp.bfloat16)

    def dot(x, y):
        return jnp.matmul(x, y, preferred_element_type=jnp.float32)

    a_hi, a_lo = split(a)
    b_hi, b_lo = split(b)
    return (dot(a_hi, b_hi) + dot(a_hi, b_lo) + dot(a_lo, b_hi)).astype(
        a.dtype)


def _three_pass_fwd(a, b):
    return three_pass(a, b), (a, b)


def _three_pass_bwd(res, g):
    a, b = res
    return three_pass(g, b.T), three_pass(a.T, g)


three_pass.defvjp(_three_pass_fwd, _three_pass_bwd)


def loss(logits, labels, mask):
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1)


def make_train(forward: Callable, mm: Callable, lr: float, momentum: float):
    """One step of SGD with momentum on ``loss(forward(...))``:
    ``(params, mom, edges, x, labels, mask) -> (params, mom, loss, grad)``."""
    def objective(params, edges, x, labels, mask):
        return loss(forward(edges, params, x, mm), labels, mask)

    @jax.jit
    def step(params, mom, edges, x, labels, mask):
        value, grad = jax.value_and_grad(objective)(params, edges, x, labels,
                                                    mask)
        mom = jax.tree.map(lambda m, g: momentum * m + g, mom, grad)
        params = jax.tree.map(lambda p, m: p - lr * m, params, mom)
        return params, mom, value, grad

    return step


def run_steps(step, params, edges, x, labels, mask, steps):
    """``steps`` steps from zero momentum: ``(losses, first gradient,
    final params)`` as NumPy float64."""
    mom = jax.tree.map(jnp.zeros_like, params)
    losses, grad0 = [], None
    for _ in range(steps):
        params, mom, value, grad = step(params, mom, edges, x, labels, mask)
        losses.append(float(value))
        if grad0 is None:
            grad0 = grad
    as_np = lambda t: jax.tree.map(lambda a: np.asarray(a, np.float64), t)
    return np.array(losses), as_np(grad0), as_np(params)


def on_host_f64(fn, *args):
    """``fn(*args)`` in float64 on the host CPU: floating leaves widened,
    integer leaves kept."""
    cpu = jax.devices("cpu")[0]

    def widen(a):
        a = np.asarray(a)
        if np.issubdtype(a.dtype, np.floating):
            a = a.astype(np.float64)
        return jax.device_put(a, cpu)

    with jax.enable_x64(True):
        return fn(*jax.tree.map(widen, args))
