"""Communication/compute overlap primitives for the sharded sparse path.

Two reusable pieces live here (DESIGN.md §14):

  * :func:`ring_scatter_pipeline` — the double-buffered ``ppermute`` ring
    that ``pallas_sharded_overlap`` (``distributed/sparse_shard_overlap``)
    uses to replace the trailing bulk ``psum`` of the sharded sparse ops.
    Each device's balanced launch is sub-split into *segment batches*
    (``partition_schedule(..., n_batches=)``); the compact partial output
    of batch *i* circulates the ring while batch *i+1* computes, so on
    real hardware XLA's async collective-permute (``-start``/``-done``)
    hides the ICI hops behind MXU work — the same overlap the seed
    collective matmul below demonstrated for dense TP, finally wired into
    the sparse path.
  * :func:`ring_allgather_matmul` / :func:`collective_matmul` — the seed
    dense demo (ring all-gather overlapped with partial matmuls), kept as
    the minimal reference for the pattern; ``distributed/
    collective_matmul.py`` is now a thin re-export shim.

Everything is ``shard_map``-body level: plain ``jax.lax.ppermute`` over a
named axis, testable on CPU via
``XLA_FLAGS=--xla_force_host_platform_device_count=N``.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

__all__ = [
    "ring_scatter_pipeline",
    "ring_allgather_matmul",
    "collective_matmul",
]


def ring_scatter_pipeline(compute: Callable[[int], Tuple[jax.Array, ...]],
                          scatter: Callable[..., jax.Array],
                          acc: jax.Array, *, axis_name: str, axis_size: int,
                          n_batches: int) -> jax.Array:
    """Pipelined ring scatter-accumulate over ``n_batches`` local batches.

    ``compute(b)`` produces this device's compact partial for batch ``b``
    as a tuple of same-shaped-across-devices arrays (typically ``(buffer,
    row_index)``); ``scatter(acc, *partial)`` folds one partial —
    locally-computed or just-arrived — into the accumulator.  The
    schedule interleaves one ``compute`` per step with **one ring hop of
    every in-flight partial**, so batch ``b``'s message is issued while
    batch ``b+1`` computes (double-buffered, two live buffers per lane)
    and every partial makes exactly ``axis_size - 1`` hops — each device
    folds each ``(origin, batch)`` partial exactly once, which is why the
    result equals the bulk ``psum`` up to fp32 summation grouping.

    ``axis_size == 1`` degenerates to a plain local batch loop with no
    collectives; the loop is unrolled at trace time (``n_batches`` and
    ``axis_size`` are small static ints).
    """
    if n_batches < 1:
        raise ValueError(f"n_batches must be >= 1, got {n_batches}")
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]
    live = []  # [partial_tuple, hops_done]
    for step in range(n_batches + max(axis_size - 2, 0)):
        if step < n_batches:
            part = tuple(compute(step))
            acc = scatter(acc, *part)
            if axis_size > 1:
                live.append([part, 0])
        nxt = []
        for part, hops in live:
            part = tuple(jax.lax.ppermute(x, axis_name, perm) for x in part)
            acc = scatter(acc, *part)
            if hops + 1 < axis_size - 1:
                nxt.append([part, hops + 1])
        live = nxt
    return acc


# ---------------------------------------------------------------------------
# Seed dense demo: ring all-gather overlapped with partial matmuls
# (Wang et al., ASPLOS'23 style).  Kept as the reference instance of the
# pattern; the sparse ops use ring_scatter_pipeline above.
# ---------------------------------------------------------------------------


def ring_allgather_matmul(x_shard: jax.Array, w: jax.Array, axis_name: str,
                          axis_size: int) -> jax.Array:
    """Per-shard body: x logically ``(B, K)`` sharded on K; ``w`` ``(K, N/n)``
    resident.  Each ring step contributes ``x_chunk @ w_rows`` for the
    chunk currently held, so each ICI hop overlaps the previous chunk's
    MXU work.
    """
    n = axis_size
    idx = jax.lax.axis_index(axis_name)
    k_shard = x_shard.shape[-1]

    def step(s, carry):
        acc, chunk = carry
        src = jax.lax.rem(idx + s, n)
        acc = acc + jnp.dot(chunk, _dyn_rows(w, src, k_shard),
                            preferred_element_type=jnp.float32)
        chunk = jax.lax.ppermute(
            chunk, axis_name, [(i, (i - 1) % n) for i in range(n)])
        return acc, chunk

    out_cols = w.shape[1]
    acc0 = jnp.zeros(x_shard.shape[:-1] + (out_cols,), jnp.float32)
    acc, _ = jax.lax.fori_loop(0, n, step, (acc0, x_shard))
    return acc.astype(x_shard.dtype)


def _dyn_rows(w, src, k_shard):
    return jax.lax.dynamic_slice_in_dim(w, src * k_shard, k_shard, axis=0)


def collective_matmul(x: jax.Array, w: jax.Array, mesh: Mesh,
                      contract_axis: str = "data",
                      out_axis: Optional[str] = "model") -> jax.Array:
    """y = x @ w with ring-overlapped gather of x's contracting shards.

    x: (..., K) sharded P(..., contract_axis); w: (K, N) sharded
    P(None, out_axis).  Returns y: (..., N) sharded P(..., out_axis).
    Degenerate (axis size 1) falls back to plain dot.
    """
    n = mesh.shape.get(contract_axis, 1)
    if n == 1:
        return jnp.dot(x, w, preferred_element_type=jnp.float32).astype(x.dtype)

    x_spec = P(*([None] * (x.ndim - 1)), contract_axis)
    w_spec = P(None, out_axis)
    y_spec = P(*([None] * (x.ndim - 1)), out_axis)

    body = functools.partial(ring_allgather_matmul, axis_name=contract_axis,
                             axis_size=n)
    return jax.shard_map(body, mesh=mesh, in_specs=(x_spec, w_spec),
                         out_specs=y_spec, check_vma=False)(x, w)
