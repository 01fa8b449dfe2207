"""Row-wise sparse softmax over blocked ME-BCRS values.

Needed by attention GNNs (AGNN/GAT): SDDMM scores → per-row softmax →
SpMM aggregation, all without leaving the blocked layout.  A sparse row
(window w, lane r) is scattered across all K-blocks of window w at vector
position r, so the reduction is a masked segment max/sum keyed by
``block_win``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .format import BlockedMEBCRS
from .metrics import op_tag

__all__ = ["sparse_softmax"]


def sparse_softmax(blocked: BlockedMEBCRS, scores: jax.Array) -> jax.Array:
    """Numerically-stable softmax per sparse row.

    ``scores``: (NNZP, V) blocked-layout values (e.g. SDDMM output), or
    (H, NNZP, V) with a leading batch/head dim (per-head sparse attention)
    — the reduction is per row per head.  Returns probabilities in the
    same layout; masked/padding entries are 0.
    """
    with op_tag("fs.sparse_softmax"):
        if scores.ndim == 3:
            return jax.vmap(_sparse_softmax_2d, in_axes=(None, 0))(blocked,
                                                                   scores)
        return _sparse_softmax_2d(blocked, scores)


@jax.jit
def _sparse_softmax_2d(blocked: BlockedMEBCRS, scores: jax.Array) -> jax.Array:
    v = blocked.vector_size
    k_blk = blocked.k_blk
    nb = blocked.num_blocks
    w = blocked.num_windows
    mask = blocked.mask

    neg = jnp.finfo(jnp.float32).min
    s = jnp.where(mask, scores.astype(jnp.float32), neg).reshape(nb, k_blk, v)

    block_max = jnp.max(s, axis=1)                                   # (NB, V)
    row_max = jax.ops.segment_max(block_max, blocked.block_win,
                                  num_segments=w)                     # (W, V)
    row_max = jnp.maximum(row_max, neg)  # empty windows stay finite-safe
    e = jnp.exp(s - row_max[blocked.block_win][:, None, :])
    e = e * mask.reshape(nb, k_blk, v)
    block_sum = jnp.sum(e, axis=1)                                    # (NB, V)
    row_sum = jax.ops.segment_sum(block_sum, blocked.block_win,
                                  num_segments=w)                     # (W, V)
    denom = jnp.maximum(row_sum, 1e-20)
    p = e / denom[blocked.block_win][:, None, :]
    return p.reshape(nb * k_blk, v).astype(scores.dtype)
