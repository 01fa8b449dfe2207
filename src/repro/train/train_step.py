"""Train-step builder: microbatched grad accumulation + AdamW + compression.

The returned ``train_step(state, batch) → (state, metrics)`` is a pure
function designed for ``jax.jit`` with explicit shardings (launch/dryrun.py,
launch/train.py).  Composition order:

  batch (B, S) → reshape (microbatches, B/μ, S)
  lax.scan over microbatches: remat'd loss → grads, f32 accumulation
    (per-layer remat lives inside the model via cfg.remat; the scan keeps
    peak activation memory at one microbatch)
  optional gradient compression (int8 / top-k) with error feedback carried
    in state["err"] — models the compressed DP all-reduce numerics exactly
    (quantize → reduce → dequantize), traffic accounting in §Perf
  AdamW update (optionally 8-bit moments)

``compressed_psum`` is the shard_map reference for an actual compressed
data-parallel reduction (all-gather int8 + local dequant-sum), used by the
GNN example and validated against plain psum in tests.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.config import ArchConfig
from repro.models.lm import init_lm, lm_loss

from .compression import (
    CompressionConfig,
    compress_int8,
    compress_topk,
    decompress_int8,
    decompress_topk,
    init_error,
)
from .optimizer import AdamWConfig, adamw_init, adamw_update

f32 = jnp.float32

__all__ = ["TrainStepConfig", "init_train_state", "make_train_step",
           "make_gnn_train_step", "compressed_psum"]


@dataclasses.dataclass(frozen=True)
class TrainStepConfig:
    opt: AdamWConfig = AdamWConfig()
    microbatches: int = 1
    compression: CompressionConfig = CompressionConfig(kind="none")
    aux_weight: float = 0.01
    # grad-accumulation buffer dtype: f32 default; bf16 halves the largest
    # training buffer for ≥100B-param configs (≈0.3-bit/step noise over 16
    # microbatches — §Perf measures the trade)
    accum_dtype: str = "float32"


def init_train_state(key: jax.Array, cfg: ArchConfig,
                     ts: TrainStepConfig) -> Dict[str, Any]:
    params = init_lm(key, cfg)
    state: Dict[str, Any] = {
        "params": params,
        "opt": adamw_init(params, ts.opt),
        "step": jnp.zeros((), jnp.int32),
    }
    if ts.compression.kind != "none":
        state["err"] = init_error(params)
    return state


def _split_microbatches(batch: Dict[str, jax.Array], n: int) -> Dict[str, jax.Array]:
    def resh(x):
        b = x.shape[0]
        assert b % n == 0, f"batch {b} not divisible by microbatches {n}"
        return x.reshape((n, b // n) + x.shape[1:])

    return jax.tree.map(resh, batch)


def make_train_step(cfg: ArchConfig, ts: TrainStepConfig
                    ) -> Callable[[Dict, Dict], Tuple[Dict, Dict]]:
    """Build the pure train step for one architecture."""

    def loss_fn(params, mb):
        total, parts = lm_loss(params, mb, cfg, aux_weight=ts.aux_weight)
        return total, parts

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def accumulate(params, batch):
        if ts.microbatches == 1:
            (loss, parts), grads = grad_fn(params, batch)
            return loss, parts, grads

        mbs = _split_microbatches(batch, ts.microbatches)
        acc_dt = jnp.dtype(ts.accum_dtype)

        def body(carry, mb):
            acc, loss_acc, ce_acc, aux_acc = carry
            (loss, parts), grads = grad_fn(params, mb)
            acc = jax.tree.map(lambda a, g: a + g.astype(acc_dt), acc, grads)
            return (acc, loss_acc + loss, ce_acc + parts["ce"],
                    aux_acc + parts["aux"]), None

        zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, acc_dt), params)
        z = jnp.zeros((), f32)
        (acc, loss_sum, ce_sum, aux_sum), _ = jax.lax.scan(
            body, (zeros, z, z, z), mbs)
        inv = 1.0 / ts.microbatches
        grads = jax.tree.map(lambda g: g * inv, acc)
        return loss_sum * inv, {"ce": ce_sum * inv, "aux": aux_sum * inv}, grads

    def train_step(state: Dict, batch: Dict) -> Tuple[Dict, Dict]:
        loss, parts, grads = accumulate(state["params"], batch)

        new_err = None
        if ts.compression.kind == "int8":
            comp, new_err = compress_int8(grads, state["err"], ts.compression)
            grads = decompress_int8(comp, grads)
        elif ts.compression.kind == "topk":
            comp, new_err = compress_topk(grads, state["err"], ts.compression)
            grads = decompress_topk(comp, grads)

        params, opt, om = adamw_update(grads, state["opt"], state["params"], ts.opt)
        new_state = {
            "params": params,
            "opt": opt,
            "step": state["step"] + 1,
        }
        if new_err is not None:
            new_state["err"] = new_err
        metrics = {"loss": loss, "ce": parts["ce"], "aux": parts["aux"], **om}
        return new_state, metrics

    return train_step


# ---------------------------------------------------------------------------
# GNN train step (paper §4.4 end-to-end case)
# ---------------------------------------------------------------------------


def make_gnn_train_step(cfg, lr: float = 1e-2):
    """SGD-with-momentum train step for the GNN models.

    Validates ``cfg.impl`` against the sparse-op dispatch registry before
    tracing: the impl must carry the ``differentiable`` capability flag
    (XLA ``blocked`` natively; the Pallas impls via the custom_vjp wrappers
    in :mod:`repro.core.autodiff`, which require the adjacency to arrive
    as an ``ADPlan``).  A non-differentiable impl (e.g. the staged
    ablation baselines) fails here with the list of usable ones, instead
    of deep inside tracing.

    ``step(params, mom, adj, x, labels, train_mask)`` — ``adj`` is an
    ``ADPlan`` or ``BlockedMEBCRS`` pytree, jit-traced like any operand.
    Each call runs under the host span ``fs.step``
    (:func:`repro.core.metrics.span`): on the host it lasts the dispatch,
    not the device's work.
    """
    from repro.core import dispatch as sparse_dispatch
    from repro.core.autodiff import ADPlan
    from repro.core.metrics import span
    from repro.models.gnn import gnn_loss

    sparse_dispatch.require("spmm", cfg.impl, differentiable=True)
    if cfg.model == "agnn":
        sparse_dispatch.require("sddmm", cfg.impl, differentiable=True)

    @jax.jit
    def jit_step(params, mom, adj, x, labels, train_mask):
        (loss, acc), grads = jax.value_and_grad(gnn_loss, has_aux=True)(
            params, adj, x, labels, train_mask, cfg)
        mom = jax.tree.map(lambda m, g: 0.9 * m + g, mom, grads)
        params = jax.tree.map(lambda p, m: p - lr * m, params, mom)
        return params, mom, loss, acc

    def step(params, mom, adj, x, labels, train_mask):
        # The Pallas impls differentiate only through the custom_vjp
        # wrappers, which need the ADPlan's cached transpose; catch a bare
        # blocked adjacency here instead of deep inside grad tracing.
        if cfg.impl != "blocked" and not isinstance(adj, ADPlan):
            raise ValueError(
                f"impl={cfg.impl!r} trains only through an ADPlan adjacency "
                f"(build one with ad_plan(fmt, impl={cfg.impl!r})); got "
                f"{type(adj).__name__}")
        with span("fs.step"):
            return jit_step(params, mom, adj, x, labels, train_mask)

    return step


# ---------------------------------------------------------------------------
# shard_map reference: actual compressed DP reduction (all-gather int8 +
# local dequant-sum).  Mean-reduces ``x`` over ``axis_name``.
# ---------------------------------------------------------------------------


def compressed_psum(x: jax.Array, axis_name: str, block: int = 256) -> jax.Array:
    """Inside shard_map: int8-compressed mean over the mapped axis."""
    flat = x.astype(f32).reshape(-1)
    pad = (-flat.shape[0]) % block
    xp = jnp.pad(flat, (0, pad)).reshape(-1, block)
    scale = jnp.maximum(jnp.max(jnp.abs(xp), axis=-1, keepdims=True), 1e-12) / 127.0
    q = jnp.clip(jnp.round(xp / scale), -127, 127).astype(jnp.int8)

    q_all = jax.lax.all_gather(q, axis_name)            # (n, nb, block) int8
    s_all = jax.lax.all_gather(scale, axis_name)        # (n, nb, 1)
    deq = q_all.astype(f32) * s_all                     # local dequant
    mean = deq.mean(axis=0).reshape(-1)
    size = 1
    for s in x.shape:
        size *= s
    return mean[:size].reshape(x.shape).astype(x.dtype)
