"""Beyond-paper integration: FlashSparse block-sparse attention in an LM.

The paper's operators are GNN-flavoured; this example shows the same
SDDMM → sparse-softmax → SpMM pipeline serving as *sparse attention* in a
transformer: a fixed block-sparse causal pattern (local window + strided
global, BigBird-ish) is stored as ME-BCRS at V=8 granularity; attention
scores are computed only at the nonzero pattern, row-normalized, and
aggregated.

The layer lives in ``repro.models.layers.sparse_attention``.  With
``--impl pallas``/``pallas_tuned`` it executes the **single-pass fused
megakernel** (DESIGN.md §10): one ``(H, W)`` grid launch computes SDDMM
scores into VMEM, applies the row-segment online softmax, and accumulates
against V — the scores never exist in HBM — and ``jax.grad`` flows through
the FlashAttention-style recompute backward onto the batched transpose-
SpMM/SDDMM duality kernels.  Validated here against dense masked
attention, values *and* gradients, plus (``--steps N``) a tiny training
loop that recovers a value projection through the fused gradient path.

  PYTHONPATH=src python examples/sparse_attention_lm.py \
      [--impl pallas] [--steps 1]
"""

import argparse

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import dispatch as sparse_dispatch
from repro.core import from_coo
from repro.core.autodiff import ad_plan
from repro.launch.cache import enable_compile_cache
from repro.models.layers import sparse_attention


def block_sparse_causal_pattern(seq: int, window: int = 64, stride: int = 128):
    """Local causal window + strided global tokens (BigBird-ish)."""
    rows, cols = [], []
    for i in range(seq):
        lo = max(0, i - window + 1)
        for j in range(lo, i + 1):
            rows.append(i), cols.append(j)
        for j in range(0, lo, stride):
            rows.append(i), cols.append(j)
    return np.asarray(rows), np.asarray(cols)


def train_value_projection(plan, q, k, v, impl: str, steps: int,
                           lr: float = 0.05):
    """Recover a value projection W from attention outputs by SGD — every
    step's forward is the fused megakernel (for Pallas impls) and its
    backward the dispatched sparse duality kernels."""
    d = v.shape[-1]
    target = sparse_attention(plan, q, k, v, impl=impl)
    w = jnp.asarray(np.random.default_rng(1).standard_normal((d, d))
                    .astype(np.float32)) * 0.1

    def loss_fn(w_):
        out = sparse_attention(plan, q, k, v @ w_, impl=impl)
        return jnp.mean((out - target) ** 2)

    loss_grad = jax.jit(jax.value_and_grad(loss_fn))
    with sparse_dispatch.record_calls() as log:
        loss0, _ = loss_grad(w)
    if impl in ("pallas", "pallas_balanced", "pallas_tuned",
                "pallas_sharded"):
        n_fused = (log.count(("attention", "pallas_fused_attn"))
                   + log.count(("attention", "pallas_balanced"))
                   + log.count(("attention", "pallas_sharded")))
        assert n_fused >= 1, f"train step did not hit the fused kernel: {log}"
        n_bwd = sum(1 for op, i in log
                    if op in ("spmm", "sddmm")
                    and i in ("pallas_batched", "pallas_balanced",
                              "pallas_sharded"))
        print(f"train step traced {n_fused} fused-megakernel forward and "
              f"{n_bwd} batched duality-kernel backward dispatches")
    losses = [float(loss0)]
    for _ in range(steps):
        loss, gw = loss_grad(w)
        w = w - lr * gw
        losses.append(float(loss))
    final = float(loss_fn(w))
    assert np.isfinite(losses).all() and np.isfinite(final), losses
    assert final < losses[0], (losses, final)
    print(f"train: loss {losses[0]:.5f} -> {final:.5f} over {steps} "
          f"step(s) through impl={impl}  ✓")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--impl", default="blocked",
                    help="registry impl: blocked | pallas | "
                         "pallas_balanced | pallas_tuned | pallas_sharded")
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--heads", type=int, default=2)
    ap.add_argument("--steps", type=int, default=0,
                    help="run N training steps through the fused gradient "
                         "path after the parity checks")
    ap.add_argument("--mesh", default=None, metavar="DATA,MODEL",
                    help="device grid for --impl pallas_sharded, e.g. 4,2 "
                         "(sequence windows over 'data', heads over "
                         "'model'); force host devices on CPU via "
                         "XLA_FLAGS=--xla_force_host_platform_device_count=8")
    args = ap.parse_args()
    enable_compile_cache()

    mesh = None
    if args.mesh is not None:
        from repro.launch.mesh import mesh_from_arg

        mesh = mesh_from_arg(args.mesh)

    seq, d, heads = args.seq, 64, args.heads
    rows, cols = block_sparse_causal_pattern(seq)
    vals = np.ones_like(rows, np.float32)
    fmt = from_coo(rows, cols, vals, (seq, seq), vector_size=8)
    plan = ad_plan(fmt, impl=args.impl, n_example=d, mesh=mesh)
    density = len(rows) / seq ** 2
    print(f"pattern: {len(rows):,} nonzeros of {seq * seq:,} "
          f"({density:.1%} dense) — compute saved vs full: {1 - density:.1%}")

    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((heads, seq, d)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((heads, seq, d)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((heads, seq, d)).astype(np.float32))

    with sparse_dispatch.record_calls() as log:
        out_sparse = sparse_attention(plan, q, k, v, impl=args.impl)
    if args.impl in ("pallas", "pallas_balanced", "pallas_tuned",
                     "pallas_sharded"):
        # a tuned/balanced/sharded plan may route onto the block-parallel
        # or multi-device megakernel
        assert len(log) == 1 and log[0][0] == "attention" and \
            log[0][1] in ("pallas_fused_attn", "pallas_balanced",
                          "pallas_sharded"), log
        print(f"forward: ONE fused megakernel launch for {heads} heads  ✓")

    # dense oracle: same mask through standard attention, per head
    mask = np.zeros((seq, seq), bool)
    mask[rows, cols] = True

    def dense_head(qh, kh, vh):
        scores = (qh @ kh.T) / np.sqrt(d)
        scores = jnp.where(jnp.asarray(mask), scores, -1e30)
        return jax.nn.softmax(scores, axis=-1) @ vh

    out_dense = jnp.stack([dense_head(q[h], k[h], v[h])
                           for h in range(heads)])

    err = float(jnp.max(jnp.abs(out_sparse - out_dense)))
    print(f"max |sparse - dense masked| = {err:.2e}")
    np.testing.assert_allclose(np.asarray(out_sparse), np.asarray(out_dense),
                               rtol=2e-4, atol=2e-4)
    print("block-sparse attention == dense masked attention  ✓")

    # gradient check: the layer trains (backward = dispatched sparse ops)
    gq = jax.grad(lambda qq: sparse_attention(plan, qq, k, v,
                                              impl=args.impl).sum())(q)
    gq_dense = jax.grad(lambda qq: jnp.stack(
        [dense_head(qq[h], k[h], v[h]) for h in range(heads)]).sum())(q)
    gerr = float(jnp.max(jnp.abs(gq - gq_dense)))
    print(f"max |∂sparse/∂Q - ∂dense/∂Q| = {gerr:.2e}")
    np.testing.assert_allclose(np.asarray(gq), np.asarray(gq_dense),
                               rtol=2e-3, atol=2e-3)
    print("sparse-attention gradients == dense masked gradients  ✓")

    if args.steps:
        train_value_projection(plan, q, k, v, args.impl, args.steps)


if __name__ == "__main__":
    main()
