"""End-to-end GNN training on FlashSparse operators (paper §4.4).

Trains GCN (SpMM aggregation) and AGNN (SDDMM attention + sparse softmax +
SpMM) on a scaled paper graph, comparing the 8×1 and 16×1 pipelines and
f32 vs bf16 numerics — the offline counterpart of paper Fig. 16 / Table 8.

The adjacency is wrapped in an autodiff plan (``ad_plan``), so ``--impl``
selects any differentiable registry implementation — ``blocked`` (XLA),
``pallas`` or ``pallas_balanced`` — and the backward pass runs the dispatched
transpose-SpMM/SDDMM duality (DESIGN.md §9) through the same kernels.

  PYTHONPATH=src python examples/gnn_train.py [--graph GitHub] [--epochs 60]
  PYTHONPATH=src python examples/gnn_train.py --steps 2 --impl pallas \
      --scale 0.002
      # smoke: one config at --scale, asserts finite decreasing loss
  XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \
      python examples/gnn_train.py --steps 2 --impl pallas_sharded --mesh 4,2 \
      --scale 0.002
      # multi-device: row segments over the 4-way "data" axis, feature
      # columns over the 2-way "model" axis (DESIGN.md §12)
"""

import argparse
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import from_coo
from repro.core.autodiff import ad_plan
from repro.launch.cache import enable_compile_cache
from repro.models.gnn import GNNConfig, init_agnn, init_gcn, make_train_step
from repro.sparse.graphs import make_dataset


def make_task(g, seed=0, num_classes=8, in_dim=64):
    rng = np.random.default_rng(seed)
    labels_np = rng.integers(0, num_classes, size=g.num_nodes)
    centers = rng.standard_normal((num_classes, in_dim)).astype(np.float32)
    x_np = centers[labels_np] + 0.5 * rng.standard_normal(
        (g.num_nodes, in_dim)).astype(np.float32)
    train_mask = jnp.asarray((rng.random(g.num_nodes) < 0.7), jnp.float32)
    labels = jnp.asarray(labels_np.astype(np.int32))
    return x_np, labels, train_mask


def train_one(g, x_np, labels, train_mask, *, model, v, dtype, impl,
              epochs, num_classes=8, in_dim=64, lr=5e-3, mesh=None):
    cfg = GNNConfig(model=model, in_dim=in_dim,
                    hidden_dim=128 if model == "gcn" else 32,
                    num_classes=num_classes,
                    num_layers=3 if model == "gcn" else 2,
                    impl=impl, dtype=dtype)
    fmt = from_coo(g.rows, g.cols, g.vals, (g.num_nodes, g.num_nodes),
                   vector_size=v, dtype=dtype)
    adj = ad_plan(fmt, impl=impl, mesh=mesh)
    x = jnp.asarray(x_np, dtype)
    init = init_gcn if model == "gcn" else init_agnn
    params = init(jax.random.key(0), cfg)
    mom = jax.tree.map(jnp.zeros_like, params)
    step = make_train_step(cfg, lr=lr)

    losses = []
    t0 = time.time()
    for _ in range(epochs):
        params, mom, loss, acc = step(params, mom, adj, x, labels, train_mask)
        losses.append(loss)  # device arrays: keep the loop async-dispatched
    jax.block_until_ready(loss)
    dt = (time.time() - t0) / max(epochs, 1) * 1e3
    return [float(l) for l in losses], float(acc), dt


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--graph", default="GitHub")
    ap.add_argument("--scale", type=float, default=0.02)
    ap.add_argument("--epochs", type=int, default=60)
    ap.add_argument("--model", default="both", choices=["gcn", "agnn", "both"])
    ap.add_argument("--impl", default="blocked",
                    help="registry impl: blocked | pallas | pallas_balanced "
                         "| pallas_sharded")
    ap.add_argument("--steps", type=int, default=None,
                    help="smoke mode: run STEPS steps of one config at "
                         "--scale and assert a finite loss decrease")
    ap.add_argument("--mesh", default=None, metavar="DATA,MODEL",
                    help="device grid for --impl pallas_sharded, e.g. 4,2 "
                         "(row segments over 'data', heads/columns over "
                         "'model'); on CPU force host devices first: "
                         "XLA_FLAGS=--xla_force_host_platform_device_count=8")
    ap.add_argument("--dtype", default="f32", choices=["f32", "bf16"],
                    help="activation/weight dtype of the smoke config "
                         "(--steps): bf16 runs the mixed-precision kernel "
                         "path end to end, fp32 masters in the optimizer "
                         "(DESIGN.md §13)")
    args = ap.parse_args()
    enable_compile_cache()

    mesh = None
    if args.mesh is not None:
        from repro.launch.mesh import mesh_from_arg

        mesh = mesh_from_arg(args.mesh)

    if args.steps is not None:
        # Smoke: one (model, V=8) config at --scale, hard asserts.
        model = args.model if args.model != "both" else "gcn"
        dtype = jnp.float32 if args.dtype == "f32" else jnp.bfloat16
        g = make_dataset(args.graph, scale=args.scale)
        x_np, labels, train_mask = make_task(g)
        losses, acc, dt = train_one(
            g, x_np, labels, train_mask, model=model, v=8,
            dtype=dtype, impl=args.impl, epochs=args.steps, lr=5e-2,
            mesh=mesh)
        print(f"smoke {model} impl={args.impl} dtype={args.dtype}: "
              f"loss {losses[0]:.4f} -> "
              f"{losses[-1]:.4f} ({dt:.1f} ms/step)")
        assert all(np.isfinite(l) for l in losses), f"non-finite loss: {losses}"
        assert losses[-1] < losses[0], \
            f"loss did not decrease under impl={args.impl}: {losses}"
        print("OK: finite decreasing loss through the "
              f"{args.impl} gradient path")
        return

    g = make_dataset(args.graph, scale=args.scale)
    print(f"{args.graph} (scale {args.scale}): {g.num_nodes:,} nodes, "
          f"{g.num_edges:,} edges")
    x_np, labels, train_mask = make_task(g)

    models = ["gcn", "agnn"] if args.model == "both" else [args.model]
    for model in models:
        for v, dtype_name in [(8, "f32"), (16, "f32"), (8, "bf16")]:
            dtype = jnp.float32 if dtype_name == "f32" else jnp.bfloat16
            losses, acc, dt = train_one(
                g, x_np, labels, train_mask, model=model, v=v, dtype=dtype,
                impl=args.impl, epochs=args.epochs, mesh=mesh)
            print(f"  {model:4s} V={v:2d} {dtype_name:4s} impl={args.impl}: "
                  f"{dt:7.1f} ms/epoch | loss {losses[-1]:.4f} | "
                  f"train acc {acc:.3f}")


if __name__ == "__main__":
    main()
