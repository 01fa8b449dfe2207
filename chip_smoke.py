"""On-chip smoke run: GCN and AGNN training through the compiled Pallas
kernels on a real-size graph.

    python chip_smoke.py                # one chip: GCN + AGNN, 3 steps each
    python chip_smoke.py --four-chips   # the pallas_sharded GCN step on 4
                                        # chips against the one-chip step

The main path is the one users train with: ``from_coo`` → ``ad_plan`` →
``models.gnn`` → ``make_gnn_train_step`` with ``impl="pallas"`` and
``interpret=False``, on the Table-4 ``Amazon`` replica at scale 1.0, at the
paper's widths (GCN: 5 layers, hidden 128, 128 input features; AGNN:
hidden 32).  Weights, features and labels are random, made from
``--seed``.  The configuration is fixed: the module constants below are
what the last line certifies.  The run fails (non-zero exit) unless

  * JAX's default backend is a TPU;
  * no dispatch fell back to another impl (``record_calls`` log);
  * every loss is finite;
  * the first layer's aggregation and the step-0 loss agree, to fp32
    tolerance, with a plain ``jax.numpy`` reference (``segment_sum`` over
    the COO edges) written here, independent of the kernels;
  * the step-0 gradient (the backward kernels; the momentum buffer after
    one step is exactly that gradient) agrees with the same reference run
    in float64 on the host CPU.  Each gradient leaf is held to the bound
    on its own scale, so a small leaf (an early layer, an attention beta)
    cannot hide behind a large one.

It prints phase times (host wall clock, compilation included where noted)
and, as its last line, ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

# The gradient reference runs in float64 on the host's CPU backend, which
# a platform list without "cpu" would leave out.
_PLATFORMS = os.environ.get("JAX_PLATFORMS", "")
if _PLATFORMS and "cpu" not in _PLATFORMS.split(","):
    os.environ["JAX_PLATFORMS"] = _PLATFORMS + ",cpu"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

# Forward values (aggregation, loss) against the fp32 reference: both
# sides run fp32 with fp32-accurate matmuls and sum at most ~20 edges per
# row, so they differ by summation order only.
RTOL = 1e-4
# Step-0 gradients against the float64 reference, per leaf.  The fp32
# reference cannot serve here: the generated graph's hub node has 403,391
# in-edges, and the deeper AGNN betas are sums over 3.4M edges that
# cancel to 1e-6..1e-7 of the largest gradient, so fp32 sums of either
# side err by up to 1.2e-2 of such a leaf.  Against float64 the kernels'
# worst leaf was 3.3e-5 (GCN) and 3.3e-4 (AGNN beta[2]) at Amazon scale
# 1.0 on a v5e; each bound is about 3x that.
GRAD_RTOL = {"gcn": 1e-4, "agnn": 1e-3}
LR = 1e-2

# The configuration: Table 4's Amazon at its published size, the paper's
# widths.
GRAPH, SCALE = "Amazon", 1.0
STEPS = 3
IN_DIM, HIDDEN_GCN, HIDDEN_AGNN, NUM_CLASSES = 128, 128, 32, 16


def _log(msg: str) -> None:
    print(msg, flush=True)


class Phases:
    """Wall-clock phase timer; every phase is printed as it ends."""

    def __init__(self):
        self.t = time.perf_counter()

    def done(self, name: str) -> float:
        now = time.perf_counter()
        dt, self.t = now - self.t, now
        _log(f"phase {name}: {dt:.3f} s")
        return dt


# ---------------------------------------------------------------------------
# Plain jax.numpy reference (no repro.kernels, no dispatch)
# ---------------------------------------------------------------------------


def make_reference(rows, cols, vals, n):
    """Reference GCN/AGNN losses and aggregation over de-duplicated COO
    edges.  Returns ``(edges, fns)``; every fn takes ``edges`` first."""
    key = rows.astype(np.int64) * n + cols
    uniq, inv = np.unique(key, return_inverse=True)
    vals = np.bincount(inv, weights=vals).astype(np.float32)  # sum duplicates
    edges = tuple(jax.device_put(a) for a in (
        (uniq // n).astype(np.int32), (uniq % n).astype(np.int32), vals))

    def edge_sum(per_edge, r):
        return jax.ops.segment_sum(per_edge, r, num_segments=n)

    def aggregate(edges, h):
        r, c, v = edges
        return edge_sum(v[:, None] * h[c], r)

    @jax.checkpoint
    def attention(edges, h, beta):
        """softmax over each row's edges of beta * cos(h_r, h_c), applied
        to h.  Each edge's probability is one node of the graph, so its
        gradient terms cancel per edge, as in a softmax's own backward;
        the layer is recomputed in the backward rather than keep its
        per-edge gathers."""
        r, c, _ = edges
        hn = h / jnp.maximum(jnp.linalg.norm(h, axis=-1, keepdims=True), 1e-6)
        s = beta * jnp.sum(hn[r] * hn[c], axis=1)
        # the softmax does not depend on the shift: no gradient through it
        row_max = jax.lax.stop_gradient(
            jax.ops.segment_max(s, r, num_segments=n))
        e = jnp.exp(s - row_max[r])
        p = e / jnp.maximum(edge_sum(e, r), 1e-20)[r]
        return edge_sum(p[:, None] * h[c], r)

    def gcn(edges, params, x):
        h = x
        for i, w in enumerate(params["w"]):
            h = aggregate(edges, h) @ w
            if i < len(params["w"]) - 1:
                h = jax.nn.relu(h)
        return h

    def agnn(edges, params, x):
        h = jax.nn.relu(x @ params["w_in"])
        for beta in params["beta"]:
            h = attention(edges, h, beta)
        return h @ params["w_out"]

    def loss(logits, labels, mask):
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1)

    def loss_and_grad(forward):
        return jax.jit(jax.value_and_grad(
            lambda p, e, x, y, m: loss(forward(e, p, x), y, m)))

    return edges, {"aggregate": jax.jit(aggregate),
                   "gcn": loss_and_grad(gcn), "agnn": loss_and_grad(agnn)}


def rel_err(got, want) -> tuple[float, str]:
    """The worst leaf of two pytrees: ``max |got - want| / max |want|``
    taken leaf by leaf, and that leaf's path."""
    worst = (0.0, "")
    for (path, w), g in zip(jax.tree_util.tree_leaves_with_path(want),
                            jax.tree.leaves(got)):
        w, g = (np.asarray(a, np.float64) for a in (w, g))
        err = float(np.max(np.abs(g - w), initial=0.0)
                    / max(np.max(np.abs(w), initial=0.0), 1e-300))
        if err >= worst[0]:
            worst = (err, jax.tree_util.keystr(path))
    return worst


def on_host_f64(fn, *args):
    """``fn(*args)`` in float64 on the host CPU: floating leaves are
    widened (their values unchanged), integer leaves kept."""
    cpu = jax.devices("cpu")[0]

    def widen(a):
        a = np.asarray(a)
        return jax.device_put(a.astype(np.float64) if np.issubdtype(
            a.dtype, np.floating) else a, cpu)

    with jax.enable_x64(True):
        return fn(*jax.tree.map(widen, args))


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke FAILED: {msg}")


def check_no_fallback(log, where: str) -> None:
    check(len(log) > 0, f"{where}: no sparse op was dispatched")
    bad = [rec for rec in log if rec[1].startswith("fallback:")
           or not rec[1].startswith("pallas")]
    check(not bad, f"{where}: dispatch left the Pallas kernels: {bad}")


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------


def build_task(seed, phases):
    from repro.core import from_coo
    from repro.sparse.graphs import make_dataset

    g = make_dataset(GRAPH, scale=SCALE, seed=seed)
    n = g.num_nodes
    fmt = from_coo(g.rows, g.cols, g.vals, (n, n), vector_size=8)
    _log(f"graph {GRAPH} scale {SCALE}: {n} nodes, "
         f"{g.num_edges} edges, {fmt.nnzv} nonzero vectors")
    phases.done("host format build")
    kx, ky, km = jax.random.split(jax.random.key(seed), 3)
    x = jax.random.normal(kx, (n, IN_DIM), jnp.float32)
    labels = jax.random.randint(ky, (n,), 0, NUM_CLASSES)
    train_mask = (jax.random.uniform(km, (n,)) < 0.7).astype(jnp.float32)
    return g, fmt, x, labels, train_mask


def train(cfg, adj, x, labels, train_mask, steps, seed, phases, tag):
    """``steps`` train steps; returns (params at step 0, their gradient,
    losses).  The momentum buffer starts at zero, so after the first step
    it holds exactly the step-0 gradient."""
    from repro.core.dispatch import record_calls
    from repro.models.gnn import init_agnn, init_gcn, make_train_step

    init = init_gcn if cfg.model == "gcn" else init_agnn
    params0 = init(jax.random.key(seed + 1), cfg)
    params = params0
    mom = jax.tree.map(jnp.zeros_like, params)
    step = make_train_step(cfg, lr=LR)
    losses = []
    with record_calls() as log:
        params, mom, loss, _ = step(params, mom, adj, x, labels, train_mask)
        losses.append(float(loss))
    grad0 = mom
    phases.done(f"{tag} compile + step 1")
    check_no_fallback(log, tag)
    for _ in range(steps - 1):
        params, mom, loss, _ = step(params, mom, adj, x, labels, train_mask)
        losses.append(float(loss))
    if steps > 1:
        phases.done(f"{tag} steps 2-{steps}")
    _log(f"{tag} losses: {losses}")
    check(all(np.isfinite(losses)), f"{tag}: non-finite loss {losses}")
    return params0, grad0, losses


def check_close(what: str, got, want, bound: float) -> None:
    err, leaf = rel_err(got, want)
    _log(f"{what}: relative error {err:.3e}"
         + (f" (worst leaf {leaf})" if leaf else ""))
    check(err <= bound, f"{what}: error {err:.3e} > {bound}")


def one_chip(seed, phases) -> None:
    from repro.core import spmm
    from repro.core.autodiff import ad_plan
    from repro.core.dispatch import record_calls
    from repro.models.gnn import GNNConfig

    g, fmt, x, labels, train_mask = build_task(seed, phases)
    plan = ad_plan(fmt, impl="pallas", n_example=HIDDEN_GCN)
    phases.done("plan (transpose, blocking, value permutation)")

    edges, ref = make_reference(g.rows, g.cols, g.vals, g.num_nodes)
    with record_calls() as log:
        agg = spmm(plan.fwd, x, impl="pallas", interpret=False, strict=True)
        agg.block_until_ready()
    check_no_fallback(log, "aggregation")
    check_close("first-layer aggregation vs reference", agg,
                ref["aggregate"](edges, x), RTOL)
    phases.done("aggregation check (compile + run + reference)")

    for model, hidden in (("gcn", HIDDEN_GCN), ("agnn", HIDDEN_AGNN)):
        cfg = GNNConfig(model=model, in_dim=IN_DIM, hidden_dim=hidden,
                        num_classes=NUM_CLASSES, impl="pallas",
                        interpret=False)
        params0, grad0, losses = train(cfg, plan, x, labels, train_mask,
                                       STEPS, seed, phases, model)
        want, _ = ref[model](params0, edges, x, labels, train_mask)
        want = float(want)
        err = abs(losses[0] - want) / max(1.0, abs(want))
        _log(f"{model} step-0 loss {losses[0]:.7f}, reference {want:.7f}, "
             f"relative error {err:.3e}")
        check(err <= RTOL, f"{model} step-0 loss error {err:.3e} > {RTOL}")
        _, grad = on_host_f64(ref[model], params0, edges, x, labels,
                              train_mask)
        check_close(f"{model} step-0 gradient vs float64 reference", grad0,
                    grad, GRAD_RTOL[model])
        phases.done(f"{model} reference loss (fp32) and gradient (float64)")
    log_peak_memory(jax.devices()[:1])


def four_chips(seed, phases) -> None:
    """The sharded GCN step on a 4x1 mesh against the one-chip step."""
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.core import spmm
    from repro.core.autodiff import ad_plan
    from repro.distributed.sharding import sparse_format_shardings
    from repro.launch.mesh import make_host_mesh
    from repro.models.gnn import GNNConfig

    check(len(jax.devices()) >= 4,
          f"--four-chips needs 4 devices, found {len(jax.devices())}")
    g, fmt, x, labels, train_mask = build_task(seed, phases)
    mesh = make_host_mesh(4, 1)
    plan1 = ad_plan(fmt, impl="pallas", n_example=HIDDEN_GCN)
    plan4 = ad_plan(fmt, impl="pallas_sharded", mesh=mesh,
                    n_example=HIDDEN_GCN)
    plan4 = jax.device_put(plan4, sparse_format_shardings(plan4, mesh))
    phases.done("plans (one chip, 4-way sharded)")

    # each device holds its own slice of the partition, not device 0 all
    part = plan4.fwd_part.seg_win
    shards = sorted(part.addressable_shards, key=lambda s: s.index[0].start)
    devices = {s.device for s in shards}
    check(len(devices) == 4, f"partition spans {len(devices)} devices")
    host = np.asarray(part)
    for i, s in enumerate(shards):
        check(np.array_equal(np.asarray(s.data)[0], host[i]),
              f"shard {i} on {s.device} is not partition row {i}")
    _log("partition shards: " + ", ".join(
        f"{s.device.id}:{s.data.shape}" for s in shards))

    # the one-chip runs keep the operands on device 0; the sharded ones
    # see them replicated over the mesh
    replicated = NamedSharding(mesh, PartitionSpec())
    x4, labels4, mask4 = (jax.device_put(a, replicated)
                          for a in (x, labels, train_mask))
    a1 = spmm(plan1.fwd, x, impl="pallas", interpret=False, strict=True)
    a4 = spmm(plan4.fwd, x4, impl="pallas_sharded", interpret=False,
              strict=True, mesh=mesh, part=plan4.fwd_part)
    check_close("aggregation 4 chips vs 1 chip", a4, a1, RTOL)
    phases.done("aggregation 1 chip and 4 chips")

    losses, grads = {}, {}
    for impl, plan, ops in (("pallas", plan1, (x, labels, train_mask)),
                            ("pallas_sharded", plan4, (x4, labels4, mask4))):
        cfg = GNNConfig(model="gcn", in_dim=IN_DIM, hidden_dim=HIDDEN_GCN,
                        num_classes=NUM_CLASSES, impl=impl, interpret=False)
        _, grads[impl], losses[impl] = train(cfg, plan, *ops, 1, seed,
                                             phases, f"gcn[{impl}]")
    err = abs(losses["pallas_sharded"][0] - losses["pallas"][0]) / max(
        1.0, abs(losses["pallas"][0]))
    _log(f"gcn step-0 loss 4 chips vs 1 chip: relative error {err:.3e}")
    check(err <= RTOL, f"sharded loss error {err:.3e} > {RTOL}")
    check_close("gcn step-0 gradient 4 chips vs 1 chip",
                grads["pallas_sharded"], grads["pallas"], GRAD_RTOL["gcn"])
    log_peak_memory(jax.devices()[:4])


def log_peak_memory(devices) -> None:
    for d in devices:
        stats = d.memory_stats() or {}
        _log(f"device {d.id}: peak bytes in use "
             f"{stats.get('peak_bytes_in_use', 'not reported')}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip pallas_sharded GCN step and "
                         "its one-chip comparison")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the graph, features, labels and weights")
    args = ap.parse_args(argv)

    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: needs a TPU, JAX's default backend is "
              f"{backend!r}", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "src"))
    from repro.launch.cache import enable_compile_cache

    _log(f"compile cache: {enable_compile_cache()}")
    # fp32 end to end: dense matmuls at full fp32 precision, like the
    # kernels' contractions, so the reference comparison is an fp32 one
    jax.config.update("jax_default_matmul_precision", "highest")
    dev = jax.devices()[0]
    _log(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}")

    phases = Phases()
    if args.four_chips:
        four_chips(args.seed, phases)
    else:
        one_chip(args.seed, phases)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
