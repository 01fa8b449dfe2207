"""One run of one benchmark cell: set-up, the measured window, the check
against the plain reference, and the result line.

Everything that belongs to one configuration, traffic mix, cell or
per-layer metric is found by its name in ``BENCHMARK.json``:

  bench/configs/<config>.json     sizes, precision and optimizer as run
  bench/models/<model>.py         program step, plain forward, work counts
  bench/traffic/<traffic>.json    the graph and the train-mask share
  bench/limits/<workload>.json    the checked steps and each number's limit
  bench/metrics/<metric>.py       ``read(ctx)``: one per-layer metric

A new cell, configuration, traffic or metric is new files and entries;
nothing here names one.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import pathlib
import shutil
import sys
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import graphs, peaks, reference, trace as trace_mod

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
WORK_DIR = ROOT / "experiments" / "bench"
JAX_CACHE = WORK_DIR / "jax_cache"
MIB = 1 << 20


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(path: pathlib.Path):
    """Import a file of the benchmark by its path (names may hold dots)."""
    name = "bench_" + "_".join(path.relative_to(BENCH).with_suffix("").parts)
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


@dataclasses.dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with its files read."""

    name: str
    config: dict
    traffic: dict
    limits: dict
    chips: int
    model: object                      # bench/models/<model>.py
    end_to_end: list                   # metric entries that apply
    per_layer: list


def applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(name: str, bench_file=ROOT / "BENCHMARK.json") -> Cell:
    spec = load_json(bench_file)
    work = {w["name"]: w for w in spec["workloads"]}
    if name not in work:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(work)}")
    w = work[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = load_json(ROOT / conf["file"])
    return Cell(
        name=name, config=config,
        traffic=load_json(BENCH / "traffic" / f"{w['traffic']}.json"),
        limits=load_json(BENCH / "limits" / f"{name}.json"),
        chips=w["chips"],
        model=load_module(BENCH / "models" / f"{config['model']}.py"),
        end_to_end=[m for m in spec["end_to_end"] if applies(m, name)],
        per_layer=[m for m in spec["per_layer"] if applies(m, name)])


def enable_compile_cache(path=JAX_CACHE) -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout;
    every program is kept, so a second run compiles nothing."""
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def seed_key(seed: int):
    """A PRNG key from any non-negative seed, above 32 bits too."""
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


class Spans:
    """Host-clock spans of the benchmark's own phases, in seconds."""

    def __init__(self):
        self.s = {}

    @contextlib.contextmanager
    def time(self, name):
        t = time.perf_counter()
        try:
            yield
        finally:
            self.s[name] = time.perf_counter() - t


@dataclasses.dataclass
class Graph:
    """The cell's graph as the program and the reference each take it."""

    n: int
    rows: np.ndarray
    cols: np.ndarray
    vals: np.ndarray
    plan: object                 # the program's ADPlan

    @property
    def counts(self):
        return {"nnz": int(np.unique(self.rows.astype(np.int64) * self.n
                                     + self.cols).size),
                "m": self.n, "n": self.n}


def build_graph(cell: Cell, spans: Spans, cache_dir=graphs.CACHE_DIR) -> Graph:
    from repro.core import from_coo
    from repro.core.autodiff import ad_plan

    with spans.time("graph_load"):
        n, rows, cols, vals = graphs.load(cell.traffic, cache_dir)
    with spans.time("format"):
        fmt = from_coo(rows, cols, vals, (n, n),
                       vector_size=cell.config["vector_size"])
    with spans.time("plan"):
        plan = ad_plan(fmt, impl=cell.config["impl"],
                       n_example=cell.config["hidden_dim"])
        jax.block_until_ready(plan)
    return Graph(n, rows, cols, vals, plan)


def make_inputs(cell: Cell, n: int, seed: int):
    """Weights, features, labels and train mask, on the device, in one
    jitted call from the seed."""
    cfg, share = cell.config, cell.traffic["train_share"]

    @jax.jit
    def make(key):
        k_p, k_x, k_y, k_m = jax.random.split(key, 4)
        params = cell.model.init(k_p, cfg)
        x = jax.random.normal(k_x, (n, cfg["in_dim"]), jnp.float32)
        labels = jax.random.randint(k_y, (n,), 0, cfg["num_classes"])
        mask = (jax.random.uniform(k_m, (n,)) < share).astype(jnp.float32)
        return params, x, labels, mask

    return make(seed_key(seed))


@dataclasses.dataclass
class Trained:
    """What the program's first steps produced, read back to the host,
    and what the window measured."""

    params0: object
    losses: np.ndarray           # the checked steps' losses
    grad0: object                # momentum after step 1 = first gradient
    params: object               # parameters after the checked steps
    dispatch: list               # (op, impl) of every dispatched sparse op
    step_s: float                # one warm step, host clock (sets the window)
    window_s: float = 0.0
    window_steps: int = 0
    window_losses: Optional[np.ndarray] = None
    peak_bytes: int = 0
    setup_end: float = 0.0       # host clock at the end of set-up
    data: tuple = ()             # features, labels, mask on the host
    xplane: Optional[str] = None


def to_host(tree):
    return jax.tree.map(lambda a: np.asarray(a, np.float64), tree)


def train(cell: Cell, graph: Graph, seed: int, seconds: float, *,
          interpret: bool, spans: Spans, trace_dir=None,
          step_factory: Optional[Callable] = None) -> Trained:
    """Set-up's first steps, then the measured window, on one step object.

    ``step_factory(cfg, interpret)`` builds the train step; the cell's
    model builds the program's.  The first ``check_steps`` steps go
    through that step's own call, are kept for the check, and their
    state is handed to the window."""
    from repro.core.dispatch import record_calls

    cfg = cell.config
    k = cell.limits["check_steps"]
    params, x, labels, mask = make_inputs(cell, graph.n, seed)
    params0 = to_host(params)
    mom = jax.tree.map(jnp.zeros_like, params)
    step = (step_factory or cell.model.program_step)(cfg, interpret)
    losses = []
    with spans.time("first_step"), record_calls() as log:
        params, mom, loss, _ = step(params, mom, graph.plan, x, labels, mask)
        jax.block_until_ready((params, mom, loss))
    losses.append(loss)
    grad0 = mom
    t = time.perf_counter()
    for _ in range(k - 1):
        params, mom, loss, _ = step(params, mom, graph.plan, x, labels, mask)
        losses.append(loss)
    jax.block_until_ready((params, mom, loss))
    warm = (time.perf_counter() - t) / max(k - 1, 1)
    out = Trained(params0=params0, losses=None, grad0=None, params=None,
                  dispatch=list(log), step_s=warm)
    out.losses = np.array([float(v) for v in losses], np.float64)
    out.grad0, out.params = to_host(grad0), to_host(params)

    out.setup_end = time.perf_counter()
    steps = max(1, round(seconds / warm)) if seconds > 0 else 0
    window_losses = []
    prof = None
    if trace_dir is not None:
        shutil.rmtree(trace_dir, ignore_errors=True)
        prof = jax.profiler.ProfileOptions()
        prof.python_tracer_level = 0
        prof.host_tracer_level = 1
        prof.enable_hlo_proto = False
        jax.profiler.start_trace(str(trace_dir), profiler_options=prof)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                for _ in range(steps):
                    params, mom, loss, _ = step(params, mom, graph.plan, x,
                                                labels, mask)
                    window_losses.append(loss)
            with jax.profiler.TraceAnnotation("bench.wait"):
                jax.block_until_ready((params, mom, window_losses))
            t1 = time.perf_counter()
    finally:
        if prof is not None:
            jax.profiler.stop_trace()
    out.window_s, out.window_steps = t1 - t0, steps
    out.window_losses = np.array([float(v) for v in window_losses])
    stats = jax.devices()[0].memory_stats() or {}
    out.peak_bytes = int(stats.get("peak_bytes_in_use", 0))
    out.data = tuple(np.asarray(a) for a in (x, labels, mask))
    if trace_dir is not None:
        out.xplane = trace_mod.find_xplane(trace_dir)
    return out


# ---------------------------------------------------------------------------
# The check: the reference follows the program's first steps in float64
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class RefRun:
    losses: np.ndarray
    grad0: object
    params: object


def reference_run(cell: Cell, graph: Graph, params0, x, labels, mask,
                  steps: int) -> RefRun:
    """The plain reference's first ``steps`` steps from ``params0``, in
    float64 on the host CPU."""
    cfg = cell.config
    edges = reference.make_edges(graph.rows, graph.cols, graph.vals, graph.n)
    step = reference.make_train(cell.model.forward, reference.exact,
                                cfg["lr"], cfg["momentum"])
    return RefRun(*reference.on_host_f64(
        lambda *a: reference.run_steps(step, *a, steps),
        params0, edges, x, labels, mask))


def leaf_norms(tree):
    return np.array([float(np.linalg.norm(np.ravel(a)))
                     for a in jax.tree.leaves(tree)])


def worst_gap(got, want, keep=None) -> float:
    """The worst leaf's gap between the two sides' norms, over the
    reference's norm of that leaf or of the median leaf, whichever is
    larger.  ``keep`` masks the leaves that count."""
    g, w = leaf_norms(got), leaf_norms(want)
    keep = np.ones(w.shape, bool) if keep is None else keep
    floor = np.median(w[keep])
    return float(np.max(np.abs(g - w)[keep] / np.maximum(w[keep], floor)))


def rel_diff(got, want) -> float:
    """The norm of the difference over the reference's norm."""
    got, want = np.ravel(got), np.ravel(want)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def numbers(trained: Trained, ref: RefRun, out_leaf) -> dict:
    """The numbers compared with their limits.

    ``loss``: the worst step's relative loss gap.  ``grad``: the first
    gradient, as the optimizer's state holds it after step 1.
    ``change``: the parameters' change over the checked steps, leaving
    out leaves whose reference gradient is under a thousandth of the
    median leaf's (nought to rounding).  ``out_grad``: the first
    gradient of the output layer (``out_leaf``), element by element; it
    holds no activation's derivative, so it is free of the units within
    rounding of zero that make the other gradients' gaps swing, and it
    reads the precision of the forward and of the last contraction."""
    g_ref = leaf_norms(ref.grad0)
    keep = g_ref >= 1e-3 * np.median(g_ref)
    delta = lambda p: jax.tree.map(np.subtract, p, trained.params0)
    return {
        "loss": float(np.max(np.abs(trained.losses - ref.losses)
                             / np.abs(ref.losses))),
        "grad": worst_gap(trained.grad0, ref.grad0),
        "change": worst_gap(delta(trained.params), delta(ref.params), keep),
        "out_grad": rel_diff(out_leaf(trained.grad0), out_leaf(ref.grad0)),
    }


def dispatch_ok(log) -> bool:
    return bool(log) and all(impl.startswith("pallas")
                             and not impl.startswith("fallback:")
                             for _, impl in log)


# ---------------------------------------------------------------------------
# Per-layer metrics
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Context:
    """What a per-layer metric's reader may read."""

    cell: Cell
    spans: dict              # host span -> seconds
    counts: dict             # the model's work per step (bench/models)
    peak: peaks.Peak
    steps: int               # steps in the traced window
    window: tuple            # (start, end) ns of the traced window
    ops: list                # device 0's ops inside the window
    trace: object            # the whole reduced trace


def read_metrics(cell: Cell, ctx: Context) -> dict:
    out = {}
    for m in cell.per_layer:
        value = load_module(BENCH / "metrics" / f"{m['name']}.py").read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def breakdown(ctx: Context) -> dict:
    lo, hi = ctx.window
    ops = sorted(trace_mod.per_op(ctx.ops).items(), key=lambda kv: -kv[1])
    gaps = sorted(trace_mod.idle_gaps(ctx.ops, lo, hi),
                  key=lambda g: g[0] - g[1])
    return {
        "device_ops": [[name, ns / 1e9] for name, ns in ops[:10]],
        "idle_gaps": [[trace_mod.innermost(ctx.trace.spans, (a + b) / 2),
                       (b - a) / 1e9]
                      for a, b in gaps[:10]],
    }


# ---------------------------------------------------------------------------
# A whole run
# ---------------------------------------------------------------------------


def run(cell: Cell, seed: int, seconds: float, traced: bool, *,
        t_start: float, interpret: bool = False,
        graph_cache=graphs.CACHE_DIR) -> tuple[dict, list]:
    """Set-up, window and check of one cell: ``(result, check lines)``."""
    dev = jax.devices()[0]
    jax.config.update("jax_default_matmul_precision",
                      cell.config["matmul_precision"])
    spans = Spans()
    graph = build_graph(cell, spans, graph_cache)
    trace_dir = WORK_DIR / "trace" / cell.name if traced else None
    trained = train(cell, graph, seed, seconds, interpret=interpret,
                    spans=spans, trace_dir=trace_dir)
    setup_s = trained.setup_end - t_start

    # The check, once the window has closed and the peak has been read.
    with spans.time("reference"):
        ref = reference_run(cell, graph, trained.params0, *trained.data,
                            cell.limits["check_steps"])
    nums = numbers(trained, ref, cell.model.out_leaf)
    limits = cell.limits["limits"]
    failed = int(np.sum(~np.isfinite(trained.window_losses)))
    ok = dispatch_ok(trained.dispatch)
    correct = ok and failed == 0 and all(
        nums[key] <= limits[key] for key in limits)
    check = {key: {"value": nums[key], "limit": limits[key]}
             for key in limits}
    lines = ["phases (s): " + ", ".join(f"{k} {v:.3f}"
                                        for k, v in spans.s.items())
             + f", warm step {trained.step_s:.3f}, window "
             f"{trained.window_s:.3f} over {trained.window_steps} steps"]
    lines.append(f"dispatch {'pallas' if ok else 'NOT pallas'}: "
                 f"{sorted(set(trained.dispatch))}")
    lines += [f"{key} {nums[key]:.6e} not compared (no fault reads far "
              f"enough above it; bench/limits/{cell.name}.json)"
              for key in nums if key not in limits]
    lines += [f"{key} {nums[key]:.6e} limit {limits[key]:.6e}"
              for key in limits]

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": trained.peak_bytes}
    result = {"correct": bool(correct), "attempted": trained.window_steps,
              "failed": failed}
    if traced:
        tr = trace_mod.load(trained.xplane)
        lo, hi = trace_mod.window(tr)
        ops = trace_mod.clip(tr.ops.get(0, []), lo, hi)
        ctx = Context(cell=cell, spans=spans.s,
                      counts=cell.model.counts(graph.counts, cell.config),
                      peak=peaks.peak(dev.device_kind),
                      steps=trained.window_steps, window=(lo, hi), ops=ops,
                      trace=tr)
        busy = np.mean([trace_mod.busy(tr.ops.get(d, []), lo, hi)
                        for d in range(cell.chips)])
        device.update(busy_s=busy / 1e9, window_s=(hi - lo) / 1e9)
        result["metrics"] = read_metrics(cell, ctx)
        result["breakdown"] = breakdown(ctx)
    else:
        values = {"step_ms": 1e3 * trained.window_s
                  / max(trained.window_steps, 1),
                  "peak_hbm_mib": trained.peak_bytes / MIB,
                  "setup_s": setup_s}
        result["metrics"] = {m["name"]: {"value": values[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
    result["device"] = device
    result["check"] = check
    return result, lines
