"""LM pretraining with checkpoint/restart — the fault-tolerance demo.

Trains a reduced config for N steps with async checkpointing, then
SIMULATES A NODE FAILURE by dropping all state, and resumes from the
newest complete checkpoint.  Asserts the resumed run continues seamlessly
(loss keeps decreasing, step counter matches, data pipeline regenerates
the exact batch stream — no iterator hand-off needed).

  PYTHONPATH=src python examples/lm_pretrain.py [--arch qwen3-0.6b]
"""

import argparse
import os
import shutil
import tempfile

import numpy as np

import jax
import jax.numpy as jnp

from repro.configs import get_reduced
from repro.data.synthetic import SyntheticLMData
from repro.launch.cache import enable_compile_cache
from repro.train.checkpoint import CheckpointManager
from repro.train.optimizer import AdamWConfig
from repro.train.train_step import (
    TrainStepConfig, init_train_state, make_train_step)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--fail-at", type=int, default=35)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-every", type=int, default=10)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_reduced(args.arch)
    ts = TrainStepConfig(opt=AdamWConfig(lr=1e-3, warmup_steps=10,
                                         decay_steps=args.steps))
    data = SyntheticLMData(cfg, args.batch, args.seq, seed=0)
    step_fn = jax.jit(make_train_step(cfg, ts), donate_argnums=0)
    ckpt_dir = tempfile.mkdtemp(prefix="repro_ckpt_")
    mgr = CheckpointManager(ckpt_dir, keep_n=2)

    def run(state, start, stop, tag):
        losses = []
        for step in range(start, stop):
            batch = jax.tree.map(jnp.asarray, data.batch(step))
            state, metrics = step_fn(state, batch)
            losses.append(float(metrics["loss"]))
            if (step + 1) % args.ckpt_every == 0:
                mgr.save_async(state, step + 1)
            if (step + 1) % 10 == 0:
                print(f"  [{tag}] step {step + 1:3d} loss {losses[-1]:.4f}")
        mgr.wait()
        return state, losses

    print(f"phase 1: train to step {args.fail_at}, checkpoints every "
          f"{args.ckpt_every} → {ckpt_dir}")
    state = init_train_state(jax.random.key(0), cfg, ts)
    state, losses1 = run(state, 0, args.fail_at, "run1")

    print("\n>>> simulated node failure: process state dropped <<<\n")
    del state

    latest = mgr.latest_step()
    print(f"phase 2: restart — newest complete checkpoint is step {latest}")
    template = jax.eval_shape(
        lambda: init_train_state(jax.random.key(0), cfg, ts))
    state, resumed_step = mgr.restore(template)
    state = jax.tree.map(jnp.asarray, state)
    assert resumed_step == latest
    assert int(state["step"]) == latest, (int(state["step"]), latest)

    state, losses2 = run(state, resumed_step, args.steps, "run2")

    early = np.mean(losses1[:5])
    late = np.mean(losses2[-5:])
    print(f"\nloss {early:.4f} (start) → {late:.4f} (end), "
          f"resume step {resumed_step}, final step {int(state['step'])}")
    assert late < early, "loss did not decrease across the restart"
    assert int(state["step"]) == args.steps
    print("fault-tolerance demo: PASS (checkpoint → crash → resume → "
          "loss continuity)")
    shutil.rmtree(ckpt_dir, ignore_errors=True)


if __name__ == "__main__":
    main()
