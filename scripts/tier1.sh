#!/usr/bin/env bash
# Tier-1 verification: the gate every PR must keep green.
#
#   scripts/tier1.sh            # full suite + gradient-path smoke
#   scripts/tier1.sh tests/test_kernels.py   # pass-through pytest args
#
# Installs dev deps (hypothesis) when a network is available; offline, the
# property tests degrade to skips via tests/_hypothesis_compat.py.
# TIER1_SMOKE=0 skips the gnn_train gradient smoke (pytest-only runs).
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

if ! python -c "import hypothesis" >/dev/null 2>&1; then
  pip install -r requirements-dev.txt \
    || echo "warn: dev deps unavailable (offline?); property tests will skip"
fi

python -m pytest -x -q "$@"

# Hardened-runtime gate (DESIGN.md §15, full runs only): re-run the
# format/op/dispatch tests with ambient full validation on every
# constructor and dispatch (REPRO_CHECK=full must be behavior-preserving
# on healthy inputs), then smoke the fault-injection harness CLI in both
# strictness modes.
if [[ $# -eq 0 ]]; then
  REPRO_CHECK=full python -m pytest -x -q \
    tests/test_format.py tests/test_sparse_ops.py tests/test_dispatch.py \
    tests/test_validate.py
  python -m repro.testing.faults --op spmm --impl blocked --strict
  python -m repro.testing.faults --op spmm --impl pallas --interpret \
    --no-strict

  # Real-matrix conformance gate: the harness must catch a broken impl
  # (self-test), then the full registry — every (op, impl, precision) —
  # must match the dense oracle on a two-matrix vendored subset.  The
  # full 14-matrix sweep is the real-matrix-conformance CI job.
  python -m repro.testing.conformance --self-test
  python -m repro.testing.conformance \
    --datasets densearray_8x6,mesh3d_4 --precision fp32
fi

# Gradient-path smoke (full runs only): two training steps through the
# Pallas impl must produce a finite, decreasing loss — the backward runs
# the transpose-SpMM/SDDMM duality (DESIGN.md §9).
if [[ $# -eq 0 && "${TIER1_SMOKE:-1}" == "1" ]]; then
  python examples/gnn_train.py --steps 2 --impl pallas \
    --model gcn --scale 0.002

  # Fused-attention smoke (interpret mode): LM example forward + one
  # train step through the single-pass pallas_fused_attn megakernel —
  # dense-oracle parity for values and gradients, one launch for all
  # heads, decreasing loss (DESIGN.md §10).
  python examples/sparse_attention_lm.py --impl pallas --seq 256 --steps 1

  # Block-parallel scheduling floor (DESIGN.md §11): skewed hub-row
  # matrices through the balanced-vs-window comparison; the balanced
  # schedule must cut the idle-cell-adjusted cost >= 1.3x on every
  # skew >= 1.5 matrix (bitwise kernel parity is asserted inside the
  # bench itself).  REPRO_CHECK=full doubles as the §15 full-validation
  # pass over the bench suite: every constructor and dispatch in the
  # bench audits its formats/schedules host-side (bench numbers are
  # cost-model floors, not wall-clock, so the audit does not skew them).
  # --datasets folds the vendored real-matrix records into the same run:
  # every dataset record asserts oracle parity before timing, and the
  # summary maps each structure class to its winning impl.
  REPRO_CHECK=full python -m benchmarks.run --op spmm --skewed --datasets \
    --scale 0.002
  python - <<'EOF'
import json
with open("BENCH_spmm.json") as f:
    summary = json.load(f)["summary"]
# Real-matrix floor: every vendored-dataset record passed its dense-
# oracle parity check, and every structure class elected a winner.
assert summary["datasets_parity_ok"], "dataset record failed oracle parity"
winners = summary["class_winners"]
print("per-class winners: " + ", ".join(
    f"{c}->{w['impl']}" for c, w in sorted(winners.items())))
assert winners, "no structure-class winners recorded"
red = summary["balanced_cost_reduction_min"]
print(f"skewed balanced-vs-window cost min {red:.2f}x")
assert red >= 1.3, f"balanced scheduling floor regressed: {red}"
# Device-level partitioner floor (DESIGN.md §12): balance_cost max/mean
# across 8 devices must stay <= 1.25 on every skewed matrix — the
# partitioner balances by the cost model, not just splits evenly.
bal = summary["device_balance_max_over_mean_8dev"]
print(f"8-device partition balance max/mean {bal:.3f}")
assert bal <= 1.25, f"device partition balance regressed: {bal}"
# Mixed-precision floor (DESIGN.md §13): the bf16 fused-SpMM records must
# model >= 1.8x less HBM traffic than fp32 on the standard suite (< 2x
# only because the int32 metadata stream does not narrow).
mp = summary["hbm_reduction_geomean_bf16_vs_fp32"]
print(f"bf16/fp32 modeled HBM reduction geomean {mp:.2f}x")
assert mp >= 1.8, f"mixed-precision HBM floor regressed: {mp}"
# Overlapped-ring floor (DESIGN.md §14): modeled overlapped-vs-bulk
# makespan (best over n_batches) must stay >= 1.15x at 8 devices on
# every row-balanced overlap-suite matrix (currently min ~1.66x).
ovl = summary["overlap_makespan_improvement_min_8dev"]
print(f"8-device overlap/bulk makespan min {ovl:.2f}x")
assert ovl >= 1.15, f"overlapped-ring makespan floor regressed: {ovl}"
EOF

  # Multi-device sharded smoke (DESIGN.md §12): two training steps through
  # impl=pallas_sharded on an 8-way forced-host-device mesh — forward and
  # both duality backward ops run one local balanced launch per device
  # under shard_map, loss must decrease.
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python examples/gnn_train.py --steps 2 --impl pallas_sharded \
    --mesh 4,2 --model gcn --scale 0.002

  # Overlapped sharded smoke (DESIGN.md §14): same mesh, but the trailing
  # psum replaced by the double-buffered ppermute ring over segment
  # batches — forward and both duality backward ops run the overlap path.
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python examples/gnn_train.py --steps 2 --impl pallas_sharded_overlap \
    --mesh 4,2 --model gcn --scale 0.002
fi
