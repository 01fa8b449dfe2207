"""Overlapped communication/compute in the sharded sparse path
(DESIGN.md §14).

Two tiers, mirroring ``tests/test_sparse_shard.py``:

* **Host-side partitioner tests** run in-process (pure numpy): the
  per-device segment-*batch* sub-partition must cover every segment
  exactly once in order, keep attention batches window-aligned, emit
  store-only dummy batches when devices outnumber non-empty segments,
  agree with :func:`device_balance` on per-device totals, and clear the
  modeled makespan floor the BENCH records enforce.
* **Multi-device tests** run in 8-device child processes
  (``tests/_child.py``) on a 4x2 mesh: gradients of the double-buffered
  ``ppermute`` ring allclose (fp32) to the single-device
  ``pallas_balanced`` plan, and the bf16/int8 tolerance ladder.

Forward parity of the ring against ``pallas_balanced`` (meshes 1x1,
2x1, 2x2 and 4x2 × ``n_batches`` {1, 2, 4}, including empty-window and
ragged-N matrices) is one child program, ``tests/_overlap_parity.py``,
which checks several meshes per process.  Its cases are split over
``tests/test_sparse_shard_overlap_parity_1x1_4x2.py`` and
``tests/test_sparse_shard_overlap_parity_2x1_2x2.py`` so that
``--dist loadfile`` runs the two halves on different workers.
"""

import os
import sys

import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
sys.path.insert(0, SRC)

from repro.core import block_format, from_coo, from_dense  # noqa: E402
from repro.distributed.sparse_shard import (  # noqa: E402
    batch_costs,
    device_balance,
    partition_schedule,
)
from repro.sparse.graphs import hub_row_graph  # noqa: E402

from _child import run_child  # noqa: E402


def _example_blocked(m=64, density=0.1, hub=True, seed=0, k_blk=8):
    rng = np.random.default_rng(seed)
    a = ((rng.random((m, m)) < density)
         * rng.standard_normal((m, m))).astype(np.float32)
    if hub:
        a[3, :] = rng.standard_normal(m) * (rng.random(m) < 0.7)
    return a, block_format(from_dense(a), k_blk)


# ---------------------------------------------------------------------------
# Host-side batched-partition invariants
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("ndev", [1, 2, 4, 8])
@pytest.mark.parametrize("nb", [1, 2, 4])
def test_batched_partition_covers_segments_exactly_once(ndev, nb):
    """Real (non-pad) (device, batch) segments, concatenated in
    (device, batch) order, must reproduce the global segment list exactly
    once; pads are store-only entries on the dummy window."""
    _, blocked = _example_blocked()
    sched = blocked.schedule(1)
    part = partition_schedule(blocked, sched, ndev, n_batches=nb)
    assert part.n_batches == nb
    seg_win = np.asarray(sched.seg_win)
    seg_meta = np.asarray(sched.seg_meta)
    bsw = np.asarray(part.bseg_win)
    bsm = np.asarray(part.bseg_meta)
    w = blocked.num_windows
    assert bsw.shape[:2] == (ndev, nb)

    real_win, real_lo_len = [], []
    for d in range(ndev):
        for t in range(nb):
            pad = bsw[d, t] == w
            assert (bsm[d, t][pad][:, :2] == 0).all(), "pads store-only"
            assert (bsm[d, t][pad][:, 2:] == 1).all()
            real_win.append(bsw[d, t][~pad])
            real_lo_len.append(bsm[d, t][~pad][:, :2])
    np.testing.assert_array_equal(np.concatenate(real_win), seg_win)
    np.testing.assert_array_equal(np.concatenate(real_lo_len),
                                  seg_meta[:, :2])

    # batch row indices: every real row index < m, pads == m, and the
    # union over (d, b) covers every row some real segment's window owns
    bri = np.asarray(part.brow_idx)
    assert bri.shape[:2] == (ndev, nb)
    assert ((bri <= blocked.shape[0]).all())


@pytest.mark.parametrize("nb", [2, 4])
def test_window_aligned_batches_never_straddle(nb):
    """window_split=False (the attention path): a window's segments must
    land in exactly one (device, batch) slot — online-softmax state never
    crosses a ring step."""
    _, blocked = _example_blocked(hub=True)
    sched = blocked.schedule(1)
    part = partition_schedule(blocked, sched, 4, window_split=False,
                              n_batches=nb)
    w = blocked.num_windows
    bsw = np.asarray(part.bseg_win)
    seen = set()
    for d in range(4):
        for t in range(nb):
            wins = set(int(x) for x in bsw[d, t][bsw[d, t] != w])
            assert not (wins & seen), "window split across batch slots"
            seen |= wins


def test_more_devices_than_segments_store_only_batches():
    """Regression: a matrix with fewer non-empty segments than devices
    (or batches) must still partition — the surplus (device, batch)
    slots hold store-only dummy segments, not garbage."""
    fmt = from_dense(np.eye(16, dtype=np.float32))  # 2 windows, few segs
    blocked = block_format(fmt, 8)
    sched = blocked.schedule(1)
    part = partition_schedule(blocked, sched, 8, n_batches=4)
    w = blocked.num_windows
    bsw = np.asarray(part.bseg_win)
    bsm = np.asarray(part.bseg_meta)
    pad = bsw == w
    assert pad.any(), "expected dummy batches with 8 devices x 4 batches"
    assert (bsm[pad][:, :2] == 0).all() and (bsm[pad][:, 2:] == 1).all()
    # real segments still cover the schedule exactly once
    real = np.concatenate([bsw[d, t][bsw[d, t] != w]
                           for d in range(8) for t in range(4)])
    np.testing.assert_array_equal(real, np.asarray(sched.seg_win))
    # pad row indices are the sentinel (zero-masked by the gather)
    bri = np.asarray(part.brow_idx)
    assert (bri[pad.any(axis=-1) if bri.ndim == 3 else pad]
            <= blocked.shape[0]).all()


def test_batch_costs_match_device_balance():
    """Shared-cost-model invariant: summing the (D, NB) batch costs over
    batches reproduces device_balance's per-device totals — the batch
    cuts subdivide the device cuts, never move them."""
    rows, cols = hub_row_graph(1000, 8.0, seed=0, skew=1.5)
    fmt = from_coo(rows, cols, np.ones_like(rows, np.float32),
                   (1000, 1000), vector_size=8)
    blocked = block_format(fmt, 8)
    bal = device_balance(blocked, 8, split_blk=1)
    for nb in (1, 2, 4):
        stats = batch_costs(blocked, 8, nb)
        np.testing.assert_allclose(stats["costs"].sum(axis=1),
                                   np.asarray(bal["costs"]), rtol=1e-12)
        assert stats["rows"].shape == (8, nb)
        assert (stats["rows"] >= 0).all()


def test_overlap_makespan_floor():
    """The acceptance floor the BENCH_spmm.json overlap records enforce:
    modeled overlapped-vs-bulk makespan (best over n_batches) >= 1.15x at
    8 devices on every row-balanced overlap-suite matrix."""
    from benchmarks.common import overlap_makespan, overlap_suite

    for g, kind in overlap_suite(0.002):
        fmt = from_coo(g.rows, g.cols, g.vals,
                       (g.num_nodes, g.num_nodes), vector_size=8)
        blocked = block_format(fmt, 8)
        best = max(overlap_makespan(blocked, 128, num_devices=8,
                                    n_batches=nb)["improvement"]
                   for nb in (1, 2, 4))
        assert best >= 1.15, (g.name, best)


def test_registry_overlapped_flags():
    from repro.core import dispatch

    for op in ("spmm", "sddmm", "attention"):
        e = dispatch.get(op, "pallas_sharded_overlap")
        assert e.overlapped and e.multi_device and e.differentiable \
            and e.batched and e.load_balanced, e
        assert not dispatch.get(op, "pallas_sharded").overlapped
    assert "bf16" in dispatch.get("spmm", "pallas_sharded_overlap").precisions


def test_ad_plan_rejects_overlap_batches_on_bulk_impl():
    """overlap_batches > 1 is an overlap-capability knob; asking for it on
    a non-overlapped impl must fail loudly, not silently ignore."""
    from repro.core.autodiff import ad_plan

    a, _ = _example_blocked()
    with pytest.raises(ValueError, match="overlap"):
        ad_plan(from_dense(a), impl="pallas_balanced", overlap_batches=2)


# ---------------------------------------------------------------------------
# Multi-device gradients and precision (child processes)
# ---------------------------------------------------------------------------

def test_overlap_gradients_match_sharded():
    """spmm_ad / sddmm_ad / attention_ad with impl=pallas_sharded_overlap:
    forward AND duality backward ops all ride the ppermute ring (the call
    log proves no bulk fallback), grads allclose to the single-device
    balanced plan."""
    out = run_child("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import from_dense
        from repro.core import dispatch as sd
        from repro.core.autodiff import (ad_plan, attention_ad, sddmm_ad,
                                         spmm_ad)
        from repro.launch.mesh import make_host_mesh

        mesh = make_host_mesh(4, 2)
        rng = np.random.default_rng(0)
        m = 64
        a = ((rng.random((m, m)) < 0.1)
             * rng.standard_normal((m, m))).astype(np.float32)
        a[5, :] = rng.standard_normal(m) * (rng.random(m) < 0.8)
        fmt = from_dense(a)
        plan = ad_plan(fmt, impl="pallas_sharded_overlap", mesh=mesh,
                       overlap_batches=2)
        assert plan.overlap_batches == 2
        ref = ad_plan(fmt, impl="pallas_balanced")
        b = jnp.asarray(rng.standard_normal((m, 32)).astype(np.float32))
        q = jnp.asarray(rng.standard_normal((m, 16)).astype(np.float32))
        k = jnp.asarray(rng.standard_normal((m, 16)).astype(np.float32))
        v3 = jnp.asarray(rng.standard_normal((2, m, 16)).astype(np.float32))
        q3 = jnp.asarray(rng.standard_normal((2, m, 16)).astype(np.float32))

        with sd.record_calls() as log:
            gv, gb = jax.grad(
                lambda vals, bb: jnp.sum(spmm_ad(plan, vals, bb) ** 2),
                argnums=(0, 1))(plan.vals, b)
        assert all(i == "pallas_sharded_overlap" for _, i in log), log
        assert any(op == "sddmm" for op, _ in log), log  # dVals duality
        gv_r, gb_r = jax.grad(
            lambda vals, bb: jnp.sum(spmm_ad(ref, vals, bb) ** 2),
            argnums=(0, 1))(ref.vals, b)
        np.testing.assert_allclose(np.asarray(gv), np.asarray(gv_r),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(gb), np.asarray(gb_r),
                                   rtol=2e-4, atol=2e-4)

        gq = jax.grad(lambda qq: jnp.sum(sddmm_ad(plan, qq, k) ** 2))(q)
        gq_r = jax.grad(lambda qq: jnp.sum(sddmm_ad(ref, qq, k) ** 2))(q)
        np.testing.assert_allclose(np.asarray(gq), np.asarray(gq_r),
                                   rtol=2e-4, atol=2e-4)

        with sd.record_calls() as log:
            ga = jax.grad(
                lambda qq: jnp.sum(attention_ad(plan, qq, k, v3) ** 2))(q3)
        assert all(i == "pallas_sharded_overlap" for _, i in log), log
        ga_r = jax.grad(
            lambda qq: jnp.sum(attention_ad(ref, qq, k, v3) ** 2))(q3)
        np.testing.assert_allclose(np.asarray(ga), np.asarray(ga_r),
                                   rtol=2e-4, atol=2e-4)
        print("OVERLAP_GRADS_OK")
    """, devices=8, timeout=720)
    assert "OVERLAP_GRADS_OK" in out


def test_overlap_precision_ladder():
    """Overlapped SpMM at bf16/int8 and attention at bf16 match the
    single-device path within the DESIGN.md §13 tolerance ladder (ring
    scatter-add regroups the fp32 accumulation like the psum does)."""
    out = run_child("""
        import numpy as np
        import jax.numpy as jnp
        from repro.core import block_format, from_dense
        from repro.distributed.sparse_shard_overlap import (
            attention_sharded_overlap, spmm_sharded_overlap)
        from repro.kernels import ops
        from repro.launch.mesh import make_host_mesh

        rng = np.random.default_rng(0)
        a = (rng.standard_normal((64, 64)) * (rng.random((64, 64)) < 0.15)
             ).astype(np.float32)
        blocked = block_format(from_dense(a, vector_size=8), k_blk=8)
        b = jnp.asarray(rng.standard_normal((64, 32)), jnp.float32)
        mesh = make_host_mesh(4, 2)
        for prec in ("bf16", "int8"):
            ref = np.asarray(ops.spmm(blocked, b, interpret=True,
                                      precision=prec), np.float32)
            out = np.asarray(spmm_sharded_overlap(
                blocked, b, mesh=mesh, n_batches=2, interpret=True,
                precision=prec), np.float32)
            np.testing.assert_allclose(out, ref, rtol=2e-2,
                                       atol=2e-2 * np.abs(ref).max() + 0.07)
        q = jnp.asarray(rng.standard_normal((2, 64, 16)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((2, 64, 16)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((2, 64, 16)), jnp.float32)
        ref = np.asarray(ops.attention(blocked, q, k, v, interpret=True,
                                       precision="bf16"), np.float32)
        out = np.asarray(attention_sharded_overlap(
            blocked, q, k, v, mesh=mesh, n_batches=2, interpret=True,
            precision="bf16"), np.float32)
        np.testing.assert_allclose(out, ref, rtol=5e-2, atol=8e-2)
        print("OVERLAP_LADDER_OK")
    """, devices=8, timeout=180)
    assert "OVERLAP_LADDER_OK" in out
