"""Multi-device sharded sparse ops (DESIGN.md §12).

Two tiers:

* **Host-side partitioner tests** run in-process (pure numpy — no mesh
  needed): segment-coverage invariants, window alignment, ownership
  disjointness, padding inertness, and the balance floor the BENCH
  records enforce.
* **Parity tests** run in child processes (``tests/_child.py``) with
  8 forced host devices (the main pytest process must keep the single
  real CPU device), asserting allclose (fp32) of sharded
  SpMM/SDDMM/attention — forward and gradients — against the
  single-device ``pallas_balanced`` path on standard and skewed
  matrices.  Forward parity is one child: it computes each single-device
  reference once, then checks the meshes 1x1, 2x1, 2x2 and 4x2 in turn
  and prints one line per mesh, which its own parametrised case reads.

The overlapped (``ppermute`` ring) path's tests are in
``tests/test_sparse_shard_overlap*.py``.
"""

import os
import sys

import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
sys.path.insert(0, SRC)

from repro.core import block_format, from_coo, from_dense  # noqa: E402
from repro.distributed.sparse_shard import (  # noqa: E402
    device_balance,
    partition_schedule,
)
from repro.sparse.graphs import hub_row_graph  # noqa: E402

from _child import assert_mesh_ok, run_child  # noqa: E402


def _example_blocked(m=64, density=0.1, hub=True, seed=0, k_blk=8):
    rng = np.random.default_rng(seed)
    a = ((rng.random((m, m)) < density)
         * rng.standard_normal((m, m))).astype(np.float32)
    if hub:
        a[3, :] = rng.standard_normal(m) * (rng.random(m) < 0.7)
    return a, block_format(from_dense(a), k_blk)


# ---------------------------------------------------------------------------
# Host-side partitioner invariants
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("ndev", [1, 2, 4, 8])
@pytest.mark.parametrize("window_split", [True, False])
def test_partition_covers_segments_exactly_once(ndev, window_split):
    _, blocked = _example_blocked()
    sched = blocked.schedule(1)
    part = partition_schedule(blocked, sched, ndev,
                              window_split=window_split)
    seg_win = np.asarray(sched.seg_win)
    seg_meta = np.asarray(sched.seg_meta)
    sw = np.asarray(part.seg_win)
    sm = np.asarray(part.seg_meta)
    w = blocked.num_windows

    # Real (non-pad) local segments, concatenated in device order, must
    # reproduce the global segment list exactly once, in order — pads are
    # exactly the entries pointing at the dummy window.
    real_win, real_lo_len = [], []
    for d in range(ndev):
        pad = sw[d] == w
        assert (sm[d][pad][:, :2] == 0).all(), "pads must be store-only"
        assert (sm[d][pad][:, 2:] == 1).all()
        real_win.append(sw[d][~pad])
        real_lo_len.append(sm[d][~pad][:, :2])
    np.testing.assert_array_equal(np.concatenate(real_win), seg_win)
    np.testing.assert_array_equal(np.concatenate(real_lo_len),
                                  seg_meta[:, :2])

    # Block ownership partitions the scheduled blocks exactly.
    own = np.asarray(part.blk_own)
    nnzp_owned = own.sum(axis=0)
    scheduled = np.zeros(own.shape[1], bool)
    scheduled[: part.num_blocks * blocked.k_blk] = True
    np.testing.assert_array_equal(nnzp_owned, scheduled.astype(int))


def test_window_aligned_partition_never_straddles():
    _, blocked = _example_blocked(hub=True)
    sched = blocked.schedule(1)
    part = partition_schedule(blocked, sched, 4, window_split=False)
    w = blocked.num_windows
    sw = np.asarray(part.seg_win)
    seen = set()
    for d in range(part.num_devices):
        wins = set(int(x) for x in sw[d][sw[d] != w])
        assert not (wins & seen), "window owned by two devices"
        seen |= wins
    # row ownership disjoint and complete
    own = np.asarray(part.row_own)
    np.testing.assert_array_equal(own.sum(axis=0),
                                  np.ones(own.shape[1], int))


def test_straddled_window_flags_reinit_per_device():
    """A hub window cut mid-range must re-init on the second device and
    store a partial on the first (the psum recombines)."""
    _, blocked = _example_blocked(m=32, density=0.0, hub=True)
    sched = blocked.schedule(1)
    part = partition_schedule(blocked, sched, 2, window_split=True)
    sw = np.asarray(part.seg_win)
    sm = np.asarray(part.seg_meta)
    w = blocked.num_windows
    hub_win = 0   # row 3 lives in window 0
    on = [np.flatnonzero(sw[d] == hub_win) for d in range(2)]
    if all(len(x) for x in on):   # the cut actually straddled the hub
        assert sm[0, on[0][0], 2] == 1 and sm[0, on[0][-1], 3] == 1
        assert sm[1, on[1][0], 2] == 1 and sm[1, on[1][-1], 3] == 1


def test_partition_balance_floor_on_skewed_matrix():
    """The acceptance floor the BENCH_spmm.json records enforce:
    per-device balance_cost max/mean <= 1.25 at 8 devices on a hub-row
    matrix (the partitioner balances by cost, not by segment count)."""
    rows, cols = hub_row_graph(2000, 8.0, seed=0, skew=2.0)
    fmt = from_coo(rows, cols, np.ones_like(rows, np.float32),
                   (2000, 2000), vector_size=8)
    blocked = block_format(fmt, 8)
    bal = device_balance(blocked, 8, split_blk=1)
    assert len(bal["costs"]) == 8
    assert bal["max_over_mean"] <= 1.25, bal


def test_single_device_partition_is_the_whole_schedule():
    _, blocked = _example_blocked()
    sched = blocked.schedule(1)
    part = partition_schedule(blocked, sched, 1)
    np.testing.assert_array_equal(np.asarray(part.seg_win)[0],
                                  np.asarray(sched.seg_win))
    assert np.asarray(part.row_own).all()


def test_all_empty_matrix_partitions():
    fmt = from_dense(np.zeros((24, 24), np.float32))
    blocked = block_format(fmt, 8)
    part = partition_schedule(blocked, blocked.schedule(1), 4)
    assert part.num_blocks == 0
    assert not np.asarray(part.blk_own).any()
    # every (empty) window still owned exactly once → zero output covered
    np.testing.assert_array_equal(
        np.asarray(part.row_own).sum(axis=0), np.ones(24, int))


# ---------------------------------------------------------------------------
# Multi-device parity (child processes)
# ---------------------------------------------------------------------------

_MESHES = [(1, 1, 1), (2, 1, 2), (2, 2, 4), (4, 2, 8)]

_PARITY = """
    import traceback
    import numpy as np, jax, jax.numpy as jnp
    from repro.core import from_dense, block_format
    from repro.kernels import ops
    from repro.launch.mesh import make_host_mesh
    from repro.distributed.sparse_shard import (
        spmm_sharded, sddmm_sharded, attention_sharded)

    rng = np.random.default_rng(0)
    mats = []
    for seed, hub in [(0, False), (1, True)]:
        m = 64
        a = ((rng.random((m, m)) < 0.1)
             * rng.standard_normal((m, m))).astype(np.float32)
        if hub:
            a[5, :] = rng.standard_normal(m) * (rng.random(m) < 0.8)
        mats.append(a)
    # operands and single-device references, drawn and computed once
    cases = []
    for a in mats:
        m = a.shape[0]
        blocked = block_format(from_dense(a), 8)
        b = jnp.asarray(rng.standard_normal((m, 32)).astype(np.float32))
        q = jnp.asarray(rng.standard_normal((m, 16)).astype(np.float32))
        k = jnp.asarray(rng.standard_normal((m, 16)).astype(np.float32))
        q3 = jnp.asarray(rng.standard_normal((2, m, 16)).astype(np.float32))
        v3 = jnp.asarray(rng.standard_normal((2, m, 16)).astype(np.float32))
        b3 = jnp.stack([b, 2 * b])
        refs = (ops.spmm_balanced(blocked, b, interpret=True),
                ops.sddmm_balanced(blocked, q, k, interpret=True),
                ops.attention_balanced(blocked, q3, k, v3, interpret=True),
                ops.spmm_balanced(blocked, b3, interpret=True))
        cases.append((blocked, b, q, k, q3, v3, b3, refs))

    def close(out, ref):
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    for data, model in {meshes}:
        try:
            mesh = make_host_mesh(data, model)
            for blocked, b, q, k, q3, v3, b3, refs in cases:
                close(spmm_sharded(blocked, b, mesh=mesh), refs[0])
                close(sddmm_sharded(blocked, q, k, mesh=mesh), refs[1])
                # batched heads (H=2): heads ride the model axis when it
                # divides
                close(attention_sharded(blocked, q3, k, v3, mesh=mesh),
                      refs[2])
                close(spmm_sharded(blocked, b3, mesh=mesh), refs[3])
            print("PARITY_OK", data, model, flush=True)
        except Exception as e:
            traceback.print_exc()
            print("PARITY_FAIL", data, model, " ".join(str(e).split()),
                  flush=True)
"""


@pytest.fixture(scope="module")
def parity_out():
    """One 8-device child checks every mesh of ``_MESHES``."""
    meshes = [(d, m) for d, m, _ in _MESHES]
    return run_child(_PARITY.format(meshes=meshes), devices=8,
                     timeout=420)


@pytest.mark.parametrize("data,model,devices", _MESHES)
def test_sharded_parity_vs_balanced(parity_out, data, model, devices):
    assert data * model == devices
    assert_mesh_ok(parity_out, "PARITY", data, model)


def test_sharded_gradients_match_balanced():
    """spmm_ad / sddmm_ad / attention_ad with impl=pallas_sharded: the
    backward duality ops run the sharded kernels on each direction's own
    partition, grads allclose to the single-device balanced plan."""
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
        plan = ad_plan(fmt, impl="pallas_sharded", mesh=mesh)
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
        # the whole vjp must stay on the sharded impls — no dense fallback
        assert all(i == "pallas_sharded" for _, i in log), log
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

        ga = jax.grad(
            lambda qq: jnp.sum(attention_ad(plan, qq, k, v3) ** 2))(q3)
        ga_r = jax.grad(
            lambda qq: jnp.sum(attention_ad(ref, qq, k, v3) ** 2))(q3)
        np.testing.assert_allclose(np.asarray(ga), np.asarray(ga_r),
                                   rtol=2e-4, atol=2e-4)
        print("GRADS_OK")
    """, devices=8, timeout=240)
    assert "GRADS_OK" in out


def test_sharded_empty_and_registry_flags():
    out = run_child("""
        import numpy as np, jax.numpy as jnp
        from repro.core import from_dense, block_format
        from repro.core import dispatch
        from repro.launch.mesh import make_host_mesh
        from repro.distributed.sparse_shard import (
            sddmm_sharded, spmm_sharded)

        for op in ("spmm", "sddmm", "attention"):
            e = dispatch.get(op, "pallas_sharded")
            assert e.multi_device and e.differentiable and e.batched \\
                and e.load_balanced, e

        mesh = make_host_mesh(2, 1)
        blocked = block_format(from_dense(np.zeros((24, 24), np.float32)), 8)
        b = jnp.ones((24, 8), jnp.float32)
        out = spmm_sharded(blocked, b, mesh=mesh)
        assert not np.asarray(out).any() and out.shape == (24, 8)
        sd = sddmm_sharded(blocked, b, b, mesh=mesh)
        assert not np.asarray(sd).any()
        print("EMPTY_OK")
    """, devices=2, timeout=60)
    assert "EMPTY_OK" in out


def test_sharded_format_shardings_place_partition_on_data_axis():
    out = run_child("""
        import numpy as np, jax
        from repro.core import from_dense
        from repro.core.autodiff import ad_plan
        from repro.launch.mesh import make_host_mesh
        from repro.distributed.sharding import sparse_format_shardings
        from repro.distributed.sparse_shard import ShardedSchedule

        mesh = make_host_mesh(4, 2)
        rng = np.random.default_rng(0)
        a = ((rng.random((64, 64)) < 0.1)
             * rng.standard_normal((64, 64))).astype(np.float32)
        plan = ad_plan(from_dense(a), impl="pallas_sharded", mesh=mesh)
        sh = sparse_format_shardings(plan, mesh)
        # partition arrays shard their device dim; everything else replicates
        assert tuple(sh.fwd_part.seg_win.spec) == ("data",)
        assert tuple(sh.bwd_part.row_own.spec) == ("data",)
        assert tuple(sh.fwd.vals.spec) == ()
        for leaf in (sh.vec_idx, sh.vec_row, sh.ovf_src, sh.ovf_dst):
            assert tuple(leaf.spec) == ()

        # heads_over_model placement matches the sharded ops' head-mode
        # in_specs: leading head dim over "model", nothing over "data"
        # (row parallelism lives inside the op), replicated when 2-D
        from repro.distributed.sharding import sparse_operand_pspec
        assert tuple(sparse_operand_pspec(
            mesh, batched=True, heads_over_model=True)) == ("model",)
        assert tuple(sparse_operand_pspec(
            mesh, batched=False, heads_over_model=True)) == ()
        print("SHARDINGS_OK")
    """, devices=8, timeout=60)
    assert "SHARDINGS_OK" in out
