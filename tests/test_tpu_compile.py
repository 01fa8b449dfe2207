"""Compile rehearsal of the main-path Pallas kernels for a TPU v5e chip.

The TPU compiler is installed with JAX, and it compiles for a chip that
is described (``v5e:2x2`` topology, one of its devices) rather than
attached.  Mosaic rejects what interpret mode accepts — slices not
aligned to the (8, 128) tiling, single-row copies of packed 16-bit
arrays, scalar-prefetched operands larger than SMEM — so these tests
compile the kernels of GNN training (``from_coo`` → ``ad_plan`` →
``models.gnn``) with ``interpret=False`` at the shapes of the Table-4
``Amazon`` graph at scale 1.0, through the same wrappers the registry
dispatches to.  Nothing runs; each compile takes a second or two.

The topology is described inside a fixture, never while the module is
imported: one process at a time may load the TPU library.
"""

from __future__ import annotations

import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import ad_plan, from_coo
from repro.core.autodiff import ADPlan
from repro.core.format import BlockedMEBCRS, Schedule
from repro.kernels.attention_pallas import (attention_launch_counts,
                                            attention_pallas)
from repro.kernels.layout import vectors_on_lanes
from repro.kernels.sddmm_pallas import sddmm_launch_counts, sddmm_pallas
from repro.kernels.spmm_pallas import (spmm_launch_counts, spmm_pallas,
                                       spmm_pallas_balanced)
from repro.models.gnn import (GNNConfig, init_agnn, init_gcn,
                              make_train_step)

# make_dataset("Amazon", scale=1.0) blocked at V=8, K_BLK=8: nodes, nonzero
# vectors padded to whole K-blocks (NNZP), K-blocks (NB), and the same for
# the transpose the backward runs.
NODES = 403_394
V, K_BLK = 8, 8
FWD = dict(nnzp=1_817_584, nb=227_198)
BWD = dict(nnzp=3_440_536, nb=430_067)
OVERFLOW = 71        # Aᵀ entries past the re-layout map's one level
GCN_WIDTH, AGNN_WIDTH = 128, 32


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache off meanwhile."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo, no_persistent_cache):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _blocked(sharding, nnzp, nb, dtype=jnp.float32):
    """Shapes of an Amazon-sized BlockedMEBCRS on the described chip."""
    w = -(-NODES // V)
    return BlockedMEBCRS(
        vals=_sds((nnzp, V), dtype, sharding),
        cols=_sds((nnzp,), jnp.int32, sharding),
        mask=_sds((nnzp, V), jnp.bool_, sharding),
        block_win=_sds((nb,), jnp.int32, sharding),
        win_ptr=_sds((w + 1,), jnp.int32, sharding),
        shape=(NODES, NODES), vector_size=V, k_blk=K_BLK)


def _compile_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("precision,shapes", [
    ("fp32", FWD), ("fp32", BWD), ("bf16", FWD)],
    ids=["fp32-forward", "fp32-transpose", "bf16-forward"])
def test_fused_spmm_compiles(one_chip, precision, shapes):
    """fp32 compiles; bf16 is refused before lowering, since it could only
    run widened to fp32 words (no packed 16-bit layout yet)."""
    dtype = jnp.float32 if precision == "fp32" else jnp.bfloat16
    blocked = _blocked(one_chip, **shapes, dtype=dtype)
    b = _sds((NODES, GCN_WIDTH), dtype, one_chip)

    def compile_text():
        return _compile_text(
            lambda a, x: spmm_pallas(a, x, interpret=False,
                                     precision=precision), blocked, b)

    if precision != "fp32":
        with pytest.raises(ValueError, match="fp32 operands only"):
            compile_text()
        return
    assert "tpu_custom_call" in compile_text()


def test_balanced_spmm_compiles(one_chip):
    blocked = _blocked(one_chip, **FWD)
    ns = FWD["nb"] + -(-NODES // V)          # every block + empty windows
    sched = Schedule(seg_win=_sds((ns,), jnp.int32, one_chip),
                     seg_meta=_sds((ns, 4), jnp.int32, one_chip),
                     split_blk=1, num_blocks=FWD["nb"])
    b = _sds((NODES, GCN_WIDTH), jnp.float32, one_chip)
    text = _compile_text(
        lambda a, s, x: spmm_pallas_balanced(a, x, schedule=s,
                                             interpret=False),
        blocked, sched, b)
    assert "tpu_custom_call" in text


def test_fused_sddmm_compiles(one_chip):
    blocked = _blocked(one_chip, **FWD)
    h = _sds((NODES, AGNN_WIDTH), jnp.float32, one_chip)
    text = _compile_text(
        lambda a, q, k: sddmm_pallas(a, q, k, interpret=False),
        blocked, h, h)
    assert "tpu_custom_call" in text


def test_fused_attention_compiles(one_chip):
    blocked = _blocked(one_chip, **FWD)
    h = _sds((NODES, AGNN_WIDTH), jnp.float32, one_chip)
    text = _compile_text(
        lambda a, q, k, v: attention_pallas(a, q, k, v, interpret=False),
        blocked, h, h, h)
    assert "tpu_custom_call" in text


def test_value_relayout_gathers_one_value_per_vector(one_chip):
    """The transpose plan's value re-layout, compiled for the chip at
    Amazon's shapes (one level, 71 overflow entries) and fed to the
    kernels' value layout: one gather of a value per Aᵀ vector, one of
    the overflow, and no copy of the values padded to 128 lanes (a
    ``(rows, 8)`` float array in the row-major ``{1,0}`` tiled layout)."""
    idx = lambda n, dt: _sds((n,), dt, one_chip)
    plan = ADPlan(fwd=_blocked(one_chip, **FWD), bwd=_blocked(one_chip, **BWD),
                  vec_idx=idx(BWD["nnzp"], jnp.int32),
                  vec_row=idx(BWD["nnzp"], jnp.int8),
                  ovf_src=idx(OVERFLOW, jnp.int32),
                  ovf_dst=idx(OVERFLOW, jnp.int32),
                  impl="pallas", n_blk=128, n_blk_t=128, f_blk=128)
    text = _compile_text(
        lambda p, x: vectors_on_lanes(p.transpose_vals(x), K_BLK), plan,
        _sds((FWD["nnzp"], V), jnp.float32, one_chip))
    gathers = re.findall(r"= f32\[(\d+)\]\{[^}]*\} gather\(", text)
    assert sorted(map(int, gathers)) == [OVERFLOW, BWD["nnzp"]]
    assert not re.search(r"f32\[\d+,8\]\{1,0:T\(8,128\)", text)


def test_gcn_step_kernels_carry_their_launch_counts(one_chip):
    """Lowered for the chip, each SpMM kernel of a GCN step names its
    direction and the work it starts in its ``kernel_metadata``.  The two
    layers' forward launches share one lowered kernel.  Lowering is
    enough."""
    rng = np.random.default_rng(0)
    n, nnz = 64, 300
    plan = ad_plan(from_coo(rng.integers(0, n, nnz), rng.integers(0, n, nnz),
                            np.ones(nnz, np.float32), (n, n)), impl="pallas")
    cfg = GNNConfig(in_dim=16, hidden_dim=16, num_classes=4, num_layers=2,
                    impl="pallas", interpret=False)
    params = init_gcn(jax.random.key(0), cfg)
    args = (params, params, plan, jnp.ones((n, 16)),
            jnp.zeros((n,), jnp.int32), jnp.ones((n,)))
    shapes = jax.tree.map(lambda a: _sds(a.shape, a.dtype, one_chip), args)
    text = jax.jit(make_train_step(cfg)).lower(*shapes).as_text(dialect="hlo")
    metas = [json.loads(m) for m in
             re.findall(r"kernel_metadata=(\{[^{}]*\})", text)]
    assert sorted(m["dir"] for m in metas) == ["bwd", "fwd"]
    for m in metas:
        blocked = plan.fwd if m["dir"] == "fwd" else plan.bwd
        want = spmm_launch_counts(
            nnzp=blocked.cols.shape[0], num_windows=blocked.num_windows,
            num_steps=blocked.num_windows, heads=1, n_pad=128, k_blk=8,
            n_blk=128)
        assert m == {"op": "spmm", "dir": m["dir"],
                     **{k: str(v) for k, v in want.items()}}


def test_agnn_step_kernels_carry_their_launch_counts(one_chip):
    """Lowered for the chip, an AGNN step's fused attention kernel and its
    SDDMM kernels (the recomputed scores and the probabilities' gradient)
    carry ``kernel_metadata`` with their launch counts, and the step
    carries the recompute's and the softmax's tags."""
    rng = np.random.default_rng(1)
    n, nnz, width = 64, 300, AGNN_WIDTH
    plan = ad_plan(from_coo(rng.integers(0, n, nnz), rng.integers(0, n, nnz),
                            np.ones(nnz, np.float32), (n, n)), impl="pallas")
    cfg = GNNConfig(model="agnn", in_dim=16, hidden_dim=width,
                    num_classes=4, num_layers=1, impl="pallas",
                    interpret=False)
    params = init_agnn(jax.random.key(0), cfg)
    args = (params, params, plan, jnp.ones((n, 16)),
            jnp.zeros((n,), jnp.int32), jnp.ones((n,)))
    shapes = jax.tree.map(lambda a: _sds(a.shape, a.dtype, one_chip), args)
    text = jax.jit(make_train_step(cfg)).lower(*shapes).as_text(dialect="hlo")
    metas = [json.loads(m) for m in
             re.findall(r"kernel_metadata=(\{[^{}]*\})", text)]
    fwd = plan.fwd
    nnzp = fwd.cols.shape[0]
    want = {
        "attention": attention_launch_counts(
            nnzp=nnzp, num_windows=fwd.num_windows,
            num_steps=fwd.num_windows, heads=1, d_pad=128, dv_pad=128,
            k_blk=8),
        "sddmm": sddmm_launch_counts(
            nnzp=nnzp, num_windows=fwd.num_windows,
            num_chunks=-(-nnzp // 128), heads=1, f_pad=128, f_blk=128),
    }
    # launches of one signature share a lowered kernel
    assert {m["op"] for m in metas} == {"attention", "sddmm", "spmm"}
    for m in metas:
        if m["op"] in want:
            assert m == {"op": m["op"], "dir": "fwd",
                         **{k: str(v) for k, v in want[m["op"]].items()}}
    for tag in ("fs.attn_recompute", "fs.sparse_softmax"):
        assert f'flashsparse_op="{tag}"' in text
