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

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.format import BlockedMEBCRS, Schedule
from repro.kernels.attention_pallas import attention_pallas
from repro.kernels.sddmm_pallas import sddmm_pallas
from repro.kernels.spmm_pallas import spmm_pallas, spmm_pallas_balanced

# make_dataset("Amazon", scale=1.0) blocked at V=8, K_BLK=8: nodes, nonzero
# vectors padded to whole K-blocks (NNZP), K-blocks (NB), and the same for
# the transpose the backward runs.
NODES = 403_394
V, K_BLK = 8, 8
FWD = dict(nnzp=1_817_584, nb=227_198)
BWD = dict(nnzp=3_440_536, nb=430_067)
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
