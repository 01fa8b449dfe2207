"""Gradient compression: error feedback, traffic accounting, psum parity."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.train.compression import (
    CompressionConfig, compress_int8, compress_topk, compressed_bytes,
    decompress_int8, decompress_topk, init_error, raw_bytes)

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


def _grads(seed=0, shape=(33, 65)):
    rng = np.random.default_rng(seed)
    return {"w": jnp.asarray(rng.standard_normal(shape).astype(np.float32))}


def test_int8_roundtrip_small_error():
    g = _grads()
    cfg = CompressionConfig(kind="int8", block=32)
    comp, err = compress_int8(g, init_error(g), cfg)
    g_hat = decompress_int8(comp, g)
    rel = float(jnp.linalg.norm(g_hat["w"] - g["w"])
                / jnp.linalg.norm(g["w"]))
    assert rel < 0.01
    # error buffer holds exactly what was dropped
    np.testing.assert_allclose(np.asarray(err["w"]),
                               np.asarray(g["w"] - g_hat["w"]), rtol=1e-5,
                               atol=1e-6)


def test_error_feedback_preserves_signal():
    """Constant gradient through lossy top-k: the error-feedback residual
    stays bounded, so mean applied update → true gradient as O(1/T)."""
    g = _grads(2, (512,))
    cfg = CompressionConfig(kind="topk", topk_frac=0.1)

    def drift_after(steps):
        err = init_error(g)
        applied = jnp.zeros_like(g["w"])
        for _ in range(steps):
            comp, err = compress_topk(g, err, cfg)
            applied = applied + decompress_topk(comp, g)["w"]
        return float(jnp.linalg.norm(applied / steps - g["w"])
                     / jnp.linalg.norm(g["w"]))

    d20, d100 = drift_after(20), drift_after(100)
    assert d100 < d20 / 2, (d20, d100)   # O(1/T) decay
    assert d100 < 0.1, d100


def test_traffic_accounting():
    g = _grads(3, (256, 64))
    cfg = CompressionConfig(kind="int8", block=256)
    comp, _ = compress_int8(g, init_error(g), cfg)
    assert raw_bytes(g) == 256 * 64 * 4
    ratio = compressed_bytes(comp) / raw_bytes(g)
    assert ratio < 0.30  # ≈ 4x reduction + scales


def test_compressed_psum_matches_mean():
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import make_host_mesh
    from repro.train.train_step import compressed_psum

    mesh = make_host_mesh(1, 1)
    x = jnp.asarray(np.random.default_rng(0)
                    .standard_normal((4, 8)).astype(np.float32))
    out = jax.shard_map(lambda v: compressed_psum(v, "data"),
                        mesh=mesh, in_specs=P(), out_specs=P(),
                        check_vma=False)(x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x), rtol=2e-2,
                               atol=2e-2)


if HAVE_HYPOTHESIS:
    @settings(max_examples=25, deadline=None)
    @given(st.integers(0, 2 ** 31 - 1), st.integers(1, 400),
           st.floats(1e-3, 1e3))
    def test_int8_error_bounded_property(seed, n, scale):
        """|x − dequant(quant(x))| ≤ blockmax/254 + eps, any shape/scale."""
        rng = np.random.default_rng(seed)
        x = {"w": jnp.asarray(
            (rng.standard_normal(n) * scale).astype(np.float32))}
        cfg = CompressionConfig(kind="int8", block=64)
        comp, _ = compress_int8(x, init_error(x), cfg)
        x_hat = decompress_int8(comp, x)
        err = np.abs(np.asarray(x_hat["w"] - x["w"]))
        bound = np.abs(np.asarray(x["w"])).max() / 127.0 + 1e-6
        assert err.max() <= bound
