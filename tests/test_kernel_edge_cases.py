"""Edge cases for the gather-free fused Pallas kernels (interpret mode).

Covers the hazards the in-kernel DMA redesign introduced: empty windows
(the ``_zero_unvisited`` replacement), N not a multiple of ``n_blk``, a
window whose vector count is an exact multiple of ``k_blk``, the
serialized-DMA ablation's parity with the coalesced path, and the staged
baseline's agreement with the fused kernel.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import block_format, from_dense, spmm_blocked, sddmm_blocked
from repro.kernels import ops


def random_sparse(rng, m, k, density):
    a = rng.standard_normal((m, k)).astype(np.float32)
    a *= rng.random((m, k)) < density
    return a


def make_blocked(a, v=8, k_blk=8):
    return block_format(from_dense(a, vector_size=v), k_blk=k_blk)


# -------------------------------------------------------- empty windows ----


def test_empty_windows_are_zero_in_kernel():
    """Windows with no nonzero vectors must come out exactly zero — the
    fused epilogue's exactly-once init replaces the _zero_unvisited pass."""
    rng = np.random.default_rng(0)
    a = random_sparse(rng, 48, 40, 0.3)
    a[8:24] = 0.0  # windows 1 and 2 (V=8) are empty
    a[40:48] = 0.0  # last window empty too
    blocked = make_blocked(a)
    b = jnp.asarray(rng.standard_normal((40, 16)), dtype=jnp.float32)
    out = np.asarray(ops.spmm(blocked, b, interpret=True))
    assert np.all(out[8:24] == 0.0)
    assert np.all(out[40:48] == 0.0)
    np.testing.assert_allclose(out, a @ np.asarray(b), rtol=2e-4, atol=2e-4)


def test_all_empty_matrix():
    a = np.zeros((24, 24), np.float32)
    blocked = make_blocked(a)
    b = jnp.asarray(np.random.default_rng(1).standard_normal((24, 8)),
                    dtype=jnp.float32)
    out = np.asarray(ops.spmm(blocked, b, interpret=True))
    assert out.shape == (24, 8)
    assert np.all(out == 0.0)


# ----------------------------------------------- N not multiple of n_blk ----


@pytest.mark.parametrize("n,n_blk", [(100, 64), (48, 128), (33, 32), (1, 128)])
def test_spmm_ragged_n(n, n_blk):
    rng = np.random.default_rng(2)
    a = random_sparse(rng, 40, 56, 0.25)
    blocked = make_blocked(a)
    b = jnp.asarray(rng.standard_normal((56, n)), dtype=jnp.float32)
    out = ops.spmm(blocked, b, n_blk=n_blk, interpret=True)
    assert out.shape == (40, n)
    np.testing.assert_allclose(np.asarray(out), a @ np.asarray(b),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("f,f_blk", [(100, 64), (20, 128), (65, 32)])
def test_sddmm_ragged_f(f, f_blk):
    rng = np.random.default_rng(3)
    a = random_sparse(rng, 40, 48, 0.25)
    blocked = make_blocked(a)
    q = jnp.asarray(rng.standard_normal((40, f)), dtype=jnp.float32)
    kk = jnp.asarray(rng.standard_normal((48, f)), dtype=jnp.float32)
    out = ops.sddmm(blocked, q, kk, f_blk=f_blk, interpret=True)
    expected = sddmm_blocked(blocked, q, kk)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=1e-4, atol=1e-4)


# ------------------------------------- exact-multiple window vector count ----


def test_window_with_exact_k_blk_multiple():
    """A window holding exactly k_blk (and 2·k_blk) nonzero vectors — no
    padding vectors in its last K-block."""
    k_blk = 4
    a = np.zeros((16, 32), np.float32)
    a[0, :k_blk] = 1.5          # window 0: exactly k_blk vectors
    a[8, :2 * k_blk] = -2.0     # window 1: exactly 2·k_blk vectors
    blocked = make_blocked(a, v=8, k_blk=k_blk)
    counts = np.diff(np.asarray(blocked.win_ptr))
    assert counts.tolist() == [1, 2]
    b = jnp.asarray(np.random.default_rng(4).standard_normal((32, 24)),
                    dtype=jnp.float32)
    out = ops.spmm(blocked, b, interpret=True)
    np.testing.assert_allclose(np.asarray(out), a @ np.asarray(b),
                               rtol=2e-4, atol=2e-4)


# ------------------------------------------------------- path agreement ----


def test_noncoalesced_bitwise_parity():
    """The serialized-DMA ablation reorders copies, not arithmetic — its
    output must be bitwise identical to the coalesced fused path."""
    rng = np.random.default_rng(5)
    a = random_sparse(rng, 40, 64, 0.2)
    blocked = make_blocked(a)
    b = jnp.asarray(rng.standard_normal((64, 32)), dtype=jnp.float32)
    out_c = np.asarray(ops.spmm(blocked, b, interpret=True))
    out_nc = np.asarray(ops.spmm_noncoalesced(blocked, b, interpret=True))
    assert np.array_equal(out_c, out_nc)


def test_fused_bitwise_matches_blocked_fp32():
    """fp32 accumulation order matches spmm_blocked exactly (acceptance:
    bitwise-equal, not just allclose)."""
    rng = np.random.default_rng(6)
    for v, k_blk in [(8, 8), (8, 16), (16, 8)]:
        a = random_sparse(rng, 72, 72, 0.15)
        blocked = make_blocked(a, v=v, k_blk=k_blk)
        b = jnp.asarray(rng.standard_normal((72, 48)), dtype=jnp.float32)
        out = np.asarray(ops.spmm(blocked, b, interpret=True))
        expected = np.asarray(spmm_blocked(blocked, b))
        assert np.array_equal(out, expected), (v, k_blk)


def test_staged_baseline_matches_fused():
    rng = np.random.default_rng(7)
    a = random_sparse(rng, 56, 56, 0.2)
    a[16:24] = 0.0  # make sure the staged path's zero-pass is exercised
    blocked = make_blocked(a)
    b = jnp.asarray(rng.standard_normal((56, 40)), dtype=jnp.float32)
    out_f = np.asarray(ops.spmm(blocked, b, interpret=True))
    out_s = np.asarray(ops.spmm_staged(blocked, b, interpret=True))
    np.testing.assert_allclose(out_f, out_s, rtol=1e-5, atol=1e-5)


def test_fused_output_dtype_cast_in_kernel():
    rng = np.random.default_rng(8)
    a = random_sparse(rng, 32, 32, 0.2)
    blocked = make_blocked(a)
    b = jnp.asarray(rng.standard_normal((32, 16)), dtype=jnp.bfloat16)
    out = ops.spmm(blocked, b, interpret=True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out, np.float32), a @ np.asarray(b, np.float32),
        rtol=5e-2, atol=5e-2)


# ------------------------------------------------------ format invariant ----


def test_win_ptr_matches_block_win():
    rng = np.random.default_rng(9)
    a = random_sparse(rng, 80, 64, 0.15)
    a[24:40] = 0.0
    blocked = make_blocked(a)
    wp = np.asarray(blocked.win_ptr)
    bw = np.asarray(blocked.block_win)
    assert wp[0] == 0 and wp[-1] == blocked.num_blocks
    counts = np.diff(wp)
    expected = np.bincount(bw, minlength=blocked.num_windows)
    assert np.array_equal(counts, expected)
    # each window's claimed range really holds its blocks
    for w in range(blocked.num_windows):
        assert np.all(bw[wp[w]:wp[w + 1]] == w)


def test_win_ptr_all_empty_excludes_dummy_block():
    blocked = make_blocked(np.zeros((16, 16), np.float32))
    assert blocked.num_blocks == 1  # the dummy block keeps arrays non-empty
    assert int(np.asarray(blocked.win_ptr)[-1]) == 0  # ...but no window owns it


# ----------------------------------------------------------- HBM model ----


def test_hbm_model_fused_beats_staged():
    rng = np.random.default_rng(12)
    a = random_sparse(rng, 128, 128, 0.1)
    blocked = make_blocked(a)
    fused = ops.spmm_hbm_bytes(blocked, 128, impl="fused")
    staged = ops.spmm_hbm_bytes(blocked, 128, impl="staged")
    assert staged >= 2 * fused
    s_fused = ops.sddmm_hbm_bytes(blocked, 128, impl="fused")
    s_staged = ops.sddmm_hbm_bytes(blocked, 128, impl="staged")
    assert s_staged >= 2 * s_fused
