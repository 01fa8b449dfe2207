"""Mixed-precision kernel path: the tolerance ladder (DESIGN.md §13).

Every registered Pallas impl must hold, per precision level:

  fp32   bitwise-identical to the default (``precision=None``) run on
         fp32 operands — the narrow path may not perturb the legacy path
  bf16   within rtol ≈ 1e-2 of the fp32 run (inputs narrowed to 8-bit
         mantissas, accumulation stays fp32 in-kernel)
  int8   (SpMM only) bitwise-equal to the XLA dequantize-then-contract
         oracle, and within the scale-derived absolute bound of the fp32
         product (|ΔA| ≤ scale/2 per element ⇒ |ΔC| ≤ Σ_k bound·|b|)

plus: gradients through ``ad_plan(precision=...)`` keep fp32 master
dtypes, the dispatch registry's ``precisions`` capability gate rejects
unsupported combinations, and the ladder holds on the edge cases that
bit the fused kernels before (empty windows, ragged N, H ∈ {1, 4}).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import block_format, from_dense
from repro.core import dispatch as sparse_dispatch
from repro.core.quantize import quantize_block_values, quantize_format
from repro.kernels import ops

from _child import run_child


def random_sparse(rng, m, k, density):
    a = rng.standard_normal((m, k)).astype(np.float32)
    a *= rng.random((m, k)) < density
    return a


def make_blocked(rng, m, k, density, v=8, k_blk=8):
    a = random_sparse(rng, m, k, density)
    return a, block_format(from_dense(a, vector_size=v), k_blk=k_blk)


def int8_output_bound(blocked, b):
    """Per-element |ΔC| bound from the per-K-block quantization error.

    |Δvals| ≤ scale/2 elementwise ⇒ |ΔC[i, j]| ≤ Σ_k bound_k · |b[k, j]|
    — computed with the same sampled-column structure as the SpMM, plus
    the bf16 rounding of b itself (b rides at bf16 on the int8 path).
    """
    _, scales = quantize_block_values(blocked.vals, blocked.k_blk)
    bound_vals = np.repeat(np.asarray(scales), blocked.k_blk)[:, None] / 2
    babs = np.abs(np.asarray(
        jnp.take(b, blocked.cols, axis=0).astype(jnp.bfloat16),
        np.float32))
    nb = blocked.num_blocks
    contrib = np.einsum(
        "bkv,bkn->bvn",
        np.broadcast_to(bound_vals.reshape(nb, blocked.k_blk, 1),
                        (nb, blocked.k_blk, blocked.vector_size)),
        babs.reshape(nb, blocked.k_blk, -1))
    out = np.zeros((blocked.num_windows, blocked.vector_size, babs.shape[-1]),
                   np.float32)
    np.add.at(out, np.asarray(blocked.block_win), contrib)
    return out.reshape(-1, babs.shape[-1])[: blocked.shape[0]]


SPMM_IMPLS = ["pallas", "pallas_balanced", "blocked"]


def _run_spmm(impl, blocked, b, precision, n_blk=None):
    kw = {"precision": precision} if precision is not None else {}
    if impl == "pallas":
        return ops.spmm(blocked, b, interpret=True,
                        **({"n_blk": n_blk} if n_blk else {}), **kw)
    if impl == "pallas_balanced":
        return ops.spmm_balanced(blocked, b, schedule=blocked.schedule(1),
                                 interpret=True, **kw)
    from repro.core.spmm import spmm

    return spmm(blocked, b, impl="blocked", **kw)


# ------------------------------------------------------------ SpMM ladder ----


@pytest.mark.parametrize("impl", SPMM_IMPLS)
@pytest.mark.parametrize("m,k,n", [(64, 64, 128), (48, 40, 33)])
def test_spmm_ladder(impl, m, k, n):
    rng = np.random.default_rng(0)
    a, blocked = make_blocked(rng, m, k, 0.15)
    b = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)

    base = np.asarray(_run_spmm(impl, blocked, b, None))
    # fp32: bitwise vs the default path on fp32 operands
    np.testing.assert_array_equal(
        np.asarray(_run_spmm(impl, blocked, b, "fp32")), base)

    # bf16: fp32 accumulation over bf16 inputs
    out16 = _run_spmm(impl, blocked, b, "bf16")
    assert out16.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out16, np.float32), base,
                               rtol=2e-2, atol=2e-2 * np.abs(base).max())

    # int8: matches the XLA dequantize oracle and the analytic bound
    out8 = _run_spmm(impl, blocked, b, "int8")
    assert out8.dtype == jnp.bfloat16
    from repro.core.spmm import spmm

    oracle = spmm(blocked, b, impl="blocked", precision="int8")
    np.testing.assert_allclose(np.asarray(out8, np.float32),
                               np.asarray(oracle, np.float32),
                               rtol=2e-2, atol=2e-2 * np.abs(base).max())
    err = np.abs(np.asarray(out8, np.float32) - base)
    bound = int8_output_bound(blocked, b)
    # analytic quantization bound + bf16 resolution of the output store
    slack = np.maximum(np.abs(base), 1.0) * 2 ** -7
    assert np.all(err <= bound + slack + 1e-5)


def test_spmm_quantized_format_autodetect():
    """A format already carrying int8 vals + scales runs the dequantizing
    kernel with no precision annotation, on every impl."""
    rng = np.random.default_rng(1)
    a, blocked = make_blocked(rng, 56, 48, 0.2)
    b = jnp.asarray(rng.standard_normal((48, 64)), jnp.float32)
    qf = quantize_format(blocked)
    ref = np.asarray(ops.spmm(blocked, b, interpret=True, precision="int8"),
                     np.float32)
    for impl in SPMM_IMPLS:
        out = np.asarray(_run_spmm(impl, qf, b, None), np.float32)
        np.testing.assert_allclose(out, ref, rtol=2e-2,
                                   atol=2e-2 * np.abs(ref).max() + 1e-5)


@pytest.mark.parametrize("h", [1, 4])
def test_spmm_batched_ladder(h):
    rng = np.random.default_rng(2)
    a, blocked = make_blocked(rng, 40, 40, 0.2)
    b = jnp.asarray(rng.standard_normal((h, 40, 32)), jnp.float32)
    base = np.asarray(ops.spmm(blocked, b, interpret=True))
    np.testing.assert_array_equal(
        np.asarray(ops.spmm(blocked, b, interpret=True,
                                    precision="fp32")), base)
    out16 = ops.spmm(blocked, b, interpret=True, precision="bf16")
    assert out16.dtype == jnp.bfloat16 and out16.shape == (h, 40, 32)
    np.testing.assert_allclose(np.asarray(out16, np.float32), base,
                               rtol=2e-2, atol=2e-2 * np.abs(base).max())
    out8 = ops.spmm(blocked, b, interpret=True, precision="int8")
    err = np.abs(np.asarray(out8, np.float32) - base)
    bound = np.stack([int8_output_bound(blocked, b[i]) for i in range(h)])
    slack = np.maximum(np.abs(base), 1.0) * 2 ** -7
    assert np.all(err <= bound + slack + 1e-5)


def test_spmm_ladder_empty_windows_and_ragged_n():
    """Empty windows stay exactly zero at every precision; ragged N (not a
    multiple of n_blk) keeps the ladder."""
    rng = np.random.default_rng(3)
    a = random_sparse(rng, 48, 40, 0.3)
    a[8:24] = 0.0
    a[40:48] = 0.0
    blocked = block_format(from_dense(a, vector_size=8), k_blk=8)
    b = jnp.asarray(rng.standard_normal((40, 19)), jnp.float32)  # ragged N
    base = np.asarray(ops.spmm(blocked, b, interpret=True))
    for prec in ("fp32", "bf16", "int8"):
        out = np.asarray(ops.spmm(blocked, b, interpret=True, precision=prec),
                         np.float32)
        assert out.shape == (48, 19)
        assert np.all(out[8:24] == 0.0) and np.all(out[40:48] == 0.0)
        np.testing.assert_allclose(out, base, rtol=2e-2,
                                   atol=2e-2 * np.abs(base).max() + 1e-5)


# --------------------------------------------------- SDDMM / attention ----


@pytest.mark.parametrize("impl", ["pallas", "pallas_balanced", "blocked"])
def test_sddmm_ladder(impl):
    rng = np.random.default_rng(4)
    _, blocked = make_blocked(rng, 48, 56, 0.15)
    q = jnp.asarray(rng.standard_normal((48, 64)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((56, 64)), jnp.float32)

    def run(prec):
        kw = {"precision": prec} if prec is not None else {}
        if impl == "pallas":
            return ops.sddmm(blocked, q, k, interpret=True, **kw)
        if impl == "pallas_balanced":
            return ops.sddmm_balanced(blocked, q, k,
                                      schedule=blocked.schedule(1),
                                      interpret=True, **kw)
        from repro.core.sddmm import sddmm

        return sddmm(blocked, q, k, impl="blocked", **kw)

    base = np.asarray(run(None))
    np.testing.assert_array_equal(np.asarray(run("fp32")), base)
    out16 = run("bf16")
    assert out16.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out16, np.float32), base,
                               rtol=5e-2, atol=2e-1)
    # pallas paths reject in the cast, the core path in the registry gate —
    # both name int8
    with pytest.raises(ValueError, match="int8"):
        run("int8")


@pytest.mark.parametrize("h", [1, 4])
@pytest.mark.parametrize("impl", ["pallas_fused_attn", "pallas_staged"])
def test_attention_ladder(impl, h):
    rng = np.random.default_rng(5)
    m = 40
    _, blocked = make_blocked(rng, m, m, 0.2)
    q = jnp.asarray(rng.standard_normal((h, m, 16)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((h, m, 16)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((h, m, 16)), jnp.float32)

    def run(prec):
        kw = {"precision": prec} if prec is not None else {}
        return sparse_dispatch.dispatch("attention", impl, blocked, q, k, v,
                                        interpret=True, **kw)

    base = np.asarray(run(None))
    np.testing.assert_array_equal(np.asarray(run("fp32")), base)
    out16 = run("bf16")
    assert out16.dtype == jnp.bfloat16 and out16.shape == (h, m, 16)
    # softmax renormalizes → attention outputs are O(1); absolute tol works
    np.testing.assert_allclose(np.asarray(out16, np.float32), base,
                               rtol=5e-2, atol=5e-2)
    with pytest.raises(ValueError, match="int8 applies to SpMM"):
        run("int8")


# -------------------------------------------------------------- gradients ----


@pytest.mark.parametrize("impl", ["blocked", "pallas", "pallas_balanced"])
@pytest.mark.parametrize("precision", ["bf16", "int8"])
def test_spmm_grads_keep_master_dtypes(impl, precision):
    """Narrow forward, fp32 masters: grads come back in the operands'
    (fp32) dtypes and stay within the ladder of the fp32 gradients."""
    from repro.core.autodiff import ad_plan, spmm_ad

    rng = np.random.default_rng(6)
    a = random_sparse(rng, 40, 40, 0.2)
    fmt = from_dense(a, vector_size=8)
    b = jnp.asarray(rng.standard_normal((40, 32)), jnp.float32)

    def loss(vals, bb, plan):
        out = spmm_ad(plan, vals, bb, interpret=True)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    plan32 = ad_plan(fmt, impl=impl)
    plan = ad_plan(fmt, impl=impl, precision=precision)
    g32 = jax.grad(loss, argnums=(0, 1))(plan32.vals, b, plan32)
    g = jax.grad(loss, argnums=(0, 1))(plan.vals, b, plan)
    assert g[0].dtype == plan.vals.dtype == jnp.float32
    assert g[1].dtype == b.dtype == jnp.float32
    for got, want in zip(g, g32):
        atol = (0.08 if precision == "int8" else 0.05) \
            * max(float(jnp.abs(want).max()), 1.0)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=8e-2, atol=atol)


def test_attention_ad_bf16_and_int8_plan():
    from repro.core.autodiff import ad_plan, attention_ad

    rng = np.random.default_rng(7)
    m = 32
    a = random_sparse(rng, m, m, 0.25)
    fmt = from_dense(a, vector_size=8)
    q = jnp.asarray(rng.standard_normal((1, m, 16)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, m, 16)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, m, 16)), jnp.float32)

    def loss(q_, k_, v_, plan):
        return jnp.sum(attention_ad(plan, q_, k_, v_, interpret=True)
                       .astype(jnp.float32) ** 2)

    base = jax.grad(loss, argnums=(0, 1, 2))(
        q, k, v, ad_plan(fmt, impl="pallas"))
    for prec in ("bf16", "int8"):  # int8 plans fall back to bf16 attention
        plan = ad_plan(fmt, impl="pallas", precision=prec)
        grads = jax.grad(loss, argnums=(0, 1, 2))(q, k, v, plan)
        for got, want in zip(grads, base):
            assert got.dtype == jnp.float32
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), rtol=1e-1,
                atol=0.1 * max(float(jnp.abs(want).max()), 1.0))


# ------------------------------------------------------- dispatch gating ----


def test_dispatch_precision_gate():
    with pytest.raises(ValueError, match="does not support precision"):
        sparse_dispatch.require("spmm", "coo_segment", precision="bf16")
    with pytest.raises(ValueError, match="does not support precision"):
        sparse_dispatch.require("sddmm", "pallas", precision="int8")
    with pytest.raises(ValueError, match="does not support precision"):
        sparse_dispatch.require("attention", "pallas_fused_attn",
                                precision="int8")
    # and the capable paths resolve
    assert "int8" in sparse_dispatch.get("spmm", "pallas").precisions
    assert "bf16" in sparse_dispatch.get("attention",
                                         "pallas_fused_attn").precisions
    rng = np.random.default_rng(8)
    _, blocked = make_blocked(rng, 24, 24, 0.2)
    b = jnp.asarray(rng.standard_normal((24, 16)), jnp.float32)
    from repro.core.spmm import spmm

    with pytest.raises(ValueError, match="does not support precision"):
        spmm(blocked, b, impl="coo_segment", precision="bf16")


# ------------------------------------------------------------- sharded ----


def test_sharded_precision_ladder():
    """Sharded SpMM at bf16/int8 and attention at bf16 match the
    single-device path (child process pins the 8-device host platform)."""
    code = """
    import numpy as np
    import jax.numpy as jnp
    from repro.core import block_format, from_dense
    from repro.distributed.sparse_shard import (attention_sharded,
                                                spmm_sharded)
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
        out = np.asarray(spmm_sharded(blocked, b, mesh=mesh, interpret=True,
                                      precision=prec), np.float32)
        # psum regrouping: a bf16-output ulp of slack on top of the ladder
        np.testing.assert_allclose(out, ref, rtol=2e-2,
                                   atol=2e-2 * np.abs(ref).max() + 0.07)
    q = jnp.asarray(rng.standard_normal((2, 64, 16)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, 64, 16)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, 64, 16)), jnp.float32)
    ref = np.asarray(ops.attention(blocked, q, k, v, interpret=True,
                                   precision="bf16"), np.float32)
    out = np.asarray(attention_sharded(blocked, q, k, v, mesh=mesh,
                                       interpret=True, precision="bf16"),
                     np.float32)
    np.testing.assert_allclose(out, ref, rtol=5e-2, atol=8e-2)
    print("sharded precision ladder OK")
    """
    out = run_child(code, devices=8, timeout=120)
    assert "sharded precision ladder OK" in out


@pytest.mark.parametrize("op,precision", [
    ("spmm", "bf16"), ("spmm", "int8"), ("spmm-quantized-format", None),
    ("sddmm", "bf16"), ("attention", "bf16")])
def test_compiled_kernels_refuse_narrow_operands(op, precision):
    """Compiled (``interpret=False``) launches take fp32 only: a narrow
    request raises at trace time instead of running widened to fp32
    words.  The refusal comes before any lowering, so it shows here."""
    rng = np.random.default_rng(0)
    _, blocked = make_blocked(rng, 32, 32, 0.3)
    x = jnp.asarray(rng.standard_normal((32, 16)).astype(np.float32))
    run = {
        "spmm": lambda: ops.spmm(blocked, x, interpret=False,
                                 precision=precision),
        "spmm-quantized-format": lambda: ops.spmm(
            quantize_format(blocked), x, interpret=False),
        "sddmm": lambda: ops.sddmm(blocked, x, x, interpret=False,
                                   precision=precision),
        "attention": lambda: ops.attention(blocked, x, x, x,
                                           interpret=False,
                                           precision=precision),
    }[op]
    with pytest.raises(ValueError, match="fp32 operands only"):
        run()
