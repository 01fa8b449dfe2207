"""Transformer / SSM building blocks shared by all assigned architectures.

Pure-function style: every block is ``f(params_dict, x, cfg, ...)`` with
params as plain pytrees, so pjit/shard_map sharding rules can be attached
by path (see ``repro.distributed.sharding``).

Numerics policy: parameters and activations in ``cfg.dtype`` (bf16 for the
large configs), normalisation / softmax / attention statistics / router in
f32, MXU accumulation via ``preferred_element_type``.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

f32 = jnp.float32


# ------------------------------------------------------------------ norms --


def rms_norm(x: jax.Array, w: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(f32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w


# ------------------------------------------------------------------- rope --


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding. x: (..., S, H, D); positions: (S,) or (..., S).

    Pass 1-D positions whenever they are batch-uniform (training/prefill):
    the cos/sin tables are then (S, half) instead of a replicated
    (B, S, half) — a ~B× reduction of table traffic per layer.
    """
    d = x.shape[-1]
    half = d // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=f32) / half)
    angles = positions[..., :, None].astype(f32) * freqs  # (..., S, half)
    cos = jnp.cos(angles)[..., :, None, :]                # (..., S, 1, half)
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = x[..., :half].astype(f32), x[..., half:].astype(f32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# -------------------------------------------------------------- attention --


def _repeat_kv(k: jax.Array, groups: int) -> jax.Array:
    if groups == 1:
        return k
    b, s, h, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, h, groups, d)
                            ).reshape(b, s, h * groups, d)


def full_attention(q, k, v, *, causal: bool, q_offset: int = 0,
                   kv_len: Optional[jax.Array] = None) -> jax.Array:
    """Materialized-scores attention (short sequences / decode), GQA-native.

    q: (B, Sq, Hkv, G, D); k, v: (B, Sk, Hkv, D) — K/V are NEVER
    head-repeated: the grouped einsum keeps the KV sequence dim's sharding
    intact (a broadcast+reshape repeat forces GSPMD to all-gather the
    whole cache — 2.1 GB/layer observed on the 76B decode cell).
    ``kv_len``: optional (B,) valid cache length mask for decode.
    """
    d = q.shape[-1]
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", q, k,
                        preferred_element_type=f32) / math.sqrt(d)
    sq, sk = q.shape[1], k.shape[1]
    if causal:
        qpos = q_offset + jnp.arange(sq)
        kpos = jnp.arange(sk)
        mask = kpos[None, :] <= qpos[:, None]
        scores = jnp.where(mask[None, None, None], scores, -1e30)
    if kv_len is not None:
        valid = jnp.arange(sk)[None, :] < kv_len[:, None]
        scores = jnp.where(valid[:, None, None, None, :], scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v.dtype), v,
                      preferred_element_type=f32).astype(v.dtype)


def chunked_attention(q, k, v, *, causal: bool, q_offset: int = 0,
                      kv_block: int = 1024, unroll: bool = False) -> jax.Array:
    """Flash-style online-softmax attention: never materializes (Sq, Sk).

    Scans over KV blocks carrying running (acc, max, denom); O(Sq·kv_block)
    live memory.  Used for long-sequence training/prefill.

    ``unroll=True`` fully unrolls the KV scan — used by the dry-run's
    accounting compile so XLA cost analysis sees every block (while-loop
    bodies are otherwise counted once, launch/cells.py).
    """
    b, sq, h, g, d = q.shape        # GQA-native: h = kv heads, g = groups
    dv = v.shape[-1]  # may differ from d (MLA: qk 192, v 128)
    sk = k.shape[1]
    nblk = -(-sk // kv_block)
    pad = nblk * kv_block - sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kb = k.reshape(b, nblk, kv_block, h, d)
    vb = v.reshape(b, nblk, kv_block, h, dv)
    scale = 1.0 / math.sqrt(d)
    qpos = q_offset + jnp.arange(sq)

    def body(carry, inp):
        acc, m, l = carry
        kv_i, (kc, vc) = inp
        scores = jnp.einsum("bqhgd,bkhd->bhgqk", q, kc,
                            preferred_element_type=f32) * scale
        kpos = kv_i * kv_block + jnp.arange(kv_block)
        mask = kpos[None, :] < sk - 0  # padding mask
        if causal:
            mask = mask & (kpos[None, :] <= qpos[:, None])
        else:
            mask = jnp.broadcast_to(mask, (sq, kv_block))
        scores = jnp.where(mask[None, None, None], scores, -1e30)
        m_new = jnp.maximum(m, scores.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(scores - m_new[..., None])
        l_new = l * alpha + p.sum(axis=-1)
        pv = jnp.einsum("bhgqk,bkhd->bhgqd", p.astype(vc.dtype), vc,
                        preferred_element_type=f32)
        acc_new = acc * alpha[..., None] + pv
        return (acc_new, m_new, l_new), None

    acc0 = jnp.zeros((b, h, g, sq, dv), f32)
    m0 = jnp.full((b, h, g, sq), -jnp.inf, f32)
    l0 = jnp.zeros((b, h, g, sq), f32)
    (acc, m, l), _ = jax.lax.scan(
        body, (acc0, m0, l0),
        (jnp.arange(nblk), (kb.swapaxes(0, 1), vb.swapaxes(0, 1))),
        unroll=nblk if unroll else 1,
    )
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 3, 1, 2, 4).astype(q.dtype)  # (B, Sq, H, G, Dv)


def attention(q, k, v, *, causal: bool = True, q_offset: int = 0,
              kv_len=None, impl: str = "auto", kv_block: int = 1024,
              unroll: bool = False):
    """Dispatch: GQA-native grouping + full vs chunked score computation.

    q: (B, S, Hq, D); k, v: (B, Sk, Hkv, D).  Queries fold into
    (B, S, Hkv, G, D); K/V are used as-is (never head-repeated — see
    full_attention).
    """
    b, sq, hq, d = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    qg = q.reshape(b, sq, hkv, g, d)
    if impl == "auto":
        impl = "chunked" if (sq > 2048 and kv_len is None) else "full"
    if impl == "chunked":
        out = chunked_attention(qg, k, v, causal=causal, q_offset=q_offset,
                                kv_block=kv_block, unroll=unroll)
    else:
        out = full_attention(qg, k, v, causal=causal, q_offset=q_offset,
                             kv_len=kv_len)
    return out.reshape(b, sq, hq, -1)


# ------------------------------------------------- FlashSparse attention --


def sparse_attention(pattern, q: jax.Array, k: jax.Array, v: jax.Array, *,
                     scale=None, impl: Optional[str] = None,
                     interpret: Optional[bool] = None) -> jax.Array:
    """Block-sparse attention on the FlashSparse pipeline, all in ME-BCRS
    blocked layout.

    ``q``/``k``/``v``: (S, D) single-head or (H, S, D) per-head batch —
    the pattern (local window + strided global, etc.) is shared across
    heads, the scores/probabilities are per-head.  ``scale`` defaults to
    ``1/sqrt(D)`` and may be a learned traced scalar.

    ``pattern`` is an :class:`~repro.core.autodiff.ADPlan` or a bare
    :class:`BlockedMEBCRS`.  With an ADPlan and a Pallas impl this runs the
    **single-pass fused megakernel** (``kernels/attention_pallas.py``):
    per-window SDDMM scores in VMEM scratch, row-segment online softmax,
    SpMM accumulation against V — one ``(H, W)`` grid launch for any head
    count and no HBM-resident scores/probs tensor.  Gradients flow through
    the FlashAttention-style recompute backward (dispatched transpose-
    SpMM/SDDMM duality).  Every other case takes the staged 3-dispatch
    pipeline, kept as :func:`sparse_attention_staged` for parity tests and
    the BENCH_attn traffic comparison.
    """
    from repro.core.autodiff import ADPlan, attention_ad

    if isinstance(pattern, ADPlan):
        return attention_ad(pattern, q, k, v, scale=scale, impl=impl,
                            interpret=interpret)
    return sparse_attention_staged(pattern, q, k, v, scale=scale, impl=impl,
                                   interpret=interpret)


def sparse_attention_staged(pattern, q: jax.Array, k: jax.Array,
                            v: jax.Array, *, scale=None,
                            impl: Optional[str] = None,
                            interpret: Optional[bool] = None) -> jax.Array:
    """3-dispatch block-sparse attention: SDDMM → sparse softmax → SpMM.

    The (NNZP, V) score tensor round-trips HBM between the dispatched ops
    — the baseline :func:`sparse_attention` fuses away.  With an
    :class:`~repro.core.autodiff.ADPlan` every stage is differentiable for
    any registry impl; a bare :class:`BlockedMEBCRS` supports the natively
    differentiable XLA ``blocked`` impl only.
    """
    from repro.core import with_values
    from repro.core.autodiff import ADPlan, sddmm_ad, spmm_ad
    from repro.core import dispatch as sparse_dispatch
    from repro.core.softmax import sparse_softmax

    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if isinstance(pattern, ADPlan):
        scores = sddmm_ad(pattern, q, k, impl=impl, interpret=interpret)
        probs = sparse_softmax(pattern.fwd, scores * scale)
        return spmm_ad(pattern, probs.astype(v.dtype), v, impl=impl,
                       interpret=interpret)

    impl = impl or "blocked"
    if impl != "blocked":
        # Pallas impls differentiate only via the plan; fail here with
        # the remedy, not inside grad tracing.
        raise ValueError(
            f"sparse_attention with a bare BlockedMEBCRS supports only "
            f"impl='blocked'; build an ADPlan (ad_plan(fmt, impl={impl!r})) "
            f"for the Pallas paths")
    sparse_dispatch.require("sddmm", impl, differentiable=True)

    def one_head(qh, kh, vh):
        scores = sparse_dispatch.dispatch("sddmm", impl, pattern, qh, kh,
                                          k_blk=pattern.k_blk,
                                          interpret=interpret)
        probs = sparse_softmax(pattern, scores * scale)
        return sparse_dispatch.dispatch(
            "spmm", impl, with_values(pattern, probs.astype(vh.dtype)), vh,
            k_blk=pattern.k_blk, interpret=interpret)

    if q.ndim == 2:
        return one_head(q, k, v)
    return jnp.stack([one_head(q[i], k[i], v[i]) for i in range(q.shape[0])])


# -------------------------------------------------------------- GQA block --


def init_gqa(key, cfg) -> Dict:
    d, hd = cfg.d_model, cfg.head_dim
    kq, kk, kv, ko, kn1, kn2 = jax.random.split(key, 6)
    s = d ** -0.5
    p = {
        "wq": (jax.random.normal(kq, (d, cfg.n_heads * hd)) * s).astype(cfg.dtype),
        "wk": (jax.random.normal(kk, (d, cfg.n_kv_heads * hd)) * s).astype(cfg.dtype),
        "wv": (jax.random.normal(kv, (d, cfg.n_kv_heads * hd)) * s).astype(cfg.dtype),
        "wo": (jax.random.normal(ko, (cfg.n_heads * hd, d)) * s).astype(cfg.dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), cfg.dtype)
        p["k_norm"] = jnp.ones((hd,), cfg.dtype)
    return p


def gqa_project_qkv(p, x, cfg, positions):
    b, s, _ = x.shape
    hd = cfg.head_dim
    q = (x @ p["wq"]).reshape(b, s, cfg.n_heads, hd)
    k = (x @ p["wk"]).reshape(b, s, cfg.n_kv_heads, hd)
    v = (x @ p["wv"]).reshape(b, s, cfg.n_kv_heads, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.rmsnorm_eps)
        k = rms_norm(k, p["k_norm"], cfg.rmsnorm_eps)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def gqa_attn(p, x, cfg, *, causal=True, attn_impl="auto") -> jax.Array:
    b, s, _ = x.shape
    positions = jnp.arange(s)  # batch-uniform → 1-D rope tables
    q, k, v = gqa_project_qkv(p, x, cfg, positions)
    out = attention(q, k, v, causal=causal, impl=attn_impl,
                    unroll=getattr(cfg, "attn_unroll", False))
    return out.reshape(b, s, -1) @ p["wo"]


def gqa_decode(p, x, cache, pos, cfg) -> Tuple[jax.Array, Dict]:
    """One-token decode. cache: {"k","v": (B, S_max, Hkv, D)}; pos: (B,)."""
    b, s, _ = x.shape  # s == 1
    positions = pos[:, None] + jnp.arange(s)[None]
    q, k, v = gqa_project_qkv(p, x, cfg, positions)
    knew = _scatter_time(cache["k"], k, pos)
    vnew = _scatter_time(cache["v"], v, pos)
    out = attention(q, knew.astype(q.dtype), vnew.astype(q.dtype),
                    causal=False, kv_len=pos + 1, impl="full")
    y = out.reshape(b, s, -1) @ p["wo"]
    return y, {"k": knew, "v": vnew}


def _scatter_time(buf: jax.Array, new: jax.Array, pos: jax.Array) -> jax.Array:
    """Write ``new`` (B, 1, ...) into ``buf`` (B, S, ...) at per-batch pos."""
    oh = jax.nn.one_hot(pos, buf.shape[1], dtype=buf.dtype)  # (B, S)
    oh = oh.reshape(oh.shape + (1,) * (buf.ndim - 2))
    return buf * (1 - oh) + oh * new.astype(buf.dtype)


# -------------------------------------------------------------- MLA block --


def init_mla(key, cfg) -> Dict:
    d = cfg.d_model
    h = cfg.n_heads
    qk = cfg.qk_nope_dim + cfg.qk_rope_dim
    keys = jax.random.split(key, 8)
    s = d ** -0.5

    def mk(k, shape, fan):
        return (jax.random.normal(k, shape) * fan ** -0.5).astype(cfg.dtype)

    return {
        "w_dq": mk(keys[0], (d, cfg.q_lora_rank), d),
        "q_norm": jnp.ones((cfg.q_lora_rank,), cfg.dtype),
        "w_uq": mk(keys[1], (cfg.q_lora_rank, h * qk), cfg.q_lora_rank),
        "w_dkv": mk(keys[2], (d, cfg.kv_lora_rank + cfg.qk_rope_dim), d),
        "kv_norm": jnp.ones((cfg.kv_lora_rank,), cfg.dtype),
        "w_uk": mk(keys[3], (cfg.kv_lora_rank, h * cfg.qk_nope_dim), cfg.kv_lora_rank),
        "w_uv": mk(keys[4], (cfg.kv_lora_rank, h * cfg.v_head_dim), cfg.kv_lora_rank),
        "wo": mk(keys[5], (h * cfg.v_head_dim, d), h * cfg.v_head_dim),
    }


def _mla_q(p, x, cfg, positions):
    b, s, _ = x.shape
    h = cfg.n_heads
    cq = rms_norm(x @ p["w_dq"], p["q_norm"], cfg.rmsnorm_eps)
    q = (cq @ p["w_uq"]).reshape(b, s, h, cfg.qk_nope_dim + cfg.qk_rope_dim)
    q_nope, q_rope = q[..., : cfg.qk_nope_dim], q[..., cfg.qk_nope_dim:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    return q_nope, q_rope


def _mla_latent(p, x, cfg, positions):
    ckv_full = x @ p["w_dkv"]
    ckv = rms_norm(ckv_full[..., : cfg.kv_lora_rank], p["kv_norm"],
                   cfg.rmsnorm_eps)
    k_rope = ckv_full[..., cfg.kv_lora_rank:][:, :, None, :]  # (B,S,1,rope)
    k_rope = apply_rope(k_rope, positions, cfg.rope_theta)[:, :, 0]
    return ckv, k_rope


def mla_attn(p, x, cfg, *, causal=True, attn_impl="auto") -> jax.Array:
    """Training/prefill MLA: decompress K/V per token (standard form)."""
    b, s, _ = x.shape
    h = cfg.n_heads
    positions = jnp.arange(s)  # batch-uniform → 1-D rope tables
    q_nope, q_rope = _mla_q(p, x, cfg, positions)
    ckv, k_rope = _mla_latent(p, x, cfg, positions)
    k_nope = (ckv @ p["w_uk"]).reshape(b, s, h, cfg.qk_nope_dim)
    v = (ckv @ p["w_uv"]).reshape(b, s, h, cfg.v_head_dim)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                  (b, s, h, cfg.qk_rope_dim))], axis=-1)
    out = attention(q, k, v, causal=causal, impl=attn_impl,
                    unroll=getattr(cfg, "attn_unroll", False))
    return out.reshape(b, s, -1) @ p["wo"]


def mla_decode(p, x, cache, pos, cfg) -> Tuple[jax.Array, Dict]:
    """Absorbed-matmul MLA decode over the **latent** cache.

    cache: {"ckv": (B, S, kv_lora), "k_rope": (B, S, rope)}; pos: (B,).
    Attention runs in latent space: w_uk is absorbed into the query and
    w_uv into the output, so per step cost is O(S · kv_lora) instead of
    O(S · H · head_dim) — DeepSeek-V3's deployment optimization, and the
    reason the cache is only (kv_lora + rope) wide.
    """
    b, s, _ = x.shape
    h = cfg.n_heads
    positions = pos[:, None] + jnp.arange(s)[None]
    q_nope, q_rope = _mla_q(p, x, cfg, positions)          # (B,1,H,·)
    ckv_new, k_rope_new = _mla_latent(p, x, cfg, positions)

    ckv = _scatter_time(cache["ckv"], ckv_new, pos)
    k_rope = _scatter_time(cache["k_rope"], k_rope_new, pos)

    w_uk = p["w_uk"].reshape(cfg.kv_lora_rank, h, cfg.qk_nope_dim)
    q_lat = jnp.einsum("bqhd,chd->bqhc", q_nope.astype(f32),
                       w_uk.astype(f32))                   # absorb W_uk
    scores = (
        jnp.einsum("bqhc,btc->bhqt", q_lat, ckv.astype(f32))
        + jnp.einsum("bqhr,btr->bhqt", q_rope.astype(f32),
                     k_rope.astype(f32))
    ) / math.sqrt(cfg.qk_nope_dim + cfg.qk_rope_dim)
    valid = jnp.arange(ckv.shape[1])[None, :] < (pos + 1)[:, None]
    scores = jnp.where(valid[:, None, None, :], scores, -1e30)
    pr = jax.nn.softmax(scores, axis=-1)
    out_lat = jnp.einsum("bhqt,btc->bqhc", pr, ckv.astype(f32))
    w_uv = p["w_uv"].reshape(cfg.kv_lora_rank, h, cfg.v_head_dim)
    out = jnp.einsum("bqhc,chd->bqhd", out_lat, w_uv.astype(f32))
    y = out.reshape(b, s, -1).astype(x.dtype) @ p["wo"]
    return y, {"ckv": ckv, "k_rope": k_rope}


# -------------------------------------------------------------- MLP / MoE --


def init_mlp(key, cfg, d_ff=None) -> Dict:
    d = cfg.d_model
    d_ff = d_ff or cfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": (jax.random.normal(k1, (d, d_ff)) * d ** -0.5).astype(cfg.dtype),
        "w_up": (jax.random.normal(k2, (d, d_ff)) * d ** -0.5).astype(cfg.dtype),
        "w_down": (jax.random.normal(k3, (d_ff, d)) * d_ff ** -0.5).astype(cfg.dtype),
    }


def mlp(p, x) -> jax.Array:
    return (jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]


def init_moe(key, cfg) -> Dict:
    d = cfg.d_model
    e = cfg.moe_experts
    dff = cfg.moe_d_ff or cfg.d_ff
    kr, k1, k2, k3, ks = jax.random.split(key, 5)
    p = {
        "router": (jax.random.normal(kr, (d, e)) * d ** -0.5).astype(f32),
        "w_gate": (jax.random.normal(k1, (e, d, dff)) * d ** -0.5).astype(cfg.dtype),
        "w_up": (jax.random.normal(k2, (e, d, dff)) * d ** -0.5).astype(cfg.dtype),
        "w_down": (jax.random.normal(k3, (e, dff, d)) * dff ** -0.5).astype(cfg.dtype),
    }
    if cfg.moe_shared_experts:
        p["shared"] = init_mlp(ks, cfg, d_ff=dff * cfg.moe_shared_experts)
    return p


def moe_ffn(p, x: jax.Array, cfg) -> Tuple[jax.Array, jax.Array]:
    """Token-choice top-k MoE with sort-based grouped dispatch.

    The dispatch is the same grouped-GEMM data flow as the FlashSparse SpMM
    kernel (group id ↔ output window, capacity blocks ↔ K-blocks); on TPU
    both reduce to contiguous gathers + batched MXU matmuls.

    Two execution paths:
      * default — single global sort/scatter; GSPMD partitions it (and, as
        the dry-run shows, replicates the (T·k, d) dispatch buffers per
        device at pod scale — the recorded baseline);
      * ``cfg.moe_ep`` — expert-parallel shard_map: local routing on each
        token shard, per-shard expert capacity, local grouped GEMM on the
        expert shard, one combine psum over the model axis per layer.

    x: (B, S, D) → (out, aux_loss).
    """
    if cfg.moe_ep:
        from repro.distributed.ctx import current_mesh

        mesh = current_mesh()
        if mesh is not None and mesh.shape.get("model", 1) > 1 \
                and cfg.moe_experts % mesh.shape["model"] == 0:
            return moe_ffn_ep(p, x, cfg, mesh)
    b, s, d = x.shape
    t = b * s
    e, k = cfg.moe_experts, cfg.moe_top_k
    xt = x.reshape(t, d)

    logits = (xt.astype(f32) @ p["router"]).astype(f32)       # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, eidx = jax.lax.top_k(probs, k)                     # (T, K)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

    # load-balancing aux loss (Switch-style)
    me = probs.mean(axis=0)
    ce = jnp.zeros((e,), f32).at[eidx.reshape(-1)].add(1.0) / (t * k)
    aux = e * jnp.sum(me * ce)

    cap = max(int(t * k / e * cfg.capacity_factor), 8)

    flat_e = eidx.reshape(-1)                                 # (T*K,)
    order = jnp.argsort(flat_e)
    sorted_e = flat_e[order]
    starts = jnp.searchsorted(sorted_e, jnp.arange(e))
    pos_in_e = jnp.arange(t * k) - starts[sorted_e]
    keep = pos_in_e < cap
    slot = jnp.where(keep, sorted_e * cap + pos_in_e, e * cap)  # drop → sentinel

    # pad the slot buffer past the sentinel to a shardable row count
    # (e·cap+1 is odd → would replicate per device); constraints keep the
    # dispatch buffers distributed so GSPMD lowers the token shuffle to
    # collectives instead of replicating (T·K, d) per device.
    from repro.distributed.ctx import constrain

    rows = e * cap + max(e, 256)
    token_of = order // k
    xd = constrain(jnp.take(xt, token_of, axis=0), "act_batch")   # (T*K, d)
    xbuf = jnp.zeros((rows, d), x.dtype).at[slot].set(xd)
    xg = constrain(xbuf[: e * cap].reshape(e, cap, d), "expert")
    h = jnp.einsum("ecd,edf->ecf", xg, p["w_gate"],
                   preferred_element_type=f32).astype(x.dtype)
    u = jnp.einsum("ecd,edf->ecf", xg, p["w_up"],
                   preferred_element_type=f32).astype(x.dtype)
    yg = jnp.einsum("ecf,efd->ecd", jax.nn.silu(h) * u, p["w_down"],
                    preferred_element_type=f32).astype(x.dtype)
    yg = constrain(yg, "expert")

    ybuf = yg.reshape(e * cap, d)
    y_tok = jnp.where(keep[:, None], ybuf[jnp.clip(slot, 0, e * cap - 1)], 0.0)
    y_tok = constrain(y_tok, "act_batch")
    g_tok = gates.reshape(-1)[order][:, None].astype(x.dtype)
    out = jnp.zeros((t, d), x.dtype).at[token_of].add(y_tok * g_tok)
    out = constrain(out, "act_batch")

    if cfg.moe_shared_experts:
        out = out + mlp(p["shared"], xt)
    return out.reshape(b, s, d), aux


def _moe_local_dispatch(xt, gates, eidx, *, e_loc, j0, e, k, cap_loc, d):
    """Group this shard's tokens by LOCAL expert id (same sort trick as the
    global path, restricted to experts [j0, j0+e_loc))."""
    t_loc = xt.shape[0]
    flat_e = eidx.reshape(-1)
    order = jnp.argsort(flat_e)
    sorted_e = flat_e[order]
    starts = jnp.searchsorted(sorted_e, jnp.arange(e))
    pos_in_e = jnp.arange(t_loc * k) - starts[sorted_e]
    local = (sorted_e >= j0) & (sorted_e < j0 + e_loc)
    keep = (pos_in_e < cap_loc) & local
    slot = jnp.where(keep, (sorted_e - j0) * cap_loc + pos_in_e,
                     e_loc * cap_loc)
    token_of = order // k
    rows = e_loc * cap_loc + 8
    xbuf = jnp.zeros((rows, d), xt.dtype).at[slot].set(
        jnp.take(xt, token_of, axis=0))
    xg = xbuf[: e_loc * cap_loc].reshape(e_loc, cap_loc, d)
    return xg, slot, keep, token_of, order


def moe_ffn_ep(p, x: jax.Array, cfg, mesh) -> Tuple[jax.Array, jax.Array]:
    """Expert-parallel MoE (DESIGN.md §6, EP over the "model" axis).

    Device (i, j) routes token shard i locally and computes only its
    e/|model| experts; a single psum over "model" combines the top-k
    contributions.  FSDP'd expert weights are all-gathered over "data"
    inside the shard (ZeRO-3 semantics preserved: backward turns the
    gather into a reduce-scatter of expert grads).
    """
    from jax.sharding import PartitionSpec as P

    b, s, d = x.shape
    e, k = cfg.moe_experts, cfg.moe_top_k
    n_model = mesh.shape["model"]
    e_loc = e // n_model
    token_axes = tuple(a for a in ("pod", "data")
                       if a in mesh.shape and mesh.shape[a] > 1)
    n_tok_shards = 1
    for a in token_axes:
        n_tok_shards *= mesh.shape[a]
    if b % max(n_tok_shards, 1):
        token_axes = ()
        n_tok_shards = 1
    t_loc = (b // n_tok_shards) * s
    cap_loc = max(int(t_loc * k / e * cfg.capacity_factor), 4)

    data_ax = "data" if "data" in mesh.shape and mesh.shape["data"] > 1 else None
    batch_spec = token_axes[0] if len(token_axes) == 1 else (
        token_axes if token_axes else None)

    def body(x_loc, router, wg, wu, wd):
        if data_ax:  # FSDP gather of this shard's expert weights
            wg = jax.lax.all_gather(wg, data_ax, axis=1, tiled=True)
            wu = jax.lax.all_gather(wu, data_ax, axis=1, tiled=True)
            wd = jax.lax.all_gather(wd, data_ax, axis=2, tiled=True)
        xt = x_loc.reshape(-1, d)
        logits = (xt.astype(f32) @ router).astype(f32)        # (T_loc, E)
        probs = jax.nn.softmax(logits, axis=-1)
        gates, eidx = jax.lax.top_k(probs, k)
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

        me = probs.mean(axis=0)
        ce = jnp.zeros((e,), f32).at[eidx.reshape(-1)].add(1.0) / (xt.shape[0] * k)
        if token_axes:  # global statistics before the product — exact
            me = jax.lax.pmean(me, token_axes)
            ce = jax.lax.pmean(ce, token_axes)
        aux = e * jnp.sum(me * ce)

        j0 = jax.lax.axis_index("model") * e_loc
        xg, slot, keep, token_of, order = _moe_local_dispatch(
            xt, gates, eidx, e_loc=e_loc, j0=j0, e=e, k=k,
            cap_loc=cap_loc, d=d)
        h = jnp.einsum("ecd,edf->ecf", xg, wg,
                       preferred_element_type=f32).astype(xt.dtype)
        u = jnp.einsum("ecd,edf->ecf", xg, wu,
                       preferred_element_type=f32).astype(xt.dtype)
        yg = jnp.einsum("ecf,efd->ecd", jax.nn.silu(h) * u, wd,
                        preferred_element_type=f32).astype(xt.dtype)

        ybuf = yg.reshape(e_loc * cap_loc, d)
        y_tok = jnp.where(keep[:, None],
                          jnp.take(ybuf, jnp.clip(slot, 0, e_loc * cap_loc - 1),
                                   axis=0), 0.0)
        g_tok = gates.reshape(-1)[order][:, None].astype(xt.dtype)
        part = jnp.zeros((xt.shape[0], d), f32).at[token_of].add(
            (y_tok * g_tok).astype(f32))
        out = jax.lax.psum(part, "model").astype(x_loc.dtype)
        return out.reshape(x_loc.shape), aux

    out, aux = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(batch_spec, None, None), P(None, None),
                  P("model", "data", None), P("model", "data", None),
                  P("model", None, "data")),
        out_specs=(P(batch_spec, None, None), P()),
        check_vma=False,
    )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])

    if cfg.moe_shared_experts:
        out = out + mlp(p["shared"], x.reshape(-1, d)).reshape(x.shape)
    return out, aux


# ------------------------------------------------------------- Mamba2 SSD --


def init_mamba2(key, cfg) -> Dict:
    d = cfg.d_model
    d_inner = cfg.ssm_expand * d
    n_heads = d_inner // cfg.ssm_headdim
    g, n = cfg.ssm_ngroups, cfg.ssm_state
    conv_dim = d_inner + 2 * g * n
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "in_proj": (jax.random.normal(
            k1, (d, 2 * d_inner + 2 * g * n + n_heads)) * d ** -0.5
        ).astype(cfg.dtype),
        "conv_w": (jax.random.normal(k2, (cfg.conv_width, conv_dim)) * 0.1
                   ).astype(cfg.dtype),
        "conv_b": jnp.zeros((conv_dim,), cfg.dtype),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, n_heads)).astype(f32),
        "D": jnp.ones((n_heads,), f32),
        "dt_bias": jnp.zeros((n_heads,), f32),
        "norm_w": jnp.ones((d_inner,), cfg.dtype),
        "out_proj": (jax.random.normal(k4, (d_inner, d)) * d_inner ** -0.5
                     ).astype(cfg.dtype),
    }


def _segsum(a: jax.Array) -> jax.Array:
    """Lower-triangular pairwise cumulative sums: out[..., i, j] = Σ_{j<k<=i} a_k."""
    q = a.shape[-1]
    cs = jnp.cumsum(a, axis=-1)
    seg = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((q, q), bool), 0)
    return jnp.where(mask, seg, -jnp.inf)


def ssd_scan(x, da, bm, cm, chunk: int, init_state=None):
    """Chunked SSD (Mamba-2, state-space duality form).

    x:  (B, L, H, P) inputs (already multiplied by dt)
    da: (B, L, H)    discretized decay dt·A (negative)
    bm: (B, L, G, N) input projections;  cm: (B, L, G, N) output projections
    Returns (y (B, L, H, P), final_state (B, H, P, N)).
    """
    b, l, h, pdim = x.shape
    g, n = bm.shape[2], bm.shape[3]
    hpg = h // g
    nc = l // chunk

    xc = x.reshape(b, nc, chunk, h, pdim)
    dac = da.reshape(b, nc, chunk, h).transpose(0, 3, 1, 2)     # (B,H,C,Q)
    bmc = bm.reshape(b, nc, chunk, g, n)
    cmc = cm.reshape(b, nc, chunk, g, n)

    # broadcast groups → heads
    bmh = jnp.repeat(bmc, hpg, axis=3)                          # (B,C,Q,H,N)
    cmh = jnp.repeat(cmc, hpg, axis=3)

    da_cs = jnp.cumsum(dac, axis=-1)                            # (B,H,C,Q)
    lmat = jnp.exp(_segsum(dac))                                # (B,H,C,Q,Q)

    # 1) intra-chunk (diagonal blocks)
    scores = jnp.einsum("bcqhn,bckhn->bhcqk", cmh.astype(f32), bmh.astype(f32))
    y_diag = jnp.einsum("bhcqk,bhcqk,bckhp->bcqhp",
                        scores, lmat, xc.astype(f32))

    # 2) chunk states
    decay_states = jnp.exp(da_cs[..., -1:] - da_cs)             # (B,H,C,Q)
    states = jnp.einsum("bckhn,bhck,bckhp->bchpn",
                        bmh.astype(f32), decay_states, xc.astype(f32))

    # 3) inter-chunk recurrence over chunk states
    chunk_decay = jnp.exp(da_cs[..., -1])                       # (B,H,C)

    def scan_fn(carry, inp):
        st, dec = inp                                           # (B,H,P,N), (B,H)
        new = carry * dec[..., None, None] + st
        return new, carry                                       # emit state *before* chunk

    st0 = (init_state if init_state is not None
           else jnp.zeros((b, h, pdim, n), f32))
    final, prior = jax.lax.scan(
        scan_fn, st0,
        (states.transpose(1, 0, 2, 3, 4).astype(f32),
         chunk_decay.transpose(2, 0, 1)),
    )
    prior = prior.transpose(1, 0, 2, 3, 4)                      # (B,C,H,P,N)

    # 4) state → output within chunk
    state_decay = jnp.exp(da_cs)                                # (B,H,C,Q)
    y_off = jnp.einsum("bcqhn,bchpn,bhcq->bcqhp",
                       cmh.astype(f32), prior, state_decay)

    y = (y_diag + y_off).reshape(b, l, h, pdim)
    return y, final


def _causal_conv(xbc: jax.Array, w: jax.Array, bias: jax.Array) -> jax.Array:
    """Depthwise causal conv1d: xbc (B, L, C), w (W, C)."""
    wsz = w.shape[0]
    xp = jnp.pad(xbc, ((0, 0), (wsz - 1, 0), (0, 0)))
    out = sum(xp[:, i : i + xbc.shape[1]] * w[i] for i in range(wsz))
    return out + bias


def mamba2_block(p, x, cfg, *, chunk: int = 128) -> jax.Array:
    """Full-sequence Mamba-2 block (training / prefill)."""
    b, l, d = x.shape
    d_inner = cfg.ssm_expand * d
    g, n = cfg.ssm_ngroups, cfg.ssm_state
    h = d_inner // cfg.ssm_headdim
    pdim = cfg.ssm_headdim

    zxbcdt = x @ p["in_proj"]
    z, xbc, dt = jnp.split(zxbcdt, [d_inner, 2 * d_inner + 2 * g * n], axis=-1)
    xbc = jax.nn.silu(_causal_conv(xbc, p["conv_w"], p["conv_b"]))
    xs, bm, cm = jnp.split(xbc, [d_inner, d_inner + g * n], axis=-1)

    dt = jax.nn.softplus(dt.astype(f32) + p["dt_bias"])         # (B,L,H)
    a = -jnp.exp(p["A_log"])                                    # (H,)
    da = dt * a                                                 # (B,L,H)

    xh_raw = xs.reshape(b, l, h, pdim)
    xh = xh_raw * dt[..., None].astype(xh_raw.dtype)  # fold dt into the input
    pad = (-l) % chunk
    if pad:
        xh = jnp.pad(xh, ((0, 0), (0, pad), (0, 0), (0, 0)))
        da = jnp.pad(da, ((0, 0), (0, pad), (0, 0)))
        bm = jnp.pad(bm, ((0, 0), (0, pad), (0, 0)))
        cm = jnp.pad(cm, ((0, 0), (0, pad), (0, 0)))
    y, _ = ssd_scan(
        xh, da,
        bm.reshape(b, -1, g, n), cm.reshape(b, -1, g, n), chunk)
    y = y[:, :l]
    y = y + p["D"][None, None, :, None] * xh_raw.astype(f32)  # skip uses raw x
    y = y.reshape(b, l, d_inner).astype(x.dtype)
    y = rms_norm(y * jax.nn.silu(z), p["norm_w"], cfg.rmsnorm_eps)
    return y @ p["out_proj"]


def mamba2_decode(p, x, cache, cfg) -> Tuple[jax.Array, Dict]:
    """Single-token recurrent step.

    cache: {"conv": (B, W-1, conv_dim), "ssm": (B, H, P, N)}.
    O(1) in sequence length — why SSMs run the long_500k shape.
    """
    b, s, d = x.shape  # s == 1
    d_inner = cfg.ssm_expand * d
    g, n = cfg.ssm_ngroups, cfg.ssm_state
    h = d_inner // cfg.ssm_headdim
    pdim = cfg.ssm_headdim

    zxbcdt = x[:, 0] @ p["in_proj"]
    z, xbc, dt = jnp.split(zxbcdt, [d_inner, 2 * d_inner + 2 * g * n], axis=-1)

    conv_buf = jnp.concatenate([cache["conv"], xbc[:, None]], axis=1)
    xbc = jax.nn.silu(
        jnp.einsum("bwc,wc->bc", conv_buf, p["conv_w"]) + p["conv_b"])
    conv_new = conv_buf[:, 1:]

    xs, bm, cm = jnp.split(xbc, [d_inner, d_inner + g * n], axis=-1)
    dt = jax.nn.softplus(dt.astype(f32) + p["dt_bias"])          # (B,H)
    a = -jnp.exp(p["A_log"])
    da = jnp.exp(dt * a)                                         # (B,H)

    xh = xs.reshape(b, h, pdim).astype(f32)
    bmh = jnp.repeat(bm.reshape(b, g, n), h // g, axis=1).astype(f32)
    cmh = jnp.repeat(cm.reshape(b, g, n), h // g, axis=1).astype(f32)

    ssm = cache["ssm"] * da[..., None, None] + \
        dt[..., None, None] * xh[..., None] * bmh[:, :, None, :]
    y = jnp.einsum("bhpn,bhn->bhp", ssm, cmh) + p["D"][None, :, None] * xh
    y = y.reshape(b, d_inner).astype(x.dtype)
    y = rms_norm(y * jax.nn.silu(z), p["norm_w"], cfg.rmsnorm_eps)
    out = (y @ p["out_proj"])[:, None]
    return out, {"conv": conv_new, "ssm": ssm}
