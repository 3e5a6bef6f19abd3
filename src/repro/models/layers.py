"""Model layer primitives: norms, rotary, attention (flash-style chunked,
GQA, windowed, cross), dense/MoE MLPs, Mamba2 SSD mixer.

All functions are pure; parameters are plain dicts of arrays.  Shapes use
the convention  B=batch, S=sequence, H=query heads, K=kv heads, D=d_model,
F=d_ff, E=experts, N=ssm state, P(ssd)=ssd head dim.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import (ATTN, ATTN_CHUNKED, CROSS_ATTN, DENSE, MAMBA2,
                                MOE, NONE, LayerSpec, ModelConfig)
from repro.runtime.context import constrain, get_ctx

# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def make_norm_params(cfg: ModelConfig, key) -> dict:
    if cfg.norm == "nonparam_ln":
        return {}
    return {"scale": jnp.ones((cfg.d_model,), dtype=jnp.float32)}


def apply_norm(cfg: ModelConfig, params: dict, x: jax.Array) -> jax.Array:
    """Stats accumulate in f32 via reduction dtypes; the input is never
    materialized as a bare f32 convert (a bare convert of the remat
    residual gets hoisted by XLA into an f32 copy of the whole scan-stacked
    residual buffer — EXPERIMENTS §Perf 'norm upcast hoist')."""
    dt = x.dtype
    if cfg.norm == "rmsnorm":
        ms = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1,
                      keepdims=True)
        y = x * jax.lax.rsqrt(ms + 1e-6).astype(dt)
        y = y * params["scale"].astype(dt)
    elif cfg.norm in ("layernorm", "nonparam_ln"):
        mu = jnp.mean(x.astype(jnp.float32), axis=-1, keepdims=True)
        ms = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1,
                      keepdims=True)
        var = jnp.maximum(ms - jnp.square(mu), 0.0)
        inv = jax.lax.rsqrt(var + 1e-5)
        y = (x - mu.astype(dt)) * inv.astype(dt)
        if cfg.norm == "layernorm":
            y = y * params["scale"].astype(dt)
    else:
        raise ValueError(cfg.norm)
    return y.astype(dt)


def rms_head_norm(scale: jax.Array, x: jax.Array) -> jax.Array:
    """qk-norm: RMS over the head dim."""
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + 1e-6)
    return (x * scale).astype(dt)


# ---------------------------------------------------------------------------
# Rotary embeddings
# ---------------------------------------------------------------------------


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, hd); positions: (S,) or (B, S)."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., S, half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    # broadcast over the head axis: (..., S, 1, half)
    cos, sin = cos[..., None, :], sin[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention (flash-style chunked jnp; never materializes S x S)
# ---------------------------------------------------------------------------

NEG_INF = -1e30


def _attn_chunk_sizes(s_q: int, s_kv: int) -> tuple[int, int]:
    bq = min(512, s_q)
    bkv = min(1024, s_kv)
    while s_q % bq:
        bq //= 2
    while s_kv % bkv:
        bkv //= 2
    return max(bq, 1), max(bkv, 1)


def _block_mask(qpos, kpos, causal: bool, window: int):
    mask = jnp.ones((qpos.shape[0], kpos.shape[0]), dtype=bool)
    if causal:
        mask &= qpos[:, None] >= kpos[None, :]
    if window > 0:
        mask &= (qpos[:, None] // window) == (kpos[None, :] // window)
    return mask


def _flash_fwd_impl(qg, kg, vg, *, causal: bool, window: int, q_offset,
                    bq: int, bkv: int):
    """qg: (B,K,G,Sq,hd) pre-scaled; kg/vg: (B,K,Skv,hd).
    Returns o (B,K,G,Sq,hd) f32 and row stats L = m + log(l)."""
    B, K, G, Sq, hd = qg.shape
    Skv = kg.shape[2]
    nq, nkv = Sq // bq, Skv // bkv
    q_pos_base = jnp.asarray(q_offset, dtype=jnp.int32)

    def q_block(carry_unused, qi):
        qb = jax.lax.dynamic_slice_in_dim(qg, qi * bq, bq, axis=3)
        qpos = q_pos_base + qi * bq + jnp.arange(bq, dtype=jnp.int32)

        def kv_step(ki, acc):
            o, m, l = acc
            kb = jax.lax.dynamic_slice_in_dim(kg, ki * bkv, bkv, axis=2)
            vb = jax.lax.dynamic_slice_in_dim(vg, ki * bkv, bkv, axis=2)
            kpos = ki * bkv + jnp.arange(bkv, dtype=jnp.int32)
            s = jnp.einsum("bkgqh,bkth->bkgqt", qb, kb,
                           preferred_element_type=jnp.float32)
            mask = _block_mask(qpos, kpos, causal, window)
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            o_new = o * corr[..., None] + jnp.einsum(
                "bkgqt,bkth->bkgqh", p.astype(vb.dtype), vb,
                preferred_element_type=jnp.float32)
            return o_new, m_new, l_new

        o0 = jnp.zeros((B, K, G, bq, hd), jnp.float32)
        m0 = jnp.full((B, K, G, bq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, K, G, bq), jnp.float32)
        o, m, l = jax.lax.fori_loop(0, nkv, kv_step, (o0, m0, l0))
        l = jnp.maximum(l, 1e-30)
        o = o / l[..., None]
        return carry_unused, (o, m + jnp.log(l))

    _, (blocks, Ls) = jax.lax.scan(q_block, None, jnp.arange(nq))
    # blocks: (nq,B,K,G,bq,hd) -> (B,K,G,Sq,hd); Ls -> (B,K,G,Sq)
    o = blocks.transpose(1, 2, 3, 0, 4, 5).reshape(B, K, G, Sq, hd)
    L = Ls.transpose(1, 2, 3, 0, 4).reshape(B, K, G, Sq)
    return o, L


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_core(qg, kg, vg, causal: bool, window: int, bq: int, bkv: int):
    o, _ = _flash_fwd_impl(qg, kg, vg, causal=causal, window=window,
                           q_offset=0, bq=bq, bkv=bkv)
    return o


def _flash_core_fwd(qg, kg, vg, causal, window, bq, bkv):
    o, L = _flash_fwd_impl(qg, kg, vg, causal=causal, window=window,
                           q_offset=0, bq=bq, bkv=bkv)
    return o, (qg, kg, vg, o, L)


def _flash_core_bwd(causal, window, bq, bkv, res, do):
    """FlashAttention-2 backward: recompute p per block from (q,k,L)."""
    qg, kg, vg, o, L = res
    B, K, G, Sq, hd = qg.shape
    Skv = kg.shape[2]
    nq, nkv = Sq // bq, Skv // bkv
    do = do.astype(jnp.float32)
    delta = jnp.sum(do * o, axis=-1)  # (B,K,G,Sq)

    def q_block(carry, qi):
        dk, dv = carry
        qb = jax.lax.dynamic_slice_in_dim(qg, qi * bq, bq, axis=3)
        dob = jax.lax.dynamic_slice_in_dim(do, qi * bq, bq, axis=3)
        Lb = jax.lax.dynamic_slice_in_dim(L, qi * bq, bq, axis=3)
        db = jax.lax.dynamic_slice_in_dim(delta, qi * bq, bq, axis=3)
        qpos = qi * bq + jnp.arange(bq, dtype=jnp.int32)

        def kv_step(ki, acc):
            dq, dk, dv = acc
            kb = jax.lax.dynamic_slice_in_dim(kg, ki * bkv, bkv, axis=2)
            vb = jax.lax.dynamic_slice_in_dim(vg, ki * bkv, bkv, axis=2)
            kpos = ki * bkv + jnp.arange(bkv, dtype=jnp.int32)
            s = jnp.einsum("bkgqh,bkth->bkgqt", qb, kb,
                           preferred_element_type=jnp.float32)
            mask = _block_mask(qpos, kpos, causal, window)
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            p = jnp.exp(s - Lb[..., None])                      # (B,K,G,q,t)
            dp = jnp.einsum("bkgqh,bkth->bkgqt", dob, vb,
                            preferred_element_type=jnp.float32)
            ds = p * (dp - db[..., None])
            dq = dq + jnp.einsum("bkgqt,bkth->bkgqh", ds, kb,
                                 preferred_element_type=jnp.float32)
            dkb = jnp.einsum("bkgqt,bkgqh->bkth", ds, qb,
                             preferred_element_type=jnp.float32)
            dvb = jnp.einsum("bkgqt,bkgqh->bkth", p, dob,
                             preferred_element_type=jnp.float32)
            dk = jax.lax.dynamic_update_slice_in_dim(
                dk, jax.lax.dynamic_slice_in_dim(dk, ki * bkv, bkv, 2) + dkb,
                ki * bkv, axis=2)
            dv = jax.lax.dynamic_update_slice_in_dim(
                dv, jax.lax.dynamic_slice_in_dim(dv, ki * bkv, bkv, 2) + dvb,
                ki * bkv, axis=2)
            return dq, dk, dv

        dq0 = jnp.zeros((B, K, G, bq, hd), jnp.float32)
        dq, dk, dv = jax.lax.fori_loop(0, nkv, kv_step, (dq0, dk, dv))
        return (dk, dv), dq

    dk0 = jnp.zeros((B, K, Skv, hd), jnp.float32)
    dv0 = jnp.zeros((B, K, Skv, hd), jnp.float32)
    (dk, dv), dqs = jax.lax.scan(q_block, (dk0, dv0), jnp.arange(nq))
    dq = dqs.transpose(1, 2, 3, 0, 4, 5).reshape(B, K, G, Sq, hd)
    return (dq.astype(qg.dtype), dk.astype(kg.dtype), dv.astype(vg.dtype))


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool,
                    q_offset: int | jax.Array = 0,
                    window: int = 0,
                    softcap: float = 0.0) -> jax.Array:
    """Chunked online-softmax attention with a FlashAttention-2 style
    custom VJP (residuals: o + per-row logsumexp; p recomputed per block).

    q: (B, Sq, H, hd); k, v: (B, Skv, K, hd) with H % K == 0.
    ``window > 0``: chunked-local attention — position i attends to
    positions j with  (i // window) == (j // window)  and  j <= i
    (llama4-style *chunked*, not sliding).
    ``q_offset``: absolute position of q[0] (prefill chunk offset); the
    custom-VJP path requires q_offset == 0 and softcap == 0 (all training
    configs satisfy this; serving uses the fallback).
    """
    B, Sq, H, hd = q.shape
    _, Skv, K, _ = k.shape
    G = H // K
    scale = 1.0 / math.sqrt(hd)
    bq, bkv = _attn_chunk_sizes(Sq, Skv)

    qg = (q.astype(jnp.float32) * scale).astype(q.dtype) \
        .reshape(B, Sq, K, G, hd).transpose(0, 2, 3, 1, 4)
    kg = k.transpose(0, 2, 1, 3)
    vg = v.transpose(0, 2, 1, 3)

    if softcap == 0.0 and isinstance(q_offset, int) and q_offset == 0:
        o = _flash_core(qg, kg, vg, causal, window, bq, bkv)
    else:
        o, _ = _flash_fwd_impl(qg, kg, vg, causal=causal, window=window,
                               q_offset=q_offset, bq=bq, bkv=bkv)
        if softcap > 0.0:
            raise NotImplementedError("softcap not used by assigned archs")
    out = o.astype(q.dtype).transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, hd)
    return out


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     t: jax.Array, *, window: int = 0,
                     softcap: float = 0.0) -> jax.Array:
    """Single-token decode attention against a cache.

    q: (B, 1, H, hd); caches: (B, S, K, hd); ``t``: current position
    (number of valid cache entries is t+1, the new token already written).
    """
    B, _, H, hd = q.shape
    _, S, K, _ = k_cache.shape
    G = H // K
    scale = 1.0 / math.sqrt(hd)
    qg = (q[:, 0] * scale).reshape(B, K, G, hd)
    pos = jnp.arange(S, dtype=jnp.int32)
    valid = pos <= t
    if window > 0:
        valid &= (pos // window) == (t // window)
    s = jnp.einsum("bkgh,bskh->bkgs", qg, k_cache,
                   preferred_element_type=jnp.float32)
    if softcap > 0.0:
        s = jnp.tanh(s / softcap) * softcap
    s = jnp.where(valid[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bskh->bkgh", p.astype(v_cache.dtype), v_cache,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, 1, H, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# Attention block (params + apply)
# ---------------------------------------------------------------------------


def make_attn_params(cfg: ModelConfig, key, cross: bool = False) -> dict:
    d, hd, H, K = cfg.d_model, cfg.hd, cfg.n_heads, cfg.n_kv_heads
    k1, k2, k3, k4 = jax.random.split(key, 4)
    std = d ** -0.5
    p = {
        "wq": jax.random.normal(k1, (d, H * hd), jnp.float32) * std,
        "wk": jax.random.normal(k2, (d, K * hd), jnp.float32) * std,
        "wv": jax.random.normal(k3, (d, K * hd), jnp.float32) * std,
        "wo": jax.random.normal(k4, (H * hd, d), jnp.float32) * std,
    }
    if cfg.attn_bias:
        p["bq"] = jnp.zeros((H * hd,), jnp.float32)
        p["bk"] = jnp.zeros((K * hd,), jnp.float32)
        p["bv"] = jnp.zeros((K * hd,), jnp.float32)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), jnp.float32)
        p["k_norm"] = jnp.ones((hd,), jnp.float32)
    return p


def _qkv(cfg: ModelConfig, p: dict, x: jax.Array, kv_src: jax.Array,
         dtype) -> tuple[jax.Array, jax.Array, jax.Array]:
    B, Sq, _ = x.shape
    Skv = kv_src.shape[1]
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = x @ p["wq"].astype(dtype)
    k = kv_src @ p["wk"].astype(dtype)
    v = kv_src @ p["wv"].astype(dtype)
    if cfg.attn_bias:
        q = q + p["bq"].astype(dtype)
        k = k + p["bk"].astype(dtype)
        v = v + p["bv"].astype(dtype)
    q = q.reshape(B, Sq, H, hd)
    k = k.reshape(B, Skv, K, hd)
    v = v.reshape(B, Skv, K, hd)
    if cfg.qk_norm:
        q = rms_head_norm(p["q_norm"], q)
        k = rms_head_norm(p["k_norm"], k)
    return q, k, v


def attn_forward(cfg: ModelConfig, p: dict, x: jax.Array, *,
                 mixer: str, media: Optional[jax.Array] = None,
                 positions: Optional[jax.Array] = None) -> jax.Array:
    """Full-sequence attention (train / prefill). x: (B, S, D)."""
    dtype = x.dtype
    B, S, _ = x.shape
    if mixer == CROSS_ATTN:
        q, k, v = _qkv(cfg, p, x, media, dtype)
        out = flash_attention(q, k, v, causal=False, softcap=cfg.logit_softcap)
    else:
        q, k, v = _qkv(cfg, p, x, x, dtype)
        if positions is None:
            positions = jnp.arange(S, dtype=jnp.int32)
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
        window = cfg.attn_window if mixer == ATTN_CHUNKED else 0
        out = flash_attention(q, k, v, causal=cfg.causal, window=window,
                              softcap=cfg.logit_softcap)
    out = constrain(out, P(("pod", "data"), None, "model", None))
    H, hd = cfg.n_heads, cfg.hd
    return out.reshape(B, S, H * hd) @ p["wo"].astype(dtype)


def attn_decode(cfg: ModelConfig, p: dict, x: jax.Array, cache: dict,
                t: jax.Array, *, mixer: str, slot: Optional[jax.Array] = None,
                media: Optional[jax.Array] = None) -> tuple[jax.Array, dict]:
    """Single-token decode. x: (B, 1, D). cache: {"k","v"}: (B, S, K, hd).

    ``t`` is the absolute position (rope); ``slot`` is the cache write/read
    index (differs from ``t`` for chunked-local ring-buffer caches).
    """
    dtype = x.dtype
    B = x.shape[0]
    if slot is None:
        slot = t
    if mixer == CROSS_ATTN:
        # media kv is precomputed in the cache at prefill time
        q, _, _ = _qkv(cfg, p, x, x[:, :1], dtype)  # only q matters
        kc, vc = cache["k"], cache["v"]
        M = kc.shape[1]
        out = decode_attention(q, kc, vc, jnp.asarray(M - 1, jnp.int32),
                               softcap=cfg.logit_softcap)
        new_cache = cache
    else:
        q, k, v = _qkv(cfg, p, x, x, dtype)
        pos = t[None] if t.ndim == 0 else t
        q = rope(q, pos, cfg.rope_theta)
        k = rope(k, pos, cfg.rope_theta)
        kc = jax.lax.dynamic_update_slice_in_dim(cache["k"], k.astype(cache["k"].dtype), slot, axis=1)
        vc = jax.lax.dynamic_update_slice_in_dim(cache["v"], v.astype(cache["v"].dtype), slot, axis=1)
        out = decode_attention(q, kc, vc, slot, softcap=cfg.logit_softcap)
        new_cache = {"k": kc, "v": vc}
    H, hd = cfg.n_heads, cfg.hd
    y = out.reshape(B, 1, H * hd) @ p["wo"].astype(dtype)
    return y, new_cache


# ---------------------------------------------------------------------------
# Dense MLP
# ---------------------------------------------------------------------------


def make_mlp_params(cfg: ModelConfig, key) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    std = d ** -0.5
    if cfg.mlp_gated:
        return {
            "w_gate": jax.random.normal(ks[0], (d, f), jnp.float32) * std,
            "w_up": jax.random.normal(ks[1], (d, f), jnp.float32) * std,
            "w_down": jax.random.normal(ks[2], (f, d), jnp.float32) * (f ** -0.5),
        }
    return {
        "w_up": jax.random.normal(ks[0], (d, f), jnp.float32) * std,
        "w_down": jax.random.normal(ks[1], (f, d), jnp.float32) * (f ** -0.5),
    }


def mlp_forward(cfg: ModelConfig, p: dict, x: jax.Array) -> jax.Array:
    dtype = x.dtype
    if "w_gate" in p:
        h = jax.nn.silu(x @ p["w_gate"].astype(dtype)) * (x @ p["w_up"].astype(dtype))
    else:
        h = jax.nn.gelu(x @ p["w_up"].astype(dtype))
    h = constrain(h, P(("pod", "data"), None, "model"))
    return h @ p["w_down"].astype(dtype)


# ---------------------------------------------------------------------------
# Mixture of Experts (token-choice top-k, fixed capacity, EP over data axis)
# ---------------------------------------------------------------------------


def make_moe_params(cfg: ModelConfig, key) -> dict:
    m = cfg.moe
    d, f, E = cfg.d_model, m.d_expert, m.n_experts
    ks = jax.random.split(key, 5)
    std = d ** -0.5
    p = {
        "router": jax.random.normal(ks[0], (d, E), jnp.float32) * std,
        "w_gate": jax.random.normal(ks[1], (E, d, f), jnp.float32) * std,
        "w_up": jax.random.normal(ks[2], (E, d, f), jnp.float32) * std,
        "w_down": jax.random.normal(ks[3], (E, f, d), jnp.float32) * (f ** -0.5),
    }
    if m.d_shared:
        sk = jax.random.split(ks[4], 3)
        p["shared"] = {
            "w_gate": jax.random.normal(sk[0], (d, m.d_shared), jnp.float32) * std,
            "w_up": jax.random.normal(sk[1], (d, m.d_shared), jnp.float32) * std,
            "w_down": jax.random.normal(sk[2], (m.d_shared, d), jnp.float32) * (m.d_shared ** -0.5),
        }
    return p


def _router(cfg: ModelConfig, p: dict, xf: jax.Array):
    """xf: (T, D) -> top-k expert ids (T,k) + weights (T,k) (fp32)."""
    m = cfg.moe
    logits = xf.astype(jnp.float32) @ p["router"].astype(jnp.float32)
    w, idx = jax.lax.top_k(logits, m.top_k)
    w = jax.nn.softmax(w, axis=-1)
    return idx, w


def _capacity(cfg: ModelConfig, n_tokens: int) -> int:
    m = cfg.moe
    c = int(math.ceil(n_tokens * m.top_k / m.n_experts * m.capacity_factor))
    return max(8, -(-c // 8) * 8)  # round up to 8


def _expert_ffn(p: dict, x: jax.Array) -> jax.Array:
    """x: (E, C, D) -> (E, C, D)."""
    dtype = x.dtype
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", x, p["w_gate"].astype(dtype))) \
        * jnp.einsum("ecd,edf->ecf", x, p["w_up"].astype(dtype))
    h = constrain(h, P("data", None, "model"))
    return jnp.einsum("ecf,efd->ecd", h, p["w_down"].astype(dtype))


def _dispatch_slots(cfg: ModelConfig, idx: jax.Array, T: int):
    """Single-shot slot assignment for all top-k choices.

    idx: (T, k) expert ids.  Returns slot (T, k) into a buffer of
    E * C_e rows (C_e = total per-expert capacity across all k slots);
    out-of-capacity pairs get an out-of-bounds slot (dropped by scatter
    mode='drop' / gather mode='fill')."""
    m = cfg.moe
    E = m.n_experts
    k = m.top_k
    C_e = _capacity(cfg, T)  # per-expert capacity for T local tokens
    flat_e = idx.reshape(T * k)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)          # (T*k, E)
    pos = jnp.sum(jnp.cumsum(onehot, axis=0) * onehot, -1) - 1    # (T*k,)
    keep = pos < C_e
    slot = jnp.where(keep, flat_e * C_e + pos, E * C_e)           # OOB = drop
    return slot.reshape(T, k), C_e


def _combine(xf, ret, slot, w, k):
    """ret: (E*C_e, D) expert outputs; gather per top-k slot and mix."""
    out = jnp.zeros(xf.shape, jnp.float32)
    for j in range(k):
        g = ret.at[slot[:, j]].get(mode="fill", fill_value=0)
        out = out + w[:, j:j + 1] * g.astype(jnp.float32)
    return out


def _shared_expert(p, xf):
    sh = p["shared"]
    h = jax.nn.silu(xf @ sh["w_gate"].astype(xf.dtype)) \
        * (xf @ sh["w_up"].astype(xf.dtype))
    return (h @ sh["w_down"].astype(xf.dtype)).astype(jnp.float32)


def moe_local(cfg: ModelConfig, p: dict, x: jax.Array) -> jax.Array:
    """Single-device MoE. x: (B, S, D)."""
    m = cfg.moe
    B, S, D = x.shape
    T = B * S
    xf = x.reshape(T, D)
    idx, w = _router(cfg, p, xf)
    slot, C_e = _dispatch_slots(cfg, idx, T)
    E = m.n_experts

    buf = jnp.zeros((E * C_e, D), xf.dtype)
    for j in range(m.top_k):
        buf = buf.at[slot[:, j]].set(xf, mode="drop")
    yb = _expert_ffn(p, buf.reshape(E, C_e, D)).reshape(E * C_e, D)
    out = _combine(xf, yb, slot, w, m.top_k)
    if m.d_shared:
        out = out + _shared_expert(p, xf)
    return out.reshape(B, S, D).astype(x.dtype)


def moe_distributed_replicated(cfg: ModelConfig, p: dict, x: jax.Array,
                               ep_axis: str) -> jax.Array:
    """EP with *replicated* tokens (small-batch decode: B < n_ep).  Every
    rank routes all tokens, computes its local experts, and the outputs are
    combined with one modest all-reduce — no all_to_all."""
    m = cfg.moe
    B, S, D = x.shape
    T = B * S
    n_ep = jax.lax.axis_size(ep_axis)
    E_loc = p["w_gate"].shape[0]
    E = E_loc * n_ep
    xf = x.reshape(T, D)
    idx, w = _router(cfg, p, xf)
    slot, C_e = _dispatch_slots(cfg, idx, T)

    buf = jnp.zeros((E * C_e, D), xf.dtype)
    for j in range(m.top_k):
        buf = buf.at[slot[:, j]].set(xf, mode="drop")
    my = jax.lax.axis_index(ep_axis)
    xin = jax.lax.dynamic_slice_in_dim(buf, my * E_loc * C_e, E_loc * C_e,
                                       axis=0).reshape(E_loc, C_e, D)
    yout = _expert_ffn(p, xin).reshape(E_loc * C_e, D)
    full = jnp.zeros((E * C_e, D), jnp.float32)
    full = jax.lax.dynamic_update_slice_in_dim(
        full, yout.astype(jnp.float32), my * E_loc * C_e, axis=0)
    full = jax.lax.psum(full, ep_axis)
    out = _combine(xf, full, slot, w, m.top_k)
    if m.d_shared:
        out = out + _shared_expert(p, xf)
    return out.reshape(B, S, D).astype(x.dtype)


def moe_distributed(cfg: ModelConfig, p: dict, x: jax.Array,
                    ep_axis: str) -> jax.Array:
    """Expert-parallel MoE inside a manual shard_map context.

    ``x``: (B_loc, S, D) local tokens; expert params are local shards
    (E_loc, ...) along the leading dim.  One all_to_all ships every
    top-k choice in a single (E * C_e)-row buffer (the paper-external
    forward routing collective — DESIGN §4)."""
    m = cfg.moe
    B, S, D = x.shape
    T = B * S
    n_ep = jax.lax.axis_size(ep_axis)
    E_loc = p["w_gate"].shape[0]
    E = E_loc * n_ep
    xf = x.reshape(T, D)
    idx, w = _router(cfg, p, xf)        # router replicated; runs locally
    slot, C_e = _dispatch_slots(cfg, idx, T)

    send = jnp.zeros((E * C_e, D), xf.dtype)
    for j in range(m.top_k):
        send = send.at[slot[:, j]].set(xf, mode="drop")
    send = send.reshape(n_ep, E_loc * C_e, D)
    if m.dispatch_dtype:  # e.g. fp8 dispatch (combine stays in act dtype)
        send = send.astype(jnp.dtype(m.dispatch_dtype))
    recv = jax.lax.all_to_all(send, ep_axis, split_axis=0, concat_axis=0)
    recv = recv.astype(xf.dtype)
    # recv: (n_ep, E_loc*C_e, D) — every source rank's rows for my experts
    xin = recv.reshape(n_ep, E_loc, C_e, D).transpose(1, 0, 2, 3) \
              .reshape(E_loc, n_ep * C_e, D)
    yout = _expert_ffn(p, xin)
    back = yout.reshape(E_loc, n_ep, C_e, D).transpose(1, 0, 2, 3) \
               .reshape(n_ep, E_loc * C_e, D)
    ret = jax.lax.all_to_all(back, ep_axis, split_axis=0, concat_axis=0)
    ret = ret.reshape(E * C_e, D)
    out = _combine(xf, ret, slot, w, m.top_k)
    if m.d_shared:
        out = out + _shared_expert(p, xf)
    return out.reshape(B, S, D).astype(x.dtype)


def moe_forward(cfg: ModelConfig, p: dict, x: jax.Array) -> jax.Array:
    """Dispatch: single-device -> local; manual DP context -> direct
    all_to_all EP; GSPMD context -> wrap the EP exchange in a partial-manual
    shard_map over the expert axis (GSPMD alone shards the token scatter
    catastrophically — DESIGN §6).  ``cfg.moe_seq_chunks > 1`` splits the
    dispatch over sequence chunks to bound the buffer peak."""
    if cfg.moe_seq_chunks > 1 and x.shape[1] % cfg.moe_seq_chunks == 0:
        n = cfg.moe_seq_chunks
        B, S, D = x.shape
        xs = x.reshape(B, n, S // n, D).transpose(1, 0, 2, 3)
        sub = dataclasses.replace(cfg, moe_seq_chunks=1)

        def one(xc):
            return moe_forward(sub, p, xc)

        ys = jax.lax.map(one, xs)
        return ys.transpose(1, 0, 2, 3).reshape(B, S, D)
    return _moe_forward_impl(cfg, p, x)


def _moe_forward_impl(cfg: ModelConfig, p: dict, x: jax.Array) -> jax.Array:
    import dataclasses as _dc

    from repro.runtime.context import use_ctx
    ctx = get_ctx()
    if ctx.mesh is None or ctx.ep_axis is None \
            or ctx.mesh.shape[ctx.ep_axis] == 1:
        return moe_local(cfg, p, x)
    n_ep = ctx.mesh.shape[ctx.ep_axis]
    # small-batch decode: tokens replicated over the EP axis
    dp_div = 1
    for a in ctx.dp_axes:
        dp_div *= ctx.mesh.shape[a]
    replicated_tokens = x.shape[0] % dp_div != 0 or x.shape[0] < dp_div
    if ctx.manual_dp:
        if replicated_tokens:
            return moe_distributed_replicated(cfg, p, x, ctx.ep_axis)
        return moe_distributed(cfg, p, x, ctx.ep_axis)

    ep = ctx.ep_axis
    inner_ctx = _dc.replace(ctx, manual_dp=True,
                            manual_axes=tuple(set(ctx.manual_axes) | {ep}))

    def body(p_loc, x_loc):
        with use_ctx(inner_ctx):
            if replicated_tokens:
                return moe_distributed_replicated(cfg, p_loc, x_loc, ep)
            return moe_distributed(cfg, p_loc, x_loc, ep)

    p_specs = jax.tree.map(
        lambda l: P(ep, *([None] * (l.ndim - 1))) if l.ndim == 3
        else P(*([None] * l.ndim)), p)
    x_spec = P(None, None, None) if replicated_tokens else P(ep, None, None)
    return jax.shard_map(
        body, mesh=ctx.mesh,
        in_specs=(p_specs, x_spec), out_specs=x_spec,
        axis_names=frozenset({ep}), check_vma=False)(p, x)


# ---------------------------------------------------------------------------
# Mamba2 (SSD) mixer
# ---------------------------------------------------------------------------


def make_mamba_params(cfg: ModelConfig, key) -> dict:
    """Projections are split per component (z | x | B | C | dt) so each can
    carry its own TP sharding without cross-shard slicing (DESIGN §6)."""
    s = cfg.ssm
    d = cfg.d_model
    d_in = s.expand * d
    nh = d_in // s.head_dim
    ks = jax.random.split(key, 8)
    std = d ** -0.5
    return {
        "in_z": jax.random.normal(ks[0], (d, d_in), jnp.float32) * std,
        "in_x": jax.random.normal(ks[1], (d, d_in), jnp.float32) * std,
        "in_B": jax.random.normal(ks[2], (d, s.d_state), jnp.float32) * std,
        "in_C": jax.random.normal(ks[3], (d, s.d_state), jnp.float32) * std,
        "in_dt": jax.random.normal(ks[4], (d, nh), jnp.float32) * std,
        "conv_x": jax.random.normal(ks[5], (s.d_conv, d_in), jnp.float32) * 0.1,
        "conv_xb": jnp.zeros((d_in,), jnp.float32),
        "conv_B": jax.random.normal(ks[6], (s.d_conv, s.d_state), jnp.float32) * 0.1,
        "conv_Bb": jnp.zeros((s.d_state,), jnp.float32),
        "conv_C": jax.random.normal(ks[7], (s.d_conv, s.d_state), jnp.float32) * 0.1,
        "conv_Cb": jnp.zeros((s.d_state,), jnp.float32),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, nh).astype(jnp.float32)),
        "D": jnp.ones((nh,), jnp.float32),
        "dt_bias": jnp.zeros((nh,), jnp.float32),
        "out_norm": jnp.ones((d_in,), jnp.float32),
        "out_proj": jax.random.normal(ks[0], (d_in, d), jnp.float32) * (d_in ** -0.5),
    }


def _segsum(x: jax.Array) -> jax.Array:
    """x: (..., q) -> (..., q, q) lower-tri cumulative sums  sum_{j<i<=k}."""
    q = x.shape[-1]
    cs = jnp.cumsum(x, axis=-1)
    seg = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((q, q), bool), k=0)
    return jnp.where(mask, seg, -jnp.inf)


def ssd_chunked(x: jax.Array, dt: jax.Array, A: jax.Array, Bm: jax.Array,
                Cm: jax.Array, chunk: int,
                init_state: Optional[jax.Array] = None):
    """SSD (state-space dual) forward, chunked reference in pure jnp.

    x:  (B, S, H, P) inputs per head
    dt: (B, S, H)    positive step sizes
    A:  (H,)         negative decay rates (A < 0)
    Bm: (B, S, N)    input matrix (shared across heads)
    Cm: (B, S, N)    output matrix
    Returns y: (B, S, H, P), final_state: (B, H, P, N).
    """
    Bsz, S, H, Pd = x.shape
    N = Bm.shape[-1]
    S_orig = S
    if S % chunk:  # pad with dt=0 steps (decay 1, zero input: exact no-op)
        pad = chunk - S % chunk
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0)))
        S = S + pad
    nc = S // chunk
    xc = x.reshape(Bsz, nc, chunk, H, Pd)
    dtc = dt.reshape(Bsz, nc, chunk, H)
    Bc = Bm.reshape(Bsz, nc, chunk, N)
    Cc = Cm.reshape(Bsz, nc, chunk, N)

    dA = dtc * A[None, None, None, :]                  # (B,c,q,H)
    dA_cum = jnp.cumsum(dA, axis=2)
    xdt = xc * dtc[..., None]

    # 1. intra-chunk (diagonal blocks)
    L = jnp.exp(_segsum(dA.transpose(0, 1, 3, 2)))     # (B,c,H,q,q)
    scores = jnp.einsum("bcqn,bctn->bcqt", Cc, Bc)     # (B,c,q,t)
    y_diag = jnp.einsum("bchqt,bcqt,bcthp->bcqhp",
                        L, scores, xdt)

    # 2. chunk states
    decay_states = jnp.exp(dA_cum[:, :, -1:, :] - dA_cum)   # (B,c,q,H)
    states = jnp.einsum("bcqn,bcqh,bcqhp->bchpn", Bc, decay_states, xdt)

    # 3. inter-chunk recurrence over c
    chunk_decay = jnp.exp(dA_cum[:, :, -1, :])         # (B,c,H)

    def scan_fn(carry, inp):
        st, dec = inp                                   # (B,H,P,N), (B,H)
        new = carry * dec[..., None, None] + st
        return new, carry                               # emit state *before* chunk

    init = (jnp.zeros((Bsz, H, Pd, N), x.dtype) if init_state is None
            else init_state)
    final_state, prev_states = jax.lax.scan(
        scan_fn, init,
        (states.transpose(1, 0, 2, 3, 4), chunk_decay.transpose(1, 0, 2)))
    prev_states = prev_states.transpose(1, 0, 2, 3, 4)  # (B,c,H,P,N)

    # 4. state -> output within chunk
    state_decay = jnp.exp(dA_cum)                       # (B,c,q,H)
    y_off = jnp.einsum("bcqn,bcqh,bchpn->bcqhp", Cc, state_decay, prev_states)

    y = (y_diag + y_off).reshape(Bsz, S, H, Pd)[:, :S_orig]
    return y, final_state


def _causal_conv(x: jax.Array, w: jax.Array, b: jax.Array,
                 state: Optional[jax.Array] = None):
    """x: (B, S, C); w: (K, C) depthwise causal conv. Returns y, new_state."""
    K = w.shape[0]
    if state is None:
        pad = jnp.zeros((x.shape[0], K - 1, x.shape[-1]), x.dtype)
    else:
        pad = state.astype(x.dtype)
    xp = jnp.concatenate([pad, x], axis=1)
    y = sum(xp[:, i:i + x.shape[1]] * w[i] for i in range(K)) + b
    new_state = xp[:, -(K - 1):] if K > 1 else jnp.zeros_like(pad)
    return jax.nn.silu(y), new_state


def mamba_forward(cfg: ModelConfig, p: dict, x: jax.Array,
                  state: Optional[dict] = None, decode: bool = False):
    """Mamba2 block. x: (B, S, D). state (decode): {"conv_x": (B,K-1,d_in),
    "conv_B"/"conv_C": (B,K-1,N), "ssd": (B,H,P,N)}; returns (y, state)."""
    s = cfg.ssm
    dtype = x.dtype
    Bsz, S, D = x.shape
    d_in = s.expand * D
    nh = d_in // s.head_dim
    z = x @ p["in_z"].astype(dtype)
    xr = x @ p["in_x"].astype(dtype)
    Br = x @ p["in_B"].astype(dtype)
    Cr = x @ p["in_C"].astype(dtype)
    dtr = x @ p["in_dt"].astype(dtype)

    st = state or {}
    xr, new_cx = _causal_conv(xr, p["conv_x"].astype(dtype),
                              p["conv_xb"].astype(dtype), st.get("conv_x"))
    Bm, new_cb = _causal_conv(Br, p["conv_B"].astype(dtype),
                              p["conv_Bb"].astype(dtype), st.get("conv_B"))
    Cm, new_cc = _causal_conv(Cr, p["conv_C"].astype(dtype),
                              p["conv_Cb"].astype(dtype), st.get("conv_C"))
    xs = xr.reshape(Bsz, S, nh, s.head_dim)

    dt = jax.nn.softplus(dtr.astype(jnp.float32) + p["dt_bias"])   # (B,S,H)
    A = -jnp.exp(p["A_log"])                                        # (H,)

    if decode:
        # recurrent single-step update (S == 1)
        st = state["ssd"]
        dA = jnp.exp(dt[:, 0] * A[None, :])                         # (B,H)
        dBx = jnp.einsum("bn,bhp,bh->bhpn", Bm[:, 0].astype(jnp.float32),
                         xs[:, 0].astype(jnp.float32), dt[:, 0])
        st = st * dA[..., None, None] + dBx
        y = jnp.einsum("bn,bhpn->bhp", Cm[:, 0].astype(jnp.float32), st)
        y = y[:, None].astype(dtype)                                # (B,1,H,P)
        new_ssd = st
    else:
        init = None if state is None else state["ssd"]
        y, new_ssd = ssd_chunked(xs.astype(jnp.float32), dt, A,
                                 Bm.astype(jnp.float32),
                                 Cm.astype(jnp.float32),
                                 min(s.chunk, S), init)
        y = y.astype(dtype)

    y = y + xs * p["D"].astype(dtype)[None, None, :, None]
    y = y.reshape(Bsz, S, d_in)
    # gated RMSNorm (mamba2 style)
    y = y * jax.nn.silu(z)
    yf = y.astype(jnp.float32)
    y = (yf * jax.lax.rsqrt(jnp.mean(yf * yf, -1, keepdims=True) + 1e-6)
         * p["out_norm"]).astype(dtype)
    out = y @ p["out_proj"].astype(dtype)
    new_state = {"conv_x": new_cx.astype(dtype), "conv_B": new_cb.astype(dtype),
                 "conv_C": new_cc.astype(dtype), "ssd": new_ssd}
    return out, new_state
