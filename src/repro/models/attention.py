"""Attention variants: GQA, sliding-window/global alternation, logit
softcap, QKV bias, cross-attention, and DeepSeek-V3 MLA.

Two execution paths per variant:
  * full-sequence (train / prefill) — optionally backed by the Pallas flash
    kernel on TPU (``repro.kernels.flash_attention``); pure-jnp on CPU.
  * single-token decode against a KV cache.

Softmax is always computed in f32.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, MLAConfig
from repro.models.layers import apply_rope, dense_init, softcap
from repro.sharding.ctx import shard_activation

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# standard (GQA) attention
# ---------------------------------------------------------------------------

def init_attn(key, cfg: ArchConfig, d_in: Optional[int] = None):
    """d_in lets hybrid blocks feed concat(h, emb) (zamba2: 2 * d_model)."""
    d = cfg.d_model
    d_in = d_in or d
    hd = cfg.resolved_head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {
        "wq": dense_init(k1, (d_in, cfg.n_heads * hd)),
        "wk": dense_init(k2, (d_in, cfg.n_kv_heads * hd)),
        "wv": dense_init(k3, (d_in, cfg.n_kv_heads * hd)),
        "wo": dense_init(k4, (cfg.n_heads * hd, d)),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.n_heads * hd,), jnp.float32)
        p["bk"] = jnp.zeros((cfg.n_kv_heads * hd,), jnp.float32)
        p["bv"] = jnp.zeros((cfg.n_kv_heads * hd,), jnp.float32)
    return p


def _project_qkv(cfg: ArchConfig, p, x, kv_x=None):
    """-> q (B,S,Hq,hd), k/v (B,Skv,Hkv,hd)."""
    dt = x.dtype
    hd = cfg.resolved_head_dim
    kv_x = x if kv_x is None else kv_x
    q = x @ p["wq"].astype(dt)
    k = kv_x @ p["wk"].astype(dt)
    v = kv_x @ p["wv"].astype(dt)
    if "bq" in p:
        q = q + p["bq"].astype(dt)
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    B = x.shape[0]
    q = q.reshape(B, x.shape[1], cfg.n_heads, hd)
    k = k.reshape(B, kv_x.shape[1], cfg.n_kv_heads, hd)
    v = v.reshape(B, kv_x.shape[1], cfg.n_kv_heads, hd)
    return q, k, v


def sdpa(q, k, v, *, mask=None, cap: float = 0.0, scale: float = 0.0):
    """Grouped scaled-dot-product attention.

    q: (B,S,Hq,hd); k,v: (B,T,Hkv,hd); Hq % Hkv == 0.
    mask: broadcastable to (B,1,1,S,T), True = attend.
    scale: the score scale; 0 -> hd ** -0.5 (a config's ``attn_scale``).
    """
    B, S, Hq, hd = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    group = Hq // Hkv
    qg = q.reshape(B, S, Hkv, group, hd)
    scale = scale or hd ** -0.5
    logits = jnp.einsum("bskgh,btkh->bkgst", qg, k).astype(jnp.float32) * scale
    logits = softcap(logits, cap)
    if mask is not None:
        # mask (B,1,1,S,T) -> (B,1,1,S,T) matches (b,k,g,s,t)
        logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgst,btkh->bskgh", probs, v)
    return out.reshape(B, S, Hq, hd)


def causal_mask(S: int, T: int, q_offset, window: int = 0, local_flag=None):
    """(1,1,1,S,T) boolean mask, True = attend.

    ``window`` is a static int; ``local_flag`` may be a *traced* boolean
    (scan-over-layers local/global alternation, gemma2): when False the
    window constraint is disabled for that layer.
    """
    qi = q_offset + jnp.arange(S)[:, None]
    kj = jnp.arange(T)[None, :]
    m = kj <= qi
    if window:
        win = kj > qi - window
        if local_flag is not None:
            win = win | jnp.logical_not(local_flag)
        m = m & win
    return m[None, None, None]


def attn_forward(cfg: ArchConfig, p, x, *, positions, window: int = 0,
                 local_flag=None, kv_x=None, kv_positions=None,
                 causal: bool = True):
    """Full-sequence attention (train / prefill / encoder / cross)."""
    q, k, v = _project_qkv(cfg, p, x, kv_x)
    if positions is not None:
        q = apply_rope(q, positions, cfg.rope_theta)
        kpos = kv_positions if kv_positions is not None else positions
        k = apply_rope(k, kpos, cfg.rope_theta)
    mask = None
    if causal:
        mask = causal_mask(q.shape[1], k.shape[1], 0, window, local_flag)
    out = sdpa(q, k, v, mask=mask, cap=cfg.attn_softcap,
               scale=cfg.attn_scale)
    out = shard_activation(out, "attn_out")
    B, S = x.shape[:2]
    return out.reshape(B, S, -1) @ p["wo"].astype(x.dtype), (k, v)


def attn_decode(cfg: ArchConfig, p, x, k_cache, v_cache, pos, *,
                window: int = 0, local_flag=None, rope: bool = True,
                mask_pos=None, rope_pos=None):
    """One-token decode. x: (B,1,d_in); caches: (B,T,Hkv,hd); pos scalar.

    ``mask_pos`` overrides the causal-mask position and ``rope_pos`` the
    rotary position (ring-buffer caches write at ``pos`` = slot while the
    rotary/mask positions stay absolute).
    Returns (out (B,1,d), new_k_cache, new_v_cache).
    """
    q, k, v = _project_qkv(cfg, p, x)
    if rope:
        rp = pos if rope_pos is None else rope_pos
        posv = jnp.full((x.shape[0], 1), rp, jnp.int32)
        q = apply_rope(q, posv, cfg.rope_theta)
        k = apply_rope(k, posv, cfg.rope_theta)
    k_cache = jax.lax.dynamic_update_slice(k_cache, k.astype(k_cache.dtype),
                                           (0, pos, 0, 0))
    v_cache = jax.lax.dynamic_update_slice(v_cache, v.astype(v_cache.dtype),
                                           (0, pos, 0, 0))
    T = k_cache.shape[1]
    kj = jnp.arange(T)
    mpos = pos if mask_pos is None else mask_pos
    m = kj <= mpos
    if window:
        win = kj > mpos - window
        if local_flag is not None:
            win = win | jnp.logical_not(local_flag)
        m = m & win
    mask = m[None, None, None, None, :]
    out = sdpa(q, k_cache.astype(q.dtype), v_cache.astype(q.dtype),
               mask=mask, cap=cfg.attn_softcap, scale=cfg.attn_scale)
    B = x.shape[0]
    return out.reshape(B, 1, -1) @ p["wo"].astype(x.dtype), k_cache, v_cache


def cross_attn_decode(cfg: ArchConfig, p, x, enc_k, enc_v):
    """Decode-time cross attention against precomputed encoder K/V."""
    dt = x.dtype
    hd = cfg.resolved_head_dim
    B = x.shape[0]
    q = (x @ p["wq"].astype(dt)).reshape(B, 1, cfg.n_heads, hd)
    if "bq" in p:
        q = q + p["bq"].astype(dt).reshape(1, 1, cfg.n_heads, hd)
    out = sdpa(q, enc_k.astype(dt), enc_v.astype(dt), mask=None,
               cap=cfg.attn_softcap)
    return out.reshape(B, 1, -1) @ p["wo"].astype(dt)


# ---------------------------------------------------------------------------
# DeepSeek-V3 Multi-head Latent Attention (MLA)
# ---------------------------------------------------------------------------

def init_mla(key, cfg: ArchConfig):
    m: MLAConfig = cfg.mla
    d, H = cfg.d_model, cfg.n_heads
    ks = jax.random.split(key, 7)
    qk_head = m.qk_nope_head_dim + m.qk_rope_head_dim
    return {
        "w_dq": dense_init(ks[0], (d, m.q_lora_rank)),
        "w_uq": dense_init(ks[1], (m.q_lora_rank, H * qk_head)),
        "w_dkv": dense_init(ks[2], (d, m.kv_lora_rank)),
        "w_krope": dense_init(ks[3], (d, m.qk_rope_head_dim)),
        "w_uk": dense_init(ks[4], (m.kv_lora_rank, H * m.qk_nope_head_dim)),
        "w_uv": dense_init(ks[5], (m.kv_lora_rank, H * m.v_head_dim)),
        "wo": dense_init(ks[6], (H * m.v_head_dim, d)),
    }


def _mla_q(cfg: ArchConfig, p, x, positions):
    m, H = cfg.mla, cfg.n_heads
    dt = x.dtype
    B, S = x.shape[:2]
    q = (x @ p["w_dq"].astype(dt)) @ p["w_uq"].astype(dt)
    q = q.reshape(B, S, H, m.qk_nope_head_dim + m.qk_rope_head_dim)
    q_nope = q[..., : m.qk_nope_head_dim]
    q_rope = apply_rope(q[..., m.qk_nope_head_dim:], positions, cfg.rope_theta)
    return q_nope, q_rope


def mla_forward(cfg: ArchConfig, p, x, *, positions):
    """Full-sequence MLA (train / prefill): materialize per-head k/v."""
    m, H = cfg.mla, cfg.n_heads
    dt = x.dtype
    B, S = x.shape[:2]
    q_nope, q_rope = _mla_q(cfg, p, x, positions)
    c_kv = x @ p["w_dkv"].astype(dt)                        # (B,S,r_kv)
    k_rope = apply_rope((x @ p["w_krope"].astype(dt))[:, :, None, :],
                        positions, cfg.rope_theta)          # (B,S,1,rope)
    k_nope = (c_kv @ p["w_uk"].astype(dt)).reshape(B, S, H, m.qk_nope_head_dim)
    v = (c_kv @ p["w_uv"].astype(dt)).reshape(B, S, H, m.v_head_dim)
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    logits = (jnp.einsum("bshd,bthd->bhst", q_nope, k_nope)
              + jnp.einsum("bshd,btod->bhst", q_rope, k_rope))
    logits = logits.astype(jnp.float32) * scale
    mask = causal_mask(S, S, 0)[:, :, 0]                    # (1,1,S,T)
    logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(dt)
    out = jnp.einsum("bhst,bthd->bshd", probs, v).reshape(B, S, -1)
    out = shard_activation(out, "attn_out")
    return out @ p["wo"].astype(dt), (c_kv, k_rope[:, :, 0, :])


def mla_decode(cfg: ArchConfig, p, x, ckv_cache, krope_cache, pos):
    """Absorbed-matrix MLA decode: attend in the latent space.

    score_h(t) = q_nope_h^T W_uk_h c_t + q_rope_h^T k_rope_t  — we absorb
    W_uk into the query and W_uv into the output so the cache stays
    (B,T,r_kv)+(B,T,rope): the memory win MLA exists for.
    """
    m, H = cfg.mla, cfg.n_heads
    dt = x.dtype
    B = x.shape[0]
    posv = jnp.full((B, 1), pos, jnp.int32)
    q_nope, q_rope = _mla_q(cfg, p, x, posv)                 # (B,1,H,·)
    c_kv = x @ p["w_dkv"].astype(dt)                         # (B,1,r)
    k_rope = apply_rope((x @ p["w_krope"].astype(dt))[:, :, None, :],
                        posv, cfg.rope_theta)[:, :, 0, :]    # (B,1,rope)
    ckv_cache = jax.lax.dynamic_update_slice(
        ckv_cache, c_kv.astype(ckv_cache.dtype), (0, pos, 0))
    krope_cache = jax.lax.dynamic_update_slice(
        krope_cache, k_rope.astype(krope_cache.dtype), (0, pos, 0))
    w_uk = p["w_uk"].astype(dt).reshape(m.kv_lora_rank, H, m.qk_nope_head_dim)
    q_abs = jnp.einsum("bshd,rhd->bshr", q_nope, w_uk)       # (B,1,H,r)
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    ckv = ckv_cache.astype(dt)
    logits = (jnp.einsum("bshr,btr->bhst", q_abs, ckv)
              + jnp.einsum("bshd,btd->bhst", q_rope, krope_cache.astype(dt)))
    logits = logits.astype(jnp.float32) * scale
    mask = (jnp.arange(ckv_cache.shape[1]) <= pos)[None, None, None, :]
    logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(dt)
    ctx = jnp.einsum("bhst,btr->bshr", probs, ckv)           # (B,1,H,r)
    w_uv = p["w_uv"].astype(dt).reshape(m.kv_lora_rank, H, m.v_head_dim)
    out = jnp.einsum("bshr,rhd->bshd", ctx, w_uv).reshape(B, 1, -1)
    return out @ p["wo"].astype(dt), ckv_cache, krope_cache
