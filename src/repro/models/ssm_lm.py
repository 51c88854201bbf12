"""SSM / hybrid language models: mamba2-130m (pure SSD) and the Zamba2
hybrids (arXiv:2411.15242; transformers' ``modeling_zamba2.py``).

Every layer is a pre-norm residual Mamba2 layer, ``h <- h + Mamba(
RMSNorm(x))``, scanned over the stacked layers.  In a plain layer x = h.
A hybrid layer (one of ``cfg.hybrid_layer_ids``) first invokes a shared
transformer block on concat(h, the token embeddings):

    u = RMSNorm(concat(h, emb))                    (2 * d_model wide)
    a = Attention(u)                               (RoPE, MHA, out to d)
    m = MLP(RMSNorm(a))                            (gated GELU)
    t = m @ linear_i

with no residual inside the block, and ``t`` enters only the input of
that layer's Mamba2 mixer: x = h + t.  Invocation i runs block
i % ``num_mem_blocks`` (the blocks' weights are shared) with its own
``linear_i`` and, where ``adapter_rank`` is set, its own LoRA adapter on
the MLP's gate-up projection, ``gate_up(x) + (x @ A_i) @ B_i``.

``hybrid_table(cfg)`` gives each layer's invocation index, or -1 for a
plain layer.  The forward is one scan over layers with the table as a
scanned input and the invocation as a ``lax.cond``, so the lowered HLO
holds one Mamba2 layer and one shared block; the prefill
(``ssm_lm_prefill``) is the same scan writing each layer's decode cache;
the decode step is unrolled over layers and indexes the table
statically.  The hybrid's invocations and Mamba2 layers trace under
``spans.scope("zamba2.shared")`` and ``spans.scope("zamba2.mamba")``.
"""
from __future__ import annotations

import contextlib
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.models import attention as attn
from repro.models import mamba2 as m2
from repro.models.layers import (cross_entropy, dense_init, embed_tokens,
                                 init_embed, init_rms_norm, rms_norm,
                                 unembed)
from repro.spans import scope


def hybrid_table(cfg: ArchConfig) -> Tuple[int, ...]:
    """Per layer: -1 for a plain Mamba2 layer, else the index of the
    shared-block invocation that feeds its mixer."""
    ids = [i for i in cfg.hybrid_layer_ids if i < cfg.n_layers]
    return tuple(ids.index(i) if i in ids else -1
                 for i in range(cfg.n_layers))


def n_invocations(cfg: ArchConfig) -> int:
    return sum(i >= 0 for i in hybrid_table(cfg))


def _stack(trees):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def init_ssm_lm(cfg: ArchConfig, key) -> Dict:
    ks = jax.random.split(key, 4)
    params: Dict = {"embed": init_embed(ks[0], cfg.vocab, cfg.d_model),
                    "final_norm": init_rms_norm(cfg.d_model)}
    lkeys = jax.random.split(ks[1], cfg.n_layers)

    def one_layer(k):
        return {"ln": init_rms_norm(cfg.d_model),
                "mamba": m2.init_mamba2(k, cfg)}

    params["layers"] = _stack([one_layer(k) for k in lkeys])
    n_inv = n_invocations(cfg)
    if n_inv:
        d, f = cfg.d_model, cfg.d_ff

        def one_block(k):
            k1, k2, k3 = jax.random.split(k, 3)
            return {"ln_in": init_rms_norm(2 * d),
                    "attn": attn.init_attn(k1, cfg, d_in=2 * d),
                    "ln_mlp": init_rms_norm(d),
                    "mlp": {"gate_up": dense_init(k2, (d, 2 * f)),
                            "down": dense_init(k3, (f, d))}}

        def one_invocation(k):
            k1, k2, k3 = jax.random.split(k, 3)
            p = {"linear": dense_init(k1, (d, d))}
            if cfg.adapter_rank:
                p["adapter_in"] = dense_init(k2, (d, cfg.adapter_rank))
                p["adapter_out"] = dense_init(k3, (cfg.adapter_rank, 2 * f))
            return p

        params["shared"] = _stack([one_block(k) for k in jax.random.split(
            ks[2], cfg.num_mem_blocks)])
        params["hybrid"] = _stack([one_invocation(k) for k in
                                   jax.random.split(ks[3], n_inv)])
    return params


def _shared_block(cfg: ArchConfig, bp, ip, h, emb0, attend):
    """One invocation: ``t`` from h and the embeddings, with block ``bp``
    and the invocation's own linear and adapter ``ip``.  ``attend(p, u)``
    is the attention (full sequence or one cached step) and returns
    ``(out, extra)``; returns ``(t, extra)``."""
    dt = h.dtype
    u = rms_norm(jnp.concatenate([h, emb0], axis=-1), bp["ln_in"],
                 cfg.norm_eps)
    a, extra = attend(bp["attn"], u)
    x = rms_norm(a, bp["ln_mlp"], cfg.norm_eps)
    gu = x @ bp["mlp"]["gate_up"].astype(dt)
    if "adapter_in" in ip:
        gu = gu + (x @ ip["adapter_in"].astype(dt)) \
            @ ip["adapter_out"].astype(dt)
    g, up = jnp.split(gu, 2, axis=-1)
    m = (jax.nn.gelu(g, approximate=False) * up) \
        @ bp["mlp"]["down"].astype(dt)
    return m @ ip["linear"].astype(dt), extra


def _invocation(cfg: ArchConfig, params, inv):
    """The shared block and the invocation weights of invocation ``inv``,
    a traced index (the scans over layers)."""
    bp = jax.tree.map(lambda w: jax.lax.dynamic_index_in_dim(
        w, inv % cfg.num_mem_blocks, keepdims=False), params["shared"])
    ip = jax.tree.map(lambda w: jax.lax.dynamic_index_in_dim(
        w, inv, keepdims=False), params["hybrid"])
    return bp, ip


def _mixer_scope(cfg: ArchConfig):
    """The hybrid's Mamba2 layers trace under ``zamba2.mamba``; a pure
    SSM traces as it always has."""
    if cfg.hybrid_layer_ids:
        return scope("zamba2.mamba")
    return contextlib.nullcontext()


def ssm_lm_hidden(cfg: ArchConfig, params, tokens, *, window: int = 0):
    dt = cfg.activation_dtype
    emb0 = embed_tokens(params["embed"], tokens, dt)
    h = emb0
    B, S = h.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    use_kernel = False  # jnp reference on CPU; kernels validated separately
    hybrid = params.get("hybrid")

    def attend(p, u):
        return attn.attn_forward(cfg, p, u, positions=positions,
                                 window=window)

    def invoke(inv, hh):
        with scope("zamba2.shared"):
            t, _ = _shared_block(cfg, *_invocation(cfg, params, inv), hh,
                                 emb0, attend)
        return hh + t

    def body(h, xs):
        lp, inv = xs
        x = h
        if hybrid is not None:
            x = jax.lax.cond(inv >= 0, lambda hh: invoke(inv, hh),
                             lambda hh: hh, h)
        with _mixer_scope(cfg):
            x = rms_norm(x, lp["ln"], cfg.norm_eps)
            h = h + m2.mamba2_forward(cfg, lp["mamba"], x,
                                      use_kernel=use_kernel)
        return h, None

    scan_body = jax.checkpoint(body) if cfg.remat else body
    table = jnp.asarray(hybrid_table(cfg), jnp.int32)
    h, _ = jax.lax.scan(scan_body, h, (params["layers"], table))
    return rms_norm(h, params["final_norm"], cfg.norm_eps)


def ssm_lm_loss(cfg: ArchConfig, params, batch: Dict) -> jnp.ndarray:
    tokens, labels = batch["tokens"], batch["labels"]
    # the shared attn block (zamba2-1.2b) uses its sliding window in
    # training too
    h = ssm_lm_hidden(cfg, params, tokens,
                      window=cfg.sliding_window)
    logits = unembed(params["embed"], h, cfg.final_softcap)
    mask = (labels >= 0).astype(jnp.float32)
    return cross_entropy(logits, jnp.maximum(labels, 0), mask)


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------

def init_ssm_cache(cfg: ArchConfig, batch: int, max_len: int, dtype):
    L = cfg.n_layers
    per = m2.init_ssm_cache(cfg, batch, dtype)
    cache = {"ssm": jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (L,) + x.shape).copy(), per)}
    n_inv = n_invocations(cfg)
    if n_inv:
        hd = cfg.resolved_head_dim
        # one KV cache per invocation; a sliding-window config (zamba2-1.2b
        # at long_500k) caches only its window, as a ring buffer
        T = min(max_len, cfg.sliding_window) if cfg.sliding_window else max_len
        cache["attn"] = {
            "k": jnp.zeros((n_inv, batch, T, cfg.n_kv_heads, hd), dtype),
            "v": jnp.zeros((n_inv, batch, T, cfg.n_kv_heads, hd), dtype),
        }
    return cache


# the leaves the decode step reads only through ``.astype(activation_dtype)``
# (the embedding ``tok``, every matrix and the conv bias); ``unembed``, the
# SSM's ``a_log``, ``dt_bias``, ``d_skip`` and the norm gammas are read in
# float32
_COMPUTE_LEAVES = frozenset({
    "tok", "in_proj", "out_proj", "conv_w", "conv_b",
    "wq", "wk", "wv", "wo", "bq", "bk", "bv", "gate_up", "down",
    "linear", "adapter_in", "adapter_out"})


def decode_weights(cfg: ArchConfig, params):
    """``params`` as the decode step computes with them: each leaf it
    reads only through ``.astype(cfg.activation_dtype)`` cast once to that
    dtype, every other leaf untouched; same treedef.  The step's casts
    are then no-ops, so it serves the same bits without re-casting the
    weights at every token."""
    dt = cfg.activation_dtype

    def view(path, w):
        return w.astype(dt) if path[-1].key in _COMPUTE_LEAVES else w
    return jax.tree_util.tree_map_with_path(view, params)


def ssm_lm_prefill(cfg: ArchConfig, params, cache, tokens):
    """The decode cache after ``tokens`` (B, n) at positions 0..n-1, from
    the initial cache: one full-sequence forward, a scan over layers that
    emits each layer's Mamba2 cache (``mamba2_prefill``) and writes each
    invocation's rotated K/V into its ring of ``T`` slots (position p at
    slot p % T, the last ``T`` positions where n > T), as the decode step
    would have left them.  The prompt is padded at the end to a multiple
    of ``ssm.chunk``; padding never reaches a real position, since every
    layer is causal.  No logits: the caller's first step computes them."""
    n = tokens.shape[1]
    if n == 0:
        return cache
    dt = cfg.activation_dtype
    S = -(-n // cfg.ssm.chunk) * cfg.ssm.chunk
    emb0 = embed_tokens(params["embed"], jnp.pad(tokens, ((0, 0), (0, S - n))),
                        dt)
    positions = jnp.broadcast_to(jnp.arange(S), emb0.shape[:2])
    hybrid = params.get("hybrid")

    def attend(p, u):
        return attn.attn_forward(cfg, p, u, positions=positions,
                                 window=cfg.sliding_window)

    def invoke(inv, hh, ac):
        with scope("zamba2.shared"):
            t, kv = _shared_block(cfg, *_invocation(cfg, params, inv), hh,
                                  emb0, attend)
        T = ac["k"].shape[2]
        written = np.arange(max(n - T, 0), n)          # static positions

        def write(c, x):
            ring = jax.lax.dynamic_index_in_dim(c, inv, keepdims=False)
            ring = ring.at[:, written % T].set(x[:, written].astype(c.dtype))
            return jax.lax.dynamic_update_index_in_dim(c, ring, inv, 0)
        k, v = kv
        return hh + t, {"k": write(ac["k"], k), "v": write(ac["v"], v)}

    def body(carry, xs):
        h, ac = carry
        lp, inv, lc = xs
        x = h
        if hybrid is not None:
            x, ac = jax.lax.cond(inv >= 0, lambda a: invoke(inv, h, a),
                                 lambda a: (h, a), ac)
        with _mixer_scope(cfg):
            x = rms_norm(x, lp["ln"], cfg.norm_eps)
            out, lc = m2.mamba2_prefill(cfg, lp["mamba"], x, n, lc)
        return (h + out, ac), lc

    table = jnp.asarray(hybrid_table(cfg), jnp.int32)
    (_, ac), ssm = jax.lax.scan(body, (emb0, cache.get("attn")),
                                (params["layers"], table, cache["ssm"]))
    new_cache = {"ssm": ssm}
    if ac is not None:
        new_cache["attn"] = ac
    return new_cache


def ssm_lm_decode_step(cfg: ArchConfig, params, cache, tokens, pos):
    """tokens (B,1), pos scalar -> (logits (B,1,V), new cache).

    Each invocation's KV cache is a ring buffer of ``T`` positions (the
    whole ``max_len`` without a sliding window); rotary positions stay
    absolute, so ring wrap-around is exact for window-limited attention.
    """
    dt = cfg.activation_dtype
    emb0 = embed_tokens(params["embed"], tokens, dt)
    h = emb0
    shared, hybrid = params.get("shared"), params.get("hybrid")
    ac = cache.get("attn")

    def invoke(hh, ac, inv):
        T = ac["k"].shape[2]
        write = jnp.mod(pos, T)          # ring-buffer slot

        def attend(p, u):
            # after wrap every entry is live, so the causal mask position
            # is min(pos, T-1) while writes go to pos % T
            a, kc, vc = attn.attn_decode(
                cfg, p, u, ac["k"][inv], ac["v"][inv], write,
                window=0, rope=True, rope_pos=pos,
                mask_pos=jnp.minimum(pos, T - 1))
            return a, {"k": ac["k"].at[inv].set(kc),
                       "v": ac["v"].at[inv].set(vc)}

        bp = jax.tree.map(lambda w: w[inv % cfg.num_mem_blocks], shared)
        ip = jax.tree.map(lambda w: w[inv], hybrid)
        return _shared_block(cfg, bp, ip, hh, emb0, attend)

    new_ssm = []
    for i, inv in enumerate(hybrid_table(cfg)):  # unrolled: tiny per layer
        lp = jax.tree.map(lambda x: x[i], params["layers"])
        lc = jax.tree.map(lambda x: x[i], cache["ssm"])
        x = h
        if inv >= 0:
            with scope("zamba2.shared"):
                t, ac = invoke(h, ac, inv)
            x = h + t
        with _mixer_scope(cfg):
            x = rms_norm(x, lp["ln"], cfg.norm_eps)
            out, nc = m2.mamba2_decode(cfg, lp["mamba"], x, lc)
        h = h + out
        new_ssm.append(nc)

    new_cache = {"ssm": _stack(new_ssm)}
    if ac is not None:
        new_cache["attn"] = ac
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    logits = unembed(params["embed"], h, cfg.final_softcap)
    return logits, new_cache
