"""Plain reference of the Zamba2 hybrid language model (arXiv:2411.15242,
as transformers' ``modeling_zamba2.py`` computes it), and of its prox
solve over the per-invocation adapters.

Written from the configuration file (``bench/configs/zamba2-7b.json``, the
keys of the published config.json) alone: no kernels, no chunking, no
cache, one sequence at a time, matrix products at ``Precision.HIGHEST``,
float32 throughout.  Every layer is ``h <- h + Mamba2(RMSNorm(x))``; x = h,
except in a hybrid layer (``hybrid_layer_ids``), where the i-th invocation
of a shared block feeds the mixer, x = h + t_i:

    u   = RMSNorm(concat(h, emb))                  (embeddings, 2 d wide)
    a   = o_proj(softmax(q k^T / sqrt(hd / 2)) v)  (RoPE on q and k,
                                                    whole head, causal)
    x'  = RMSNorm(a)
    gu  = x' W_gate_up + (x' A_i) B_i              (invocation i's adapter)
    t_i = (gelu(gu[:f]) * gu[f:]) W_down W_i       (invocation i's linear)

Block i % ``num_mem_blocks`` is used (its weights are shared); GELU is the
erf form.  The Mamba2 mixer is the selective SSM in its quadratic dual
form (``ssm_lm.ssd_dual``), with the gated RMSNorm taken over each of the
``mamba_ngroups`` groups of channels (mamba_ssm's ``RMSNormGated``).
RMSNorm gains are stored as gamma with the scale 1 + gamma, and the output
head is a leaf of its own (``embed/unembed``), as the benchmark's weights
(``bench/weights_zamba2.py``) hold them.

Departures: none in the mathematics.  The score scale (hd / 2) ** -0.5 is
what ``modeling_zamba2.py`` uses and config.json does not state.  The
layers are checkpointed (recomputed in the backward pass) so that the
reference fits the chip beside the weights; that changes no number.

``rnd`` is the storage precision, as in ``ssm_lm``: ``exact`` or ``fp8``
(the control).  Nothing here imports the program.
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from bench.reference.ssm_lm import F32, HIGHEST, exact, mm, rms_norm, \
    ssd_dual


class Dims(NamedTuple):
    d_model: int
    n_layer: int
    vocab: int
    d_state: int
    d_conv: int
    expand: int
    headdim: int
    ngroups: int
    eps: float
    attn_heads: int
    attn_head_dim: int
    d_ff: int
    hybrid_ids: Tuple[int, ...]
    n_blocks: int
    rank: int
    rope_theta: float

    @property
    def d_inner(self) -> int:
        return self.expand * self.d_model

    @property
    def n_heads(self) -> int:
        return self.d_inner // self.headdim

    @property
    def n_invocations(self) -> int:
        return sum(i < self.n_layer for i in self.hybrid_ids)

    @property
    def attn_scale(self) -> float:
        return (self.attn_head_dim / 2) ** -0.5

    @classmethod
    def from_config(cls, conf: dict) -> "Dims":
        return cls(d_model=conf["hidden_size"],
                   n_layer=conf["num_hidden_layers"],
                   vocab=conf["vocab_size"], d_state=conf["mamba_d_state"],
                   d_conv=conf["mamba_d_conv"], expand=conf["mamba_expand"],
                   headdim=conf["mamba_headdim"],
                   ngroups=conf["mamba_ngroups"], eps=conf["rms_norm_eps"],
                   attn_heads=conf["num_attention_heads"],
                   attn_head_dim=conf["attention_head_dim"],
                   d_ff=conf["intermediate_size"],
                   hybrid_ids=tuple(conf["hybrid_layer_ids"]),
                   n_blocks=conf["num_mem_blocks"],
                   rank=conf["adapter_rank"],
                   rope_theta=float(conf["rope_theta"]))


def rope(x, theta: float):
    """x (S, H, hd) rotated at positions 0..S-1 over the whole head
    (``rotate_half`` form: the two halves pair up)."""
    S, _, hd = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
    rot = jnp.concatenate([-x[..., hd // 2:], x[..., :hd // 2]], axis=-1)
    return x * jnp.cos(ang) + rot * jnp.sin(ang)


def attention(p, u, d: Dims, rnd):
    S, H, hd = u.shape[0], d.attn_heads, d.attn_head_dim
    q = rope(mm(u, p["wq"], rnd).reshape(S, H, hd), d.rope_theta)
    k = rope(mm(u, p["wk"], rnd).reshape(S, H, hd), d.rope_theta)
    v = mm(u, p["wv"], rnd).reshape(S, H, hd)
    s = jnp.einsum("shd,thd->hst", rnd(q), rnd(k),
                   precision=HIGHEST) * d.attn_scale
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    pr = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hst,thd->shd", rnd(pr), rnd(v), precision=HIGHEST)
    return mm(rnd(o.reshape(S, H * hd)), p["wo"], rnd)


def shared_block(bp, ip, h, emb, d: Dims, rnd):
    """t of one invocation: block ``bp``, the invocation's ``ip``."""
    u = rnd(rms_norm(jnp.concatenate([h, emb], axis=-1), bp["ln_in"],
                     d.eps))
    a = rnd(attention(bp["attn"], u, d, rnd))
    x = rnd(rms_norm(a, bp["ln_mlp"], d.eps))
    lora = mm(rnd(mm(x, ip["adapter_in"], rnd)), ip["adapter_out"], rnd)
    gu = mm(x, bp["mlp"]["gate_up"], rnd) + lora
    m = rnd(jax.nn.gelu(gu[:, :d.d_ff], approximate=False)
            * gu[:, d.d_ff:])
    return rnd(mm(rnd(mm(m, bp["mlp"]["down"], rnd)), ip["linear"], rnd))


def mamba_layer(p, h, x, d: Dims, rnd):
    """h + Mamba2(RMSNorm(x)) on one sequence (S, d)."""
    S = h.shape[0]
    di, H, P, G = d.d_inner, d.n_heads, d.headdim, d.ngroups
    gn = G * d.d_state
    m = p["mamba"]
    x = rnd(rms_norm(x, p["ln"], d.eps))
    proj = rnd(mm(x, m["in_proj"], rnd))
    z, xbc, dt_raw = proj[:, :di], proj[:, di:2 * di + 2 * gn], \
        proj[:, 2 * di + 2 * gn:]
    W = d.d_conv
    padded = jnp.concatenate([jnp.zeros((W - 1, xbc.shape[1]), F32), xbc])
    conv = sum(padded[i:i + S] * rnd(m["conv_w"][i]) for i in range(W))
    xbc = rnd(jax.nn.silu(conv + rnd(m["conv_b"])))
    xs = xbc[:, :di].reshape(S, H, P)
    b = xbc[:, di:di + gn].reshape(S, G, d.d_state)
    c = xbc[:, di + gn:].reshape(S, G, d.d_state)
    dt = jax.nn.softplus(dt_raw + m["dt_bias"])
    y = ssd_dual(xs, dt, m["a_log"], b, c) + m["d_skip"][None, :, None] * xs
    y = rnd(y.reshape(S, di)) * jax.nn.silu(z)
    y = rms_norm(y.reshape(S, G, di // G), m["gate_norm"].reshape(G, -1),
                 d.eps)
    y = rnd(y.reshape(S, di))
    return rnd(h + rnd(mm(y, m["out_proj"], rnd)))


def hidden(params, tokens, dims: Dims, rnd=exact):
    """tokens int (S,) -> final-normed hidden states (S, d) in float32.
    The plain layers between two hybrid layers run as one scan."""
    emb = rnd(params["embed"]["tok"][tokens])
    ids = [i for i in dims.hybrid_ids if i < dims.n_layer]

    def layer(i):
        return jax.tree.map(lambda x: x[i], params["layers"])

    def plain(h, i):
        return mamba_layer(layer(i), h, h, dims, rnd), None

    def hybrid(lp, bp, ip, h):
        return mamba_layer(lp, h, h + shared_block(bp, ip, h, emb, dims,
                                                   rnd), dims, rnd)

    h, start = emb, 0
    for j, i in enumerate(ids + [dims.n_layer]):
        if i > start:
            h, _ = jax.lax.scan(jax.checkpoint(plain), h,
                                jnp.arange(start, i))
        if i < dims.n_layer:
            bp = jax.tree.map(lambda x: x[j % dims.n_blocks],
                              params["shared"])
            ip = jax.tree.map(lambda x: x[j], params["hybrid"])
            h = jax.checkpoint(hybrid)(layer(i), bp, ip, h)
        start = i + 1
    return rms_norm(h, params["final_norm"], dims.eps)


def logits(params, tokens, dims: Dims, rnd=exact):
    """tokens (S,) -> next-token logits (S, V) in float32."""
    return mm(hidden(params, tokens, dims, rnd),
              params["embed"]["unembed"], rnd)


def loss(params, tokens, labels, dims: Dims, rnd=exact):
    """Mean next-token cross-entropy of one sequence."""
    lg = logits(params, tokens, dims, rnd)
    gold = jnp.take_along_axis(lg, labels[:, None], axis=1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(lg, axis=1) - gold)


def merge(full, sub):
    """``full`` with the leaves that the dict tree ``sub`` holds replaced
    by ``sub``'s (the personal subset over the frozen backbone)."""
    if not isinstance(sub, dict):
        return sub
    return {k: merge(v, sub[k]) if k in sub else v for k, v in full.items()}


def subset_loss(dims: Dims, rnd=exact):
    """``loss(sub, (backbone, tokens, labels))``: the loss as a function
    of the personal subset alone, the backbone passed in with the batch,
    for ``personalize.prox_delta`` over the subset."""
    def f(sub, batch):
        backbone, tokens, labels = batch
        return loss(merge(backbone, sub), tokens, labels, dims, rnd)
    return f
