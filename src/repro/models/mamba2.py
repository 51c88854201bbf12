"""Mamba2 (SSD — state-space duality, arXiv:2405.21060) layer.

Full-sequence path uses the chunked SSD algorithm: quadratic attention-like
intra-chunk term + linear inter-chunk recurrence (``lax.scan`` over chunks).
This is the pure-jnp reference; the Pallas TPU kernel lives in
``repro.kernels.ssd`` and computes the identical chunked algorithm with
VMEM-tiled BlockSpecs.

Decode path is the O(1)-per-token recurrence with a conv ring buffer.
The prefill (``mamba2_prefill``) is the full-sequence path over a prompt,
returning the decode cache it leaves: the last pre-conv rows and the
chunked scan's final state.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, SSMConfig
from repro.models.layers import dense_init, rms_norm, init_rms_norm
from repro.sharding.ctx import shard_activation


def dims(cfg: ArchConfig):
    s: SSMConfig = cfg.ssm
    d_inner = s.expand * cfg.d_model
    n_heads = d_inner // s.head_dim
    conv_dim = d_inner + 2 * s.n_groups * s.state_dim
    return d_inner, n_heads, conv_dim


def init_mamba2(key, cfg: ArchConfig):
    s: SSMConfig = cfg.ssm
    d = cfg.d_model
    d_inner, n_heads, conv_dim = dims(cfg)
    ks = jax.random.split(key, 4)
    proj_out = 2 * d_inner + 2 * s.n_groups * s.state_dim + n_heads
    return {
        "in_proj": dense_init(ks[0], (d, proj_out)),
        "conv_w": dense_init(ks[1], (s.conv_width, conv_dim), scale=0.5),
        "conv_b": jnp.zeros((conv_dim,), jnp.float32),
        "a_log": jnp.log(jnp.linspace(1.0, 16.0, n_heads, dtype=jnp.float32)),
        "dt_bias": jnp.zeros((n_heads,), jnp.float32),
        "d_skip": jnp.ones((n_heads,), jnp.float32),
        "gate_norm": init_rms_norm(d_inner),
        "out_proj": dense_init(ks[3], (d_inner, d)),
    }


def _split_proj(cfg: ArchConfig, proj):
    s = cfg.ssm
    d_inner, n_heads, _ = dims(cfg)
    gn = s.n_groups * s.state_dim
    z, xbc, dt = jnp.split(proj, [d_inner, 2 * d_inner + 2 * gn], axis=-1)
    return z, xbc, dt  # dt: (..., n_heads)


def _causal_conv(xbc, conv_w, conv_b):
    """Depthwise causal conv, width W.  xbc: (B,S,Cdim); conv_w: (W,Cdim)."""
    W = conv_w.shape[0]
    pad = jnp.pad(xbc, ((0, 0), (W - 1, 0), (0, 0)))
    out = sum(pad[:, i: i + xbc.shape[1], :] * conv_w[i].astype(xbc.dtype)
              for i in range(W))
    return jax.nn.silu(out + conv_b.astype(xbc.dtype))


def gated_norm(cfg: ArchConfig, y, z, gamma):
    """RMSNorm of the gated output ``y * silu(z)``, over each of the
    ``n_groups`` groups of ``d_inner / n_groups`` channels (mamba_ssm's
    ``RMSNormGated`` with ``group_size``)."""
    x = y * jax.nn.silu(z)
    g = cfg.ssm.n_groups
    if g == 1:   # the grouped form rounds differently in bfloat16
        return rms_norm(x, gamma, cfg.norm_eps)
    grouped = x.reshape(x.shape[:-1] + (g, x.shape[-1] // g))
    return rms_norm(grouped, gamma.reshape(g, -1),
                    cfg.norm_eps).reshape(x.shape)


def ssd_chunked(x, dt, a_log, B_mat, C_mat, chunk: int, precision=None):
    """Chunked SSD scan (pure jnp reference; f32 internals).

    x: (B,S,H,P); dt: (B,S,H) (post-softplus); a_log: (H,) (A = -exp(a_log));
    B_mat/C_mat: (B,S,G,N) with H % G == 0.  Returns y: (B,S,H,P) and the
    f32 state after the last position, (B,H,P,N): the final carry of the
    scan over chunks, which the decode step's recurrence continues from.
    ``precision`` goes to the contractions; at the default a TPU rounds
    their float32 inputs to bfloat16.
    """
    Bb, S, H, P = x.shape
    G, N = B_mat.shape[2], B_mat.shape[3]
    assert S % chunk == 0, (S, chunk)
    nc, rep = S // chunk, H // G
    f32 = jnp.float32

    x = x.astype(f32).reshape(Bb, nc, chunk, H, P)
    dt = dt.astype(f32).reshape(Bb, nc, chunk, H)
    Bm = jnp.repeat(B_mat.astype(f32), rep, axis=2).reshape(Bb, nc, chunk, H, N)
    Cm = jnp.repeat(C_mat.astype(f32), rep, axis=2).reshape(Bb, nc, chunk, H, N)

    A = -jnp.exp(a_log.astype(f32))              # (H,) negative
    a = dt * A                                   # (B,nc,l,H) log-decay
    a_cum = jnp.cumsum(a, axis=2)                # inclusive cumsum within chunk
    x_dt = x * dt[..., None]

    # intra-chunk (quadratic, attention-like): L[l,s] = exp(acum_l - acum_s), l>=s
    seg = a_cum[:, :, :, None, :] - a_cum[:, :, None, :, :]   # (B,nc,l,s,H)
    tri = jnp.tril(jnp.ones((chunk, chunk), bool))
    # mask BEFORE exp: upper-triangular seg is positive and exp overflows,
    # poisoning gradients through the where
    seg = jnp.where(tri[None, None, :, :, None], seg, -jnp.inf)
    L = jnp.exp(seg)
    y_diag = jnp.einsum("bclhn,bcshn,bclsh,bcshp->bclhp", Cm, Bm, L, x_dt,
                        precision=precision)

    # per-chunk terminal states
    decay_to_end = jnp.exp(a_cum[:, :, -1:, :] - a_cum)       # (B,nc,l,H)
    chunk_states = jnp.einsum("bclhn,bclh,bclhp->bchpn", Bm, decay_to_end, x_dt,
                              precision=precision)
    chunk_decay = jnp.exp(jnp.sum(a, axis=2))                 # (B,nc,H)

    def carry_fn(state, inp):
        cs, cd = inp                                          # (B,H,P,N),(B,H)
        new = state * cd[:, :, None, None] + cs
        return new, state                                      # emit state *before* chunk

    init = jnp.zeros((Bb, H, P, N), f32)
    final, prev_states = jax.lax.scan(
        carry_fn, init,
        (jnp.moveaxis(chunk_states, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)))
    prev_states = jnp.moveaxis(prev_states, 0, 1)             # (B,nc,H,P,N)

    # inter-chunk contribution
    decay_from_start = jnp.exp(a_cum)                         # (B,nc,l,H)
    y_off = jnp.einsum("bclhn,bchpn,bclh->bclhp", Cm, prev_states,
                       decay_from_start, precision=precision)
    return (y_diag + y_off).reshape(Bb, S, H, P), final


def mamba2_forward(cfg: ArchConfig, p, x, *, use_kernel: bool = False):
    """Full-sequence Mamba2 block. x: (B,S,d) -> (B,S,d)."""
    return _mamba2_sequence(cfg, p, x, use_kernel=use_kernel)[0]


def mamba2_prefill(cfg: ArchConfig, p, x, n: int, cache):
    """Full-sequence Mamba2 block over a prompt whose first ``n`` of S
    positions are real (S a multiple of ``ssm.chunk``, the rest padding),
    from the initial decode cache: returns (out (B,S,d), the decode cache
    after position n - 1).  ``conv`` is the last ``conv_width - 1``
    pre-conv rows of the real positions, behind the cache's zeros where
    there are fewer; ``state`` the SSD scan's final state, exact because
    padded positions take dt = 0 (they decay by exp(0) = 1 and add
    nothing).  The SSD contracts at ``Precision.HIGHEST``: the state
    every later decode step reads keeps float32, as the step's
    elementwise recurrence does."""
    valid = jnp.arange(x.shape[1]) < n
    out, xbc, state = _mamba2_sequence(cfg, p, x, valid=valid,
                                       precision=jax.lax.Precision.HIGHEST)
    rows = jnp.concatenate([cache["conv"], xbc[:, :n].astype(
        cache["conv"].dtype)], axis=1)
    return out, {"conv": rows[:, n:], "state": state}


def _mamba2_sequence(cfg: ArchConfig, p, x, *, use_kernel: bool = False,
                     valid=None, precision=None):
    """The full-sequence block: (out, pre-conv xbc rows, final SSD state;
    None from the kernel).  ``valid`` (S,) zeroes dt where it is False;
    ``precision`` goes to ``ssd_chunked``."""
    s = cfg.ssm
    d_inner, n_heads, _ = dims(cfg)
    dt_ = x.dtype
    proj = x @ p["in_proj"].astype(dt_)
    z, xbc_in, dt_raw = _split_proj(cfg, proj)
    xbc = _causal_conv(xbc_in, p["conv_w"], p["conv_b"])
    gn = s.n_groups * s.state_dim
    xs, Bm, Cm = jnp.split(xbc, [d_inner, d_inner + gn], axis=-1)
    Bb, S = x.shape[:2]
    xs = xs.reshape(Bb, S, n_heads, s.head_dim)
    Bm = Bm.reshape(Bb, S, s.n_groups, s.state_dim)
    Cm = Cm.reshape(Bb, S, s.n_groups, s.state_dim)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                         + p["dt_bias"].astype(jnp.float32))
    if valid is not None:
        dt = jnp.where(valid[None, :, None], dt, 0.0)
    if use_kernel:
        from repro.kernels.ssd import ops as ssd_ops
        y, state = ssd_ops.ssd(xs, dt, p["a_log"], Bm, Cm,
                               chunk=s.chunk), None
    else:
        y, state = ssd_chunked(xs, dt, p["a_log"], Bm, Cm, chunk=s.chunk,
                               precision=precision)
    y = y + p["d_skip"].astype(jnp.float32)[None, None, :, None] \
        * xs.astype(jnp.float32)
    y = y.reshape(Bb, S, d_inner).astype(dt_)
    y = shard_activation(y, "ssm_out")
    y = gated_norm(cfg, y, z, p["gate_norm"])
    return y @ p["out_proj"].astype(dt_), xbc_in, state


# ---------------------------------------------------------------------------
# decode: O(1) recurrence
# ---------------------------------------------------------------------------

def init_ssm_cache(cfg: ArchConfig, batch: int, dtype):
    s = cfg.ssm
    d_inner, n_heads, conv_dim = dims(cfg)
    return {
        "conv": jnp.zeros((batch, s.conv_width - 1, conv_dim), dtype),
        "state": jnp.zeros((batch, n_heads, s.head_dim, s.state_dim),
                           jnp.float32),
    }


def mamba2_decode(cfg: ArchConfig, p, x, cache) -> Tuple[jnp.ndarray, dict]:
    """One-token step. x: (B,1,d) -> (out (B,1,d), new cache)."""
    s = cfg.ssm
    d_inner, n_heads, conv_dim = dims(cfg)
    dt_ = x.dtype
    Bb = x.shape[0]
    proj = x[:, 0] @ p["in_proj"].astype(dt_)                 # (B, proj)
    z, xbc, dt_raw = _split_proj(cfg, proj)
    window = jnp.concatenate([cache["conv"], xbc[:, None, :]], axis=1)  # (B,W,C)
    conv_out = jnp.einsum("bwc,wc->bc", window,
                          p["conv_w"].astype(dt_)) + p["conv_b"].astype(dt_)
    xbc = jax.nn.silu(conv_out)
    new_conv = window[:, 1:, :]
    gn = s.n_groups * s.state_dim
    xs, Bm, Cm = jnp.split(xbc, [d_inner, d_inner + gn], axis=-1)
    xs = xs.reshape(Bb, n_heads, s.head_dim).astype(jnp.float32)
    Bm = Bm.reshape(Bb, s.n_groups, s.state_dim).astype(jnp.float32)
    Cm = Cm.reshape(Bb, s.n_groups, s.state_dim).astype(jnp.float32)
    rep = n_heads // s.n_groups
    Bm = jnp.repeat(Bm, rep, axis=1)                          # (B,H,N)
    Cm = jnp.repeat(Cm, rep, axis=1)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                         + p["dt_bias"].astype(jnp.float32))  # (B,H)
    A = -jnp.exp(p["a_log"].astype(jnp.float32))
    da = jnp.exp(dt * A)                                      # (B,H)
    state = cache["state"] * da[:, :, None, None] \
        + jnp.einsum("bh,bhn,bhp->bhpn", dt, Bm, xs)
    y = jnp.einsum("bhpn,bhn->bhp", state, Cm)
    y = y + p["d_skip"].astype(jnp.float32)[None, :, None] * xs
    y = y.reshape(Bb, d_inner).astype(dt_)
    y = gated_norm(cfg, y, z, p["gate_norm"])
    out = (y @ p["out_proj"].astype(dt_))[:, None, :]
    return out, {"conv": new_conv, "state": state}
