"""Plain reference of the Mamba2 language model (arXiv:2405.21060).

Straightforward ``jax.numpy`` written from the paper and the config file
alone: no kernels, no chunking, no cache, one sequence at a time.  The
selective state-space mixer is computed in its quadratic "dual" form over
the whole sequence,

    y_t = sum_{s <= t} (C_t . B_s) exp(sum_{s < r <= t} dt_r A) dt_s x_s
          + D x_t,

which is exact and independent of the chunked scan the program runs.
Matrix products run at ``Precision.HIGHEST``.

``rnd`` is the storage precision of the computation: ``exact`` keeps
float32 everywhere; ``fp8`` rounds every matmul operand, the residual
stream and the mixer's inputs and outputs to float8 (e4m3 values, e5m2
gradients, each tensor scaled to the type's range), the lower-precision
control for a configuration that computes in bfloat16.

Parameters are read by name from the benchmark's own weights
(``bench/weights.py``); nothing here imports the program.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
FP8 = jnp.float8_e4m3fn


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

    @property
    def d_inner(self) -> int:
        return self.expand * self.d_model

    @property
    def n_heads(self) -> int:
        return self.d_inner // self.headdim

    @classmethod
    def from_config(cls, conf: dict) -> "Dims":
        s = conf["ssm_layer"]
        return cls(d_model=conf["d_model"], n_layer=conf["n_layer"],
                   vocab=conf["vocab_size"], d_state=s["d_state"],
                   d_conv=s["d_conv"], expand=s["expand"],
                   headdim=s["headdim"], ngroups=s["ngroups"],
                   eps=conf["layer_norm_epsilon"])


def exact(x):
    return x.astype(F32)


def _scaled_round(x, dtype):
    """Round to ``dtype`` with a per-tensor scale that maps the tensor's
    absolute maximum onto the type's largest value (the usual float8
    recipe), and back to float32."""
    x = x.astype(F32)
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, float(jnp.finfo(dtype).max) / amax, 1.0)
    return (x * scale).astype(dtype).astype(F32) / scale


@jax.custom_vjp
def fp8(x):
    """float8: values in e4m3, their gradients in e5m2, each scaled."""
    return _scaled_round(x, FP8)


def _fp8_fwd(x):
    return fp8(x), None


def _fp8_bwd(_, g):
    return (_scaled_round(g, jnp.float8_e5m2),)


fp8.defvjp(_fp8_fwd, _fp8_bwd)


ROUNDING = {"float32": exact, "float8_e4m3fn": fp8}


def mm(a, b, rnd):
    return jnp.matmul(rnd(a), rnd(b), precision=HIGHEST)


def rms_norm(x, gamma, eps):
    x = x.astype(F32)
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * (1.0 + gamma)


def ssd_dual(x, dt, a_log, b, c):
    """x (S,H,P), dt (S,H), a_log (H,), b/c (S,G,N) -> y (S,H,P)."""
    S, H, _ = x.shape
    rep = H // b.shape[1]
    a = dt * -jnp.exp(a_log)                                  # (S,H)
    cum = jnp.cumsum(a, axis=0)
    causal = jnp.tril(jnp.ones((S, S), bool))[:, :, None]
    seg = jnp.where(causal, cum[:, None, :] - cum[None, :, :], -jnp.inf)
    decay = jnp.exp(seg)                                      # (t,s,H)
    cb = jnp.einsum("tgn,sgn->tsg", c, b, precision=HIGHEST)
    cb = jnp.repeat(cb, rep, axis=2)                          # (t,s,H)
    return jnp.einsum("tsh,sh,shp->thp", cb * decay, dt, x,
                      precision=HIGHEST)


def mamba_layer(p, h, dims: Dims, rnd):
    """One pre-norm residual Mamba2 block on one sequence h (S, d)."""
    S = h.shape[0]
    di, H, P = dims.d_inner, dims.n_heads, dims.headdim
    gn = dims.ngroups * dims.d_state
    x = rnd(rms_norm(h, p["ln"], dims.eps))
    m = p["mamba"]
    proj = rnd(mm(x, m["in_proj"], rnd))
    z, xbc, dt_raw = proj[:, :di], proj[:, di:2 * di + 2 * gn], \
        proj[:, 2 * di + 2 * gn:]
    W = dims.d_conv
    padded = jnp.concatenate([jnp.zeros((W - 1, xbc.shape[1]), F32), xbc])
    conv = sum(padded[i:i + S] * rnd(m["conv_w"][i]) for i in range(W))
    xbc = rnd(jax.nn.silu(conv + rnd(m["conv_b"])))
    xs = xbc[:, :di].reshape(S, H, P)
    b = xbc[:, di:di + gn].reshape(S, dims.ngroups, dims.d_state)
    c = xbc[:, di + gn:].reshape(S, dims.ngroups, dims.d_state)
    dt = jax.nn.softplus(dt_raw + m["dt_bias"])
    y = ssd_dual(xs, dt, m["a_log"], b, c) + m["d_skip"][None, :, None] * xs
    y = rnd(y.reshape(S, di))
    y = rnd(rms_norm(y * jax.nn.silu(z), m["gate_norm"], dims.eps))
    return rnd(h + rnd(mm(y, m["out_proj"], rnd)))


def hidden(params, tokens, dims: Dims, rnd=exact):
    """tokens int (S,) -> final-normed hidden states (S, d) in float32."""
    h = rnd(params["embed"]["tok"][tokens])

    def body(h, lp):
        return mamba_layer(lp, h, dims, rnd), None

    h, _ = jax.lax.scan(body, h, params["layers"])
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
