"""Zamba2 weights made on the device from ``--seed``, in one jitted call.

The layout is the program's parameter tree (``models/ssm_lm.py``): stacked
Mamba2 layers, the shared blocks stacked over ``num_mem_blocks``, and each
invocation's linear and adapter stacked over the invocations.  The values
follow transformers' Zamba2 initialisation (``_init_weights``): every
linear and the embedding N(0, 0.02) (``initializer_range``), the output
head starting tied (the embedding transposed); the Mamba2 mixers as
``Zamba2MambaMixer``: A = arange(1, heads + 1) stored as its log, dt drawn
log-uniform in [``time_step_min``, ``time_step_max``], floored at
``time_step_floor`` and stored through the inverse softplus as
``dt_bias``, D = 1, the depthwise conv as PyTorch's default uniform fan-in
init; RMSNorm gains 1, stored as gamma = 0 (the program scales by
1 + gamma).  Float32, the type the configuration stores them in.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from bench.reference.zamba2 import Dims

STD = 0.02


def _normal(key, shape):
    return STD * jax.random.normal(key, shape, jnp.float32)


def _mamba2_layer(key, dims: Dims, dt_min, dt_max, dt_floor):
    d, di, H = dims.d_model, dims.d_inner, dims.n_heads
    gn = dims.ngroups * dims.d_state
    conv_dim = di + 2 * gn
    k = jax.random.split(key, 5)
    dt = jnp.exp(jax.random.uniform(k[3], (H,), jnp.float32,
                                    math.log(dt_min), math.log(dt_max)))
    dt = jnp.maximum(dt, dt_floor)
    bound = 1 / math.sqrt(dims.d_conv)
    return {
        "ln": jnp.zeros((d,), jnp.float32),
        "mamba": {
            "in_proj": _normal(k[0], (d, 2 * di + 2 * gn + H)),
            "conv_w": jax.random.uniform(k[1], (dims.d_conv, conv_dim),
                                         jnp.float32, -bound, bound),
            "conv_b": jax.random.uniform(k[2], (conv_dim,), jnp.float32,
                                         -bound, bound),
            "a_log": jnp.log(jnp.arange(1, H + 1, dtype=jnp.float32)),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "d_skip": jnp.ones((H,), jnp.float32),
            "gate_norm": jnp.zeros((di,), jnp.float32),
            "out_proj": _normal(k[4], (di, d)),
        },
    }


def _block(key, dims: Dims):
    d, f = dims.d_model, dims.d_ff
    width = dims.attn_heads * dims.attn_head_dim
    k = jax.random.split(key, 6)
    return {"ln_in": jnp.zeros((2 * d,), jnp.float32),
            "attn": {"wq": _normal(k[0], (2 * d, width)),
                     "wk": _normal(k[1], (2 * d, width)),
                     "wv": _normal(k[2], (2 * d, width)),
                     "wo": _normal(k[3], (width, d))},
            "ln_mlp": jnp.zeros((d,), jnp.float32),
            "mlp": {"gate_up": _normal(k[4], (d, 2 * f)),
                    "down": _normal(k[5], (f, d))}}


def _invocation(key, dims: Dims):
    k = jax.random.split(key, 3)
    return {"linear": _normal(k[0], (dims.d_model, dims.d_model)),
            "adapter_in": _normal(k[1], (dims.d_model, dims.rank)),
            "adapter_out": _normal(k[2], (dims.rank, 2 * dims.d_ff))}


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def zamba2_weights(key, dims: Dims, dt_min: float = 1e-3,
                   dt_max: float = 0.1, dt_floor: float = 1e-4):
    """The program's zamba2 parameter tree, transformers' init, from
    ``key``."""
    ke, kl, kb, ki = jax.random.split(key, 4)
    tok = _normal(ke, (dims.vocab, dims.d_model))
    return {
        "embed": {"tok": tok, "unembed": tok.T},
        "final_norm": jnp.zeros((dims.d_model,), jnp.float32),
        "layers": jax.vmap(lambda k: _mamba2_layer(
            k, dims, dt_min, dt_max, dt_floor))(
                jax.random.split(kl, dims.n_layer)),
        "shared": jax.vmap(lambda k: _block(k, dims))(
            jax.random.split(kb, dims.n_blocks)),
        "hybrid": jax.vmap(lambda k: _invocation(k, dims))(
            jax.random.split(ki, dims.n_invocations)),
    }


def make_weights(conf: dict, key):
    """The weights a configuration file describes (its time-step range)."""
    return zamba2_weights(key, Dims.from_config(conf),
                          conf["time_step_min"], conf["time_step_max"],
                          conf["time_step_floor"])
