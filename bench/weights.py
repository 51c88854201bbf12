"""Model weights made on the device from ``--seed``, in one jitted call.

The layout is the program's parameter tree (the names the model reads);
the values follow the published initialisation of the architecture, not
the program's own ``init_params``: for Mamba2 the ``mamba_ssm`` scheme
(embedding N(0, 0.02) tied into the output head; in/out projections and
the depthwise conv as PyTorch's default uniform fan-in init, the output
projection rescaled by 1/sqrt(n_layer); A = -uniform(1, 16); dt drawn
log-uniform in [0.001, 0.1] and stored through the inverse softplus as
``dt_bias``; D = 1; RMSNorm gains 1, stored as gamma = 0 because the
program scales by 1 + gamma).  Weights are float32, the type the
configuration stores them in.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from bench.reference.ssm_lm import Dims


def _uniform(key, shape, bound):
    return jax.random.uniform(key, shape, jnp.float32, -bound, bound)


def _mamba2_layer(key, dims: Dims):
    d, di, H = dims.d_model, dims.d_inner, dims.n_heads
    gn = dims.ngroups * dims.d_state
    conv_dim = di + 2 * gn
    proj = 2 * di + 2 * gn + H
    k = jax.random.split(key, 6)
    dt = jnp.exp(jax.random.uniform(k[4], (H,), jnp.float32,
                                    math.log(1e-3), math.log(1e-1)))
    dt = jnp.maximum(dt, 1e-4)
    return {
        "ln": jnp.zeros((d,), jnp.float32),
        "mamba": {
            "in_proj": _uniform(k[0], (d, proj), 1 / math.sqrt(d)),
            "conv_w": _uniform(k[1], (dims.d_conv, conv_dim),
                               1 / math.sqrt(dims.d_conv)),
            "conv_b": _uniform(k[2], (conv_dim,), 1 / math.sqrt(dims.d_conv)),
            "a_log": jnp.log(jax.random.uniform(k[3], (H,), jnp.float32,
                                                1.0, 16.0)),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "d_skip": jnp.ones((H,), jnp.float32),
            "gate_norm": jnp.zeros((di,), jnp.float32),
            "out_proj": _uniform(k[5], (di, d), 1 / math.sqrt(di))
            / math.sqrt(dims.n_layer),
        },
    }


@functools.partial(jax.jit, static_argnums=1)
def mamba2_weights(key, dims: Dims):
    """The program's mamba2 parameter tree, published init, from ``key``."""
    ke, kl = jax.random.split(key)
    tok = 0.02 * jax.random.normal(ke, (dims.vocab, dims.d_model),
                                   jnp.float32)
    layers = jax.vmap(lambda k: _mamba2_layer(k, dims))(
        jax.random.split(kl, dims.n_layer))
    return {"embed": {"tok": tok, "unembed": tok.T},
            "final_norm": jnp.zeros((dims.d_model,), jnp.float32),
            "layers": layers}


BUILDERS = {"mamba2": mamba2_weights}
