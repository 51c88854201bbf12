"""Operation and parameter counts of the Zamba2 hybrid, and the operations
one personalization request over its adapters needs, from the sizes in a
configuration file (``bench/reference/zamba2.Dims``).

Counted as in ``bench/counts/ssm_lm.py``: a multiply-add is 2 operations,
the selective scan as its linear recurrence, causal attention over the
positions a query sees (sum over t of t keys, for scores and values), the
embedding lookup free, elementwise work and norms not counted.

A request is option C's prox solve over the adapters (K gradient steps)
on an L-token stream.  The backbone is frozen, so the layers before the
first hybrid layer do not depend on the personal variables: their forward
is needed once per request, not K times.  Each of the K steps needs the
forward of the rest (from the first hybrid layer to the output head), and
the backward from the loss to the adapters:

* the output head and every Mamba2 layer from the first hybrid layer on:
  the input gradient only (1x their forward matmuls; the scan 2x its
  forward, for its four inputs);
* each invocation: the input gradient of its linear and down projection,
  and the adapter's weight gradients (``adapter_grad_flops``); an
  invocation after the first also passes the gradient on to h, through its
  gate-up, attention and the h half of its q/k/v projections.

Recomputation (remat, the K-fold forward of the frozen leading layers)
and the quadratic intra-chunk form of the program's scan are not counted.
"""
from __future__ import annotations

from bench.counts import ssm_lm
from bench.reference.zamba2 import Dims


def mamba_layer_params(d: Dims) -> int:
    return ssm_lm.layer_params(d)


def block_params(d: Dims) -> int:
    """One shared block: input norm, q/k/v/o, MLP norm, gate-up and down."""
    width = d.attn_heads * d.attn_head_dim
    return (2 * d.d_model + 3 * 2 * d.d_model * width + width * d.d_model
            + d.d_model + d.d_model * 2 * d.d_ff + d.d_ff * d.d_model)


def invocation_params(d: Dims) -> int:
    """One invocation's linear and adapter."""
    return d.d_model ** 2 + d.rank * (d.d_model + 2 * d.d_ff)


def adapter_params(d: Dims) -> int:
    return d.n_invocations * d.rank * (d.d_model + 2 * d.d_ff)


def n_params(d: Dims) -> int:
    return (2 * d.vocab * d.d_model + d.d_model
            + d.n_layer * mamba_layer_params(d)
            + d.n_blocks * block_params(d)
            + d.n_invocations * invocation_params(d))


def mamba_forward_flops(d: Dims) -> int:
    """One Mamba2 layer, per token."""
    return (2 * d.d_model * ssm_lm.proj_width(d) + 2 * d.d_inner * d.d_model
            + 2 * d.d_conv * ssm_lm.conv_dim(d) + scan_flops(d))


def scan_flops(d: Dims) -> int:
    return 4 * d.n_heads * d.headdim * d.d_state


def attn_core_flops(d: Dims, length: int) -> int:
    """Scores and values of causal attention, per token of an L-token
    stream: 4 H hd per key seen, (L + 1) / 2 keys on average."""
    return 2 * (length + 1) * d.attn_heads * d.attn_head_dim


def qkv_flops(d: Dims) -> int:
    return 3 * 2 * (2 * d.d_model) * d.attn_heads * d.attn_head_dim


def mlp_flops(d: Dims) -> int:
    """Gate-up and down projections, per token."""
    return 2 * d.d_model * 2 * d.d_ff + 2 * d.d_ff * d.d_model


def adapter_flops(d: Dims) -> int:
    return 2 * d.d_model * d.rank + 2 * d.rank * 2 * d.d_ff


def invocation_forward_flops(d: Dims, length: int) -> int:
    """One shared-block invocation with its adapter and linear, per token."""
    width = d.attn_heads * d.attn_head_dim
    return (qkv_flops(d) + attn_core_flops(d, length) + 2 * width * d.d_model
            + mlp_flops(d) + adapter_flops(d) + 2 * d.d_model ** 2)


def head_flops(d: Dims) -> int:
    return 2 * d.d_model * d.vocab


def forward_flops(d: Dims, length: int) -> int:
    """The whole forward, per token of an L-token stream."""
    return (d.n_layer * mamba_forward_flops(d)
            + d.n_invocations * invocation_forward_flops(d, length)
            + head_flops(d))


def adapter_grad_flops(d: Dims) -> int:
    """Weight gradients of one adapter, per token: dB = (x A)^T g, and
    dA = x^T (g B^T), which needs g B^T."""
    return 2 * d.rank * 2 * d.d_ff + 2 * 2 * d.d_ff * d.rank \
        + 2 * d.d_model * d.rank


def invocation_backward_flops(d: Dims, length: int, to_h: bool) -> int:
    """Backward of one invocation to its adapter, per token; with
    ``to_h``, on to h as well."""
    width = d.attn_heads * d.attn_head_dim
    out = 2 * d.d_model ** 2 + 2 * d.d_ff * d.d_model + adapter_grad_flops(d)
    if to_h:
        out += (2 * d.d_model * 2 * d.d_ff + 2 * 2 * d.d_ff * d.rank
                + 2 * d.rank * d.d_model           # gate-up and adapter
                + 2 * width * d.d_model            # output projection
                + 2 * attn_core_flops(d, length)   # scores and values
                + qkv_flops(d) // 2)               # the h half of u
    return out


def first_hybrid(d: Dims) -> int:
    return min(i for i in d.hybrid_ids if i < d.n_layer)


def frozen_prefix_flops(d: Dims) -> int:
    """The layers before the first hybrid layer, per token: needed once
    per request."""
    return first_hybrid(d) * mamba_forward_flops(d)


def step_flops(d: Dims, length: int) -> int:
    """One prox step past the frozen prefix, per token: forward, and
    backward to the adapters."""
    rest = d.n_layer - first_hybrid(d)
    fwd = forward_flops(d, length) - frozen_prefix_flops(d)
    bwd = (head_flops(d)
           + rest * (mamba_forward_flops(d) + scan_flops(d))
           + sum(invocation_backward_flops(d, length, to_h=j > 0)
                 for j in range(d.n_invocations)))
    return fwd + bwd


def flops_per_request(conf: dict, mix: dict) -> int:
    d = Dims.from_config(conf)
    L = mix["stream_len"]
    K = conf["personalization"]["inner_steps"]
    return L * (frozen_prefix_flops(d) + K * step_flops(d, L))
