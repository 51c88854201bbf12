"""Operation and parameter counts of the Mamba2 language model, from the
sizes in a configuration file.

Only the operations the mathematics needs are counted: a multiply-add is
2 operations; the selective scan as its linear recurrence (state update
and read-out, 2 H P N multiply-adds per token and layer); the depthwise
causal conv; the embedding lookup is free.  Recomputation (remat) and the
quadratic intra-chunk form of the program's scan are not counted.
"""
from __future__ import annotations

from bench.reference.ssm_lm import Dims


def proj_width(d: Dims) -> int:
    return 2 * d.d_inner + 2 * d.ngroups * d.d_state + d.n_heads


def conv_dim(d: Dims) -> int:
    return d.d_inner + 2 * d.ngroups * d.d_state


def layer_params(d: Dims) -> int:
    return (d.d_model                                 # ln
            + d.d_model * proj_width(d)               # in_proj
            + (d.d_conv + 1) * conv_dim(d)            # conv_w, conv_b
            + 3 * d.n_heads                           # a_log, dt_bias, D
            + d.d_inner                               # gate_norm
            + d.d_inner * d.d_model)                  # out_proj


def n_params(d: Dims) -> int:
    return 2 * d.vocab * d.d_model + d.d_model + d.n_layer * layer_params(d)


def n_leaves() -> int:
    """Leaves of the parameter tree: token embedding, output head, final
    norm, and the layer norm plus 8 mixer leaves stacked over layers."""
    return 12


def head_forward_flops(d: Dims) -> int:
    """Output head (unembedding) per token."""
    return 2 * d.d_model * d.vocab


def backbone_forward_flops(d: Dims) -> int:
    """Everything below the head, per token."""
    per_layer = (2 * d.d_model * proj_width(d)
                 + 2 * d.d_inner * d.d_model
                 + 2 * d.d_conv * conv_dim(d)
                 + 4 * d.n_heads * d.headdim * d.d_state)
    return d.n_layer * per_layer


def forward_flops(d: Dims) -> int:
    return backbone_forward_flops(d) + head_forward_flops(d)
