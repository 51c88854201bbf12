"""zamba2-1.2b [hybrid] — Mamba2 backbone + one shared attention block.

38L d_model=2048 32H (kv=32) d_ff=8192 vocab=32000, ssm_state=64.
Source: [arXiv:2411.15242] (Zamba2 technical report).

Runs the same hybrid code as ``zamba2-7b`` (``models/ssm_lm.py``): one
shared block invoked before the Mamba2 mixer of every sixth layer, each
invocation with its own linear.  These are not the published Zamba2-1.2B
widths: the shared attention's head size is 64 where the published model
has 128, and the per-invocation LoRA adapters are left out.  Sub-quadratic:
runs ``long_500k`` (the shared attention uses a sliding window there).
"""
from repro.configs.base import ArchConfig, SSMConfig

CONFIG = ArchConfig(
    arch_id="zamba2-1.2b",
    family="hybrid",
    source="arXiv:2411.15242",
    n_layers=38,
    d_model=2048,
    n_heads=32,
    n_kv_heads=32,
    d_ff=8192,
    vocab=32000,
    head_dim=64,
    ssm=SSMConfig(state_dim=64, head_dim=64, expand=2, conv_width=4, chunk=128),
    hybrid_layer_ids=(5, 11, 17, 23, 29, 35),
    num_mem_blocks=1,
    sliding_window=4096,       # used by the shared attn block for long_500k
    train_microbatches=2,
    persafl_option="C",
    maml_mode="hf",            # HVP-through-scan avoided (paper Eq. D1)
)
