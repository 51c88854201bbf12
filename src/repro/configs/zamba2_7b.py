"""zamba2-7b [hybrid] — Zamba2-7B-Instruct at its published widths.

81L d_model=3584 vocab=32000; Mamba2 mixers of 112 heads x 64, d_state 64,
2 groups, conv 4, expand 2, SSD chunks of 256; two shared transformer
blocks (32 MHA heads of 224 over concat(hidden, embedding) = 7168 wide,
RoPE over the whole head, gated-GELU MLP 14336 wide) invoked alternately
A B A B before the Mamba2 mixers of the 13 ``hybrid_layer_ids``, each
invocation with its own 3584 x 3584 linear and a rank-128 LoRA adapter on
the shared MLP's gate-up projection.
Source: huggingface.co/Zyphra/Zamba2-7B-Instruct (config.json,
``model_type`` zamba2); arXiv:2411.15242.

The attention score scale is (224 / 2) ** -0.5, which config.json does
not state; it is taken from transformers' ``modeling_zamba2.py``.  The
benchmark runs a cut of this config (``bench/configs/zamba2-7b.json``):
layers 0-11, one whole A/B period.  Context 4096: the shared blocks'
decode cache holds every position, with no sliding window.
"""
from repro.configs.base import ArchConfig, SSMConfig

HYBRID_LAYER_IDS = (6, 11, 17, 23, 29, 35, 41, 47, 53, 59, 65, 71, 77)

CONFIG = ArchConfig(
    arch_id="zamba2-7b",
    family="hybrid",
    source="huggingface.co/Zyphra/Zamba2-7B-Instruct; arXiv:2411.15242",
    n_layers=81,
    d_model=3584,
    n_heads=32,
    n_kv_heads=32,
    d_ff=14336,
    vocab=32000,
    head_dim=224,
    ssm=SSMConfig(state_dim=64, head_dim=64, expand=2, conv_width=4,
                  chunk=256, n_groups=2),
    hybrid_layer_ids=HYBRID_LAYER_IDS,
    num_mem_blocks=2,
    adapter_rank=128,
    attn_scale=(224 / 2) ** -0.5,
    rope_theta=10_000.0,
    norm_eps=1e-5,
    skip_shapes=("long_500k",),   # full attention past the 4096 context
    persafl_option="C",
    maml_mode="hf",
)
