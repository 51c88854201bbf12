"""Operations one decoded sequence needs: the prompt's first P-1 tokens
advance the recurrent state and each of the T generated tokens takes one
step, every step a forward pass of one token (logits included)."""
from __future__ import annotations

from bench.counts import ssm_lm
from bench.reference.ssm_lm import Dims


def flops_per_sequence(conf: dict, mix: dict) -> int:
    d = Dims.from_config(conf)
    return ssm_lm.forward_flops(d) * (mix["prompt_len"] - 1 + mix["gen_len"])
