"""Operations one personalization request needs (option C prox solve).

Full-model: K gradient evaluations of the loss over the stream, each a
forward and a backward pass (the backward twice the forward).  The
program's solver also evaluates a (K+1)-th gradient for the inexactness
level nu, which the serving strategy discards, so it is not counted.

Head-only (``personal_subset`` is the output head): the frozen backbone's
forward once, then K evaluations of the head's forward and weight
gradient.  Recomputing the backbone on every step is not counted.
"""
from __future__ import annotations

from bench.counts import ssm_lm
from bench.reference.ssm_lm import Dims


def flops_per_request(conf: dict, mix: dict) -> int:
    d = Dims.from_config(conf)
    L = mix["stream_len"]
    K = conf["personalization"]["inner_steps"]
    subset = conf["serving"]["personal_subset"]
    if subset is None:
        return 3 * ssm_lm.forward_flops(d) * L * K
    if subset != "embed/unembed":
        raise ValueError(f"no count for personal subset {subset!r}")
    return (ssm_lm.backbone_forward_flops(d) * L
            + 2 * ssm_lm.head_forward_flops(d) * L * K)
