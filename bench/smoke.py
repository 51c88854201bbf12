"""Smoke-size cells for the CPU rehearsal tests: the benchmark's own
configuration and mixes with the program's ``reduce_for_smoke`` sizes and
short streams, so a whole run takes seconds on a CPU.

The chip's limits of ``correct`` are set at the cell's own size and do not
carry down to a model this small (its logits are a hundred times smaller),
so the smoke cells compare against limits of their own, set the same way
from CPU readings at these sizes (seeds 1-3, ``bench/control.py``'s
``readings`` on the smoke spec): the float32 program reads at most
delta_norm_gap 9.6e-5, delta_cos_dist 3.2e-7, change_norm_gap 4.5e-5,
change_cos_dist 7.6e-8 and logit_gap 0; the float8 control at least
0.039, 0.0088, 0.0257, 0.0077 and 0.033."""
from __future__ import annotations

import copy

from bench import run

SMOKE_SIZES = {"d_model": 256, "n_layer": 2, "vocab_size": 512,
               "layer_norm_epsilon": 1e-6, "traffic_vocab": 512,
               "compute_dtype": "float32"}
SMOKE_SSM = {"d_state": 16, "headdim": 32, "chunk_size": 16}
SMOKE_MIX = {"personalize": {"stream_len": 32, "user_vocab": 64,
                             "request_pool": 64},
             # more users than the configuration's head cache holds
             "decode": {"users": 5, "prompt_len": 8, "gen_len": 4,
                        "stream_len": 32, "user_vocab": 64,
                        "prompt_pool": 3}}
SMOKE_LIMITS = {"delta_norm_gap": 0.002, "delta_cos_dist": 1e-4,
                "change_norm_gap": 0.002, "change_cos_dist": 1e-4,
                "logit_gap": 0.004}


def smoke_spec(workload: str, root: str = run.ROOT) -> dict:
    spec = copy.deepcopy(run.cell_spec(workload, root))
    conf = spec["config"]
    conf.update(SMOKE_SIZES)
    conf["ssm_layer"] = dict(conf["ssm_layer"], **SMOKE_SSM)
    conf["program"] = dict(conf["program"], smoke=True)
    conf["limits"] = dict(SMOKE_LIMITS)
    spec["traffic"].update(SMOKE_MIX[spec["traffic"]["kind"]])
    return spec
