"""Bytes one aggregation window's apply needs to move.

The window folds its admitted rows into the global weights:
w <- w - sum_j s_j dequant(row_j).  Needed: every admitted row read once
(int8 codes plus one f32 scale per leaf, or f32), the weights read once
and written once.  This counts the work, not a kernel, so any kernel that
replaces the current one is held to the same count.
"""
from __future__ import annotations

from bench.counts import ssm_lm
from bench.reference.ssm_lm import Dims


def bytes_per_window(conf: dict, rows: int) -> int:
    d = Dims.from_config(conf)
    if conf["serving"]["personal_subset"] not in (None,):
        raise ValueError("head-only rows: no count yet")
    n = ssm_lm.n_params(d)
    codec = conf["serving"]["delta_dtype"]
    if codec == "int8":
        row = n + 4 * ssm_lm.n_leaves()
    elif codec == "fp32":
        row = 4 * n
    else:
        raise ValueError(f"unknown banking codec {codec!r}")
    return rows * row + 2 * 4 * n
