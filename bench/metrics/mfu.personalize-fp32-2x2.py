"""Operations the window's completed requests need
(``bench/counts/personalize_step.py``) per second of the traced window,
over the bf16 peak of every chip that ran (the four of the mesh)."""
from bench.readers import mfu


def read(data):
    return mfu(data, data["counts"]["flops_per_request"],
               data["window"]["completed"])
