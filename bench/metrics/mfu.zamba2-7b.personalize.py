"""Operations the window's completed requests need
(``bench/counts/zamba2.py``: the frozen leading layers once per request,
the rest K times, forward and backward to the adapters) per second of the
traced window, over the chip's bf16 peak."""
from bench.readers import mfu


def read(data):
    return mfu(data, data["counts"]["flops_per_request"],
               data["window"]["completed"])
