"""Operations the window's decoded sequences need
(``bench/counts/decode_step.py``) per second of the traced window, over
the chip's bf16 peak."""
from bench.readers import mfu


def read(data):
    return mfu(data, data["counts"]["flops_per_sequence"],
               data["window"]["completed"])
