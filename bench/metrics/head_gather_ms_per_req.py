"""Device time of the head-row gather (``jit_head_rows``: snapshot minus
the dequantized int8 delta rows, one program per poll) per completed
request."""
from bench.readers import module_time

PROGRAMS = ("jit_head_rows",)


def read(data):
    n, t = module_time(data, PROGRAMS)
    done = data["window"]["completed"]
    return 1e3 * t / done if n and done else None
