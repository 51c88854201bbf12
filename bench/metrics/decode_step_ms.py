"""Device time per execution of the decode-step program (one token for
every user of the batch; the entry point jits a vmapped lambda)."""
from bench.readers import module_time

PROGRAMS = ("jit__lambda",)


def read(data):
    n, t = module_time(data, PROGRAMS)
    return 1e3 * t / n if n else None
