"""Share of the cohort program's device time (``jit__lambda``, the prox
solve) spent in operations traced under ``persafl.zamba2.shared``: the
shared-block invocations with their adapters and linears, forward and
backward (``bench/scope_trace.py``)."""
from bench import scope_trace

PROGRAMS = ("jit__lambda",)


def read(data):
    return scope_trace.of_run(data, "persafl.zamba2.shared", PROGRAMS)
