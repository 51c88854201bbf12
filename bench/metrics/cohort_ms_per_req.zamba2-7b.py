"""Device time of the cohort engine's program per personalization request
in the Zamba2 cell (the prox solve over the adapters: the hybrid forward
and its backward to the adapters, K times); the program is a lambda, as
in ``cohort_ms_per_req``."""
from bench.readers import module_time

PROGRAMS = ("jit__lambda",)


def read(data):
    n, t = module_time(data, PROGRAMS)
    done = data["window"]["completed"]
    return 1e3 * t / done if n and done else None
