"""Device time of the cohort engine's program per personalization request:
the engine jits one cohort-mapped function (a lambda, hence its program
name) per bucket; every request of the window runs in one of its calls."""
from bench.readers import module_time

PROGRAMS = ("jit__lambda",)


def read(data):
    n, t = module_time(data, PROGRAMS)
    done = data["window"]["completed"]
    return 1e3 * t / done if n and done else None
