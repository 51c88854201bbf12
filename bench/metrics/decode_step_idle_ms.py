"""Device idle time per token step after a decode call's first
(``persafl.decode.step``: the step dispatch, its argmax and cast)."""
from bench import program_trace


def read(data):
    idle = program_trace.idle_s(data,
                                lambda p: "persafl.decode.step" in p)
    steps = program_trace.count(data, "persafl.decode.step")
    return 1e3 * idle / steps if idle is not None and steps else None
