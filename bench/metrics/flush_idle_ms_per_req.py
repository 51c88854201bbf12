"""Device idle time while the host was inside the server's flush
(``persafl.flush`` and the spans under it: the batcher's grouping and
layout, the engine's stacking and cohort dispatch, the error-feedback
residual gather and quantize dispatch, admission), per completed request."""
from bench import program_trace


def read(data):
    idle = program_trace.idle_s(data, lambda p: p[0] == "persafl.flush")
    done = data["window"]["completed"]
    return 1e3 * idle / done if idle is not None and done else None
