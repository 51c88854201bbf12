"""Device idle time while the host was inside the server's poll
(``persafl.poll``: the fused head gather and its per-leaf row slicing),
per completed request."""
from bench import program_trace


def read(data):
    idle = program_trace.idle_s(data, lambda p: p[0] == "persafl.poll")
    done = data["window"]["completed"]
    return 1e3 * idle / done if idle is not None and done else None
