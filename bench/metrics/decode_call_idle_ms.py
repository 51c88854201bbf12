"""Device idle time per decode call outside its token steps: under
``persafl.decode`` but not under ``persafl.decode.step`` (cache init, the
prefill with the retrace and cache load of its fresh program, the first
step with those of the step program, collection)."""
from bench import program_trace


def read(data):
    idle = program_trace.idle_s(
        data, lambda p: p[0] == "persafl.decode"
        and "persafl.decode.step" not in p)
    calls = program_trace.count(data, "persafl.decode")
    return 1e3 * idle / calls if idle is not None and calls else None
