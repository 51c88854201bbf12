"""Device time of the operations that the program traced under a named
scope (``repro.spans.scope``), read from a traced run's ``.xplane.pb``.

``jax.profiler.ProfileData`` gives each device operation's name and times
but not the metadata XLA stores with it, so this reduction parses the
trace's protocol buffer itself (``XSpace``; its Python module ships with
TensorFlow's copy of the profiler, loaded by path so that TensorFlow is
not imported).  On a device plane, each operation's event metadata holds
stats (its category, program id, source line ...); an operation counts as
in the scope where one of its stats' strings names the scope.

``share(path, scope, programs)``: the union of the scoped operations'
intervals over the device time of the programs whose name starts with
one of ``programs``, both clipped to the window span ``bench.window``
and to those programs' executions, summed over the devices, in percent;
None where no operation names the scope (the trace carries no name scopes)
or no such program ran.
"""
from __future__ import annotations

import importlib.util
import os
from typing import Iterable, Optional

from bench import trace_reduce as tr

WINDOW = "bench.window"


def _xplane_pb2():
    """The ``XSpace`` protocol buffer module, or None where it is not
    installed."""
    try:
        spec = importlib.util.find_spec("tensorflow")
    except (ImportError, ValueError):
        return None
    if spec is None or not spec.origin:
        return None
    path = os.path.join(os.path.dirname(spec.origin), "tsl", "profiler",
                        "protobuf", "xplane_pb2.py")
    if not os.path.exists(path):
        return None
    mod_spec = importlib.util.spec_from_file_location("bench_xplane_pb2",
                                                      path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def _strings(stats, names) -> Iterable[str]:
    for st in stats:
        if st.str_value:
            yield st.str_value
        elif st.ref_value:
            yield names.get(st.ref_value, "")


def _events(line, ids=None):
    """(start_ns, end_ns) of the events of one line, where ``ids`` (if
    given) holds their metadata id."""
    for ev in line.events:
        if ids is None or ev.metadata_id in ids:
            s = line.timestamp_ns + ev.offset_ps / 1e3
            yield s, s + ev.duration_ps / 1e3


def overlap(a, b) -> float:
    """Length of the intersection of two sorted unions of intervals."""
    out, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        out += max(0.0, hi - lo)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def share_planes(planes, scope: str, programs) -> Optional[float]:
    window = None
    for plane in planes:
        if plane.name.startswith("/host"):
            ids = {k for k, m in plane.event_metadata.items()
                   if m.name == WINDOW}
            for line in plane.lines:
                for iv in _events(line, ids):
                    window = window or iv
    if window is None:
        raise ValueError(f"trace has no {WINDOW!r} span")
    lo, hi = window
    total = inside = 0.0
    found = False
    for plane in planes:
        if not tr.DEVICE_PLANE.match(plane.name):
            continue
        names = {k: m.name for k, m in plane.stat_metadata.items()}
        scoped = {k for k, m in plane.event_metadata.items()
                  if any(scope in s for s in _strings(m.stats, names))}
        found = found or bool(scoped)
        mods = {k for k, m in plane.event_metadata.items()
                if m.name.startswith(tuple(programs))}
        runs, ops = [], []
        for line in plane.lines:
            if line.name == tr.MODULES_LINE:
                runs += _events(line, mods)
            elif line.name == tr.OPS_LINE:
                ops += _events(line, scoped)
        runs = tr.union(tr.clip(runs, lo, hi))
        total += sum(e - s for s, e in runs)
        inside += overlap(runs, tr.union(ops))
    if not found or total <= 0:
        return None
    return 100.0 * inside / total


def share(path: str, scope: str, programs) -> Optional[float]:
    pb2 = _xplane_pb2()
    if pb2 is None:
        return None
    space = pb2.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    return share_planes(space.planes, scope, programs)


def of_run(data: dict, scope: str, programs) -> Optional[float]:
    """``share`` of a traced run's trace (``.bench_traces/<cell>`` under
    the checkout, where ``bench/run.py`` writes it); None where the run
    was not traced or no device ran."""
    reduced = data.get("trace")
    if not reduced or not reduced["devices"]:
        return None
    spec = data["spec"]
    tdir = os.path.join(spec["root"], ".bench_traces", spec["workload"])
    try:
        return share(tr.find_xplane(tdir), scope, programs)
    except FileNotFoundError:            # the trace was not written
        return None
