"""Reduce a profiler trace (``.xplane.pb``) to the numbers the benchmark
reports: device busy time, per-program and per-op device time, and the
device's idle gaps attributed to what the host was doing.

Reading rules (one trace, one clock):

* device planes are ``/device:TPU:<n>``; on each, the ``XLA Ops`` line
  holds one event per executed operation and the ``XLA Modules`` line one
  event per executed program (its name is the jitted function's);
* host spans are the benchmark's own ``jax.profiler.TraceAnnotation``
  events, named ``bench.<layer>``, on any host plane's lines;
* the traced window is the span ``window_span``; everything is clipped to
  it.  Busy is the union of a device's op intervals, averaged over the
  devices that ran anything; an idle gap is a stretch of the window with
  no op running, charged to the innermost host span under its midpoint
  (``host.idle`` where no span covers it).
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10

Interval = Tuple[float, float]


def find_xplane(trace_dir: str) -> str:
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(found, key=os.path.getmtime)


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def clip(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def gaps(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def _events(plane, line_name=None):
    for line in plane.lines:
        if line_name is None or line.name == line_name:
            for ev in line.events:
                yield ev


def _attribute(mid: float, spans: List[Tuple[str, float, float]]) -> str:
    best, best_len = "host.idle", float("inf")
    for name, s, e in spans:
        if s <= mid <= e and e - s < best_len:
            best, best_len = name, e - s
    return best


def _top(d: Dict[str, float]) -> List[list]:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]


def reduce(path: str, window_span: str = "bench.window",
           span_prefix: str = "bench.") -> dict:
    """The reduction of one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    return reduce_planes(ProfileData.from_file(path).planes, window_span,
                         span_prefix)


def reduce_planes(planes, window_span: str = "bench.window",
                  span_prefix: str = "bench.") -> dict:
    """The reduction of a trace's planes (``ProfileData.planes``: each has
    ``name`` and ``lines``, each line ``name`` and ``events``, each event
    ``name``, ``start_ns`` and ``duration_ns``)."""
    spans, devices = [], []
    for plane in planes:
        if DEVICE_PLANE.match(plane.name):
            devices.append(plane)
        elif plane.name.startswith("/host"):
            for ev in _events(plane):
                if ev.name.startswith(span_prefix):
                    spans.append((ev.name, ev.start_ns,
                                  ev.start_ns + ev.duration_ns))
    windows = [(s, e) for n, s, e in spans if n == window_span]
    if not windows:
        raise ValueError(f"trace has no {window_span!r} span")
    lo, hi = windows[0]
    inner = [sp for sp in spans if sp[0] != window_span]

    busy_total, op_time, modules, idle = 0.0, {}, {}, {}
    used = 0
    for plane in devices:
        ops = []
        for ev in _events(plane, OPS_LINE):
            s, e = ev.start_ns, ev.start_ns + ev.duration_ns
            if e <= lo or s >= hi:
                continue
            ops.append((s, e))
            name = ev.name.split(" = ", 1)[0]     # HLO text: keep its name
            op_time[name] = op_time.get(name, 0.0) \
                + (min(e, hi) - max(s, lo)) * 1e-9
        for ev in _events(plane, MODULES_LINE):
            s, e = ev.start_ns, ev.start_ns + ev.duration_ns
            if e <= lo or s >= hi:
                continue
            name = ev.name.split("(")[0]
            n, t = modules.get(name, (0, 0.0))
            modules[name] = (n + 1, t + (min(e, hi) - max(s, lo)) * 1e-9)
        if not ops:
            continue
        used += 1
        busy = union(clip(ops, lo, hi))
        busy_total += sum(e - s for s, e in busy) * 1e-9
        for s, e in gaps(busy, lo, hi):
            who = _attribute(0.5 * (s + e), inner)
            idle[who] = idle.get(who, 0.0) + (e - s) * 1e-9
    span_time = {}
    for name, s, e in inner:
        if e > lo and s < hi:
            n, t = span_time.get(name, (0, 0.0))
            span_time[name] = (n + 1, t + (min(e, hi) - max(s, lo)) * 1e-9)
    per = max(used, 1)
    return {"window_s": (hi - lo) * 1e-9,
            "busy_s": busy_total / per,
            "devices": used,
            "device_ops": _top({k: v / per for k, v in op_time.items()}),
            "idle_gaps": _top({k: v / per for k, v in idle.items()}),
            "modules": {k: [n, t / per] for k, (n, t) in modules.items()},
            "spans": {k: [n, t] for k, (n, t) in span_time.items()}}
