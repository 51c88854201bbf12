"""The program's own spans in a traced window: where the host was, inside
the program, while the device waited.

The program records a span at each of its layer boundaries
(``repro.spans``: ``persafl.flush``, ``persafl.engine.cohort``,
``persafl.decode.step`` ...) into the same profiler session as the
benchmark's ``bench.*`` spans and the device planes.  This reduction reads
the run's ``.xplane.pb`` once more, on the rules of ``bench/trace_reduce.py``
(the same window, device planes and idle gaps), and returns

* ``program_spans``: ``{path: [count, total_s, self_s]}`` of the spans in
  the window, clipped to it.  ``path`` names the enclosing ``persafl.*``
  spans of the same host thread, outermost first, joined with ``/``
  (``persafl.flush/persafl.batcher.drain/persafl.engine.cohort``); self
  time is the duration less the time its child spans cover;
* ``program_idle``: ``{path: idle_s}``: each idle gap of the device goes to
  the path of the innermost ``persafl.*`` span under its midpoint, else to
  the innermost ``bench.*`` span there (the window's own excepted), else
  to ``host.idle``; averaged over the devices that ran anything, as
  ``idle_gaps`` is.

"Innermost" is the shortest span covering the point, ends included, ties
to the span met first: ``trace_reduce``'s rule, found here by bisection
over the spans' sorted boundaries, since a window holds thousands of spans
and up to millions of gaps.
"""
from __future__ import annotations

import bisect
import heapq
import os
from typing import Callable, Dict, List, Optional, Tuple

from bench import trace_reduce as tr

PREFIX = "persafl."
BENCH_PREFIX = "bench."
WINDOW = "bench.window"
SEP = "/"

Span = Tuple[str, int, int]          # name, start_ns, end_ns


def innermost(spans: List[Span], default: str) -> Callable[[float], str]:
    """A function of a time giving the name of the innermost span that
    covers it, or ``default`` where none does."""
    bounds = sorted({t for _, s, e in spans for t in (s, e)})
    order = sorted(range(len(spans)), key=lambda i: spans[i][1])
    # innermost span at bounds[k], and inside (bounds[k], bounds[k+1])
    at, after = [], []
    heap, j = [], 0
    for b in bounds:
        while j < len(order) and spans[order[j]][1] == b:
            i = order[j]
            heapq.heappush(heap, (spans[i][2] - spans[i][1], i))
            j += 1
        while heap and spans[heap[0][1]][2] < b:
            heapq.heappop(heap)
        at.append(heap[0][1] if heap else -1)
        while heap and spans[heap[0][1]][2] <= b:
            heapq.heappop(heap)
        after.append(heap[0][1] if heap else -1)

    def who(t: float) -> str:
        k = bisect.bisect_left(bounds, t)
        if k < len(bounds) and bounds[k] == t:
            i = at[k]
        else:
            i = after[k - 1] if k else -1
        return default if i < 0 else spans[i][0]
    return who


def _paths(threads: List[List[Span]]) -> List[Tuple[str, int, int, int]]:
    """(path, start, end, parent index or -1) of every span; a span's
    parent is the innermost span of its thread that contains it."""
    out = []
    for spans in threads:
        stack: List[int] = []
        for name, s, e in sorted(spans, key=lambda x: (x[1], -x[2])):
            while stack and out[stack[-1]][2] < e:
                stack.pop()
            parent = stack[-1] if stack else -1
            path = out[parent][0] + SEP + name if stack else name
            stack.append(len(out))
            out.append((path, s, e, parent))
    return out


def reduce_planes(planes) -> dict:
    """``program_spans`` and ``program_idle`` of a trace's planes
    (``ProfileData.planes``, as ``trace_reduce.reduce_planes`` reads)."""
    threads, bench, devices = [], [], []
    for plane in planes:
        if tr.DEVICE_PLANE.match(plane.name):
            devices.append(plane)
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                mine = []
                for ev in line.events:
                    name = ev.name
                    if name.startswith(PREFIX):
                        mine.append((name, ev.start_ns,
                                     ev.start_ns + ev.duration_ns))
                    elif name.startswith(BENCH_PREFIX):
                        bench.append((name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
                threads.append(mine)
    windows = [(s, e) for n, s, e in bench if n == WINDOW]
    if not windows:
        raise ValueError(f"trace has no {WINDOW!r} span")
    lo, hi = windows[0]

    spans = _paths(threads)
    children: Dict[int, List[tr.Interval]] = {}
    for path, s, e, parent in spans:
        if parent >= 0:
            children.setdefault(parent, []).append((s, e))
    program_spans: Dict[str, list] = {}
    for i, (path, s, e, _) in enumerate(spans):
        if e <= lo or s >= hi:
            continue
        total = min(e, hi) - max(s, lo)
        covered = sum(b - a for a, b in tr.union(
            tr.clip(children.get(i, []), max(s, lo), min(e, hi))))
        n, t, own = program_spans.get(path, (0, 0.0, 0.0))
        program_spans[path] = [n + 1, t + total * 1e-9,
                               own + (total - covered) * 1e-9]

    in_program = innermost([sp[:3] for sp in spans], "")
    in_bench = innermost([sp for sp in bench if sp[0] != WINDOW],
                         "host.idle")
    idle: Dict[str, float] = {}
    used = 0
    for plane in devices:
        ops = [(ev.start_ns, ev.start_ns + ev.duration_ns)
               for ev in tr._events(plane, tr.OPS_LINE)]
        ops = tr.clip(ops, lo, hi)
        if not ops:
            continue
        used += 1
        for s, e in tr.gaps(tr.union(ops), lo, hi):
            mid = 0.5 * (s + e)
            who = in_program(mid) or in_bench(mid)
            idle[who] = idle.get(who, 0.0) + (e - s) * 1e-9
    per = max(used, 1)
    return {"program_spans": program_spans,
            "program_idle": {k: v / per for k, v in idle.items()}}


def reduce(path: str) -> dict:
    """The reduction of one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    return reduce_planes(ProfileData.from_file(path).planes)


def of_run(data: dict) -> Optional[dict]:
    """The reduction of a traced run's trace, read once per run and kept
    in ``data``; None where the run was not traced or no device ran.  The
    trace is where ``bench/run.py`` writes it: ``.bench_traces/<cell>``
    under the checkout."""
    if "program_trace" not in data:
        found, reduced = None, data.get("trace")
        if reduced and reduced["devices"]:
            spec = data["spec"]
            tdir = os.path.join(spec["root"], ".bench_traces",
                                spec["workload"])
            try:
                found = reduce(tr.find_xplane(tdir))
            except FileNotFoundError:       # the trace was not written
                pass
        data["program_trace"] = found
    return data["program_trace"]


def _parts(path: str) -> List[str]:
    return path.split(SEP)


def idle_s(data: dict, keep: Callable[[List[str]], bool]) -> Optional[float]:
    """Idle seconds of the window charged to the span paths that ``keep``
    accepts (given a path's names, outermost first); None where no such
    span ran in the window."""
    red = of_run(data)
    if not red or not any(keep(_parts(p)) for p in red["program_spans"]):
        return None
    return sum(v for p, v in red["program_idle"].items()
               if keep(_parts(p)))


def count(data: dict, name: str) -> int:
    """Spans named ``name`` in the window, wherever they nest."""
    red = of_run(data) or {"program_spans": {}}
    return sum(n for p, (n, _, _) in red["program_spans"].items()
               if _parts(p)[-1] == name)
