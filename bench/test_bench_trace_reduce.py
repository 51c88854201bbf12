"""The trace reduction on interval arithmetic and on a trace built by hand
in the layout ``jax.profiler.ProfileData`` reads from a TPU (a
``/device:TPU:<n>`` plane with ``XLA Ops`` and ``XLA Modules`` lines, and a
host plane holding the benchmark's ``bench.*`` spans), and on a small
trace recorded on a TPU (``bench/fixtures/``)."""
import collections
import os

import pytest

from bench import trace_reduce as tr


def test_union_merges_overlaps_and_keeps_disjoint():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]


def test_clip_and_gaps():
    busy = tr.union(tr.clip([(0, 4), (6, 9), (12, 20)], 2, 15))
    assert busy == [(2, 4), (6, 9), (12, 15)]
    assert tr.gaps(busy, 0, 16) == [(0, 2), (4, 6), (9, 12), (15, 16)]
    assert tr.gaps([], 0, 3) == [(0, 3)]


def test_gap_charged_to_innermost_span():
    spans = [("bench.window", 0, 100), ("bench.poll", 10, 50),
             ("bench.flush", 20, 30)]
    assert tr._attribute(25, spans) == "bench.flush"
    assert tr._attribute(40, spans) == "bench.poll"
    assert tr._attribute(200, spans) == "host.idle"


def _plane(name, **lines):
    ev = collections.namedtuple("Event", "name start_ns duration_ns")
    line = collections.namedtuple("Line", "name events")
    return collections.namedtuple("Plane", "name lines")(
        name, [line(k.replace("_", " "), [ev(n, s, e - s) for n, s, e in v])
               for k, v in lines.items()])


def test_planes_by_hand():
    host = _plane("/host:CPU", python=[
        ("bench.window", 100, 1100), ("bench.flush", 150, 500),
        ("bench.poll", 500, 700), ("bench.advance", 700, 1000),
        ("PjitFunction(step)", 160, 170)])
    tpu = _plane("/device:TPU:0", XLA_Ops=[
        ("opA", 50, 300), ("opB", 250, 400), ("opA", 580, 650),
        ("opC", 800, 900), ("opD", 1050, 1200)],
        XLA_Modules=[("jit_step(7)", 50, 420), ("jit_apply(9)", 790, 910)])
    idle_tpu = _plane("/device:TPU:1", XLA_Ops=[])
    got = tr.reduce_planes([host, tpu, idle_tpu])
    ns = 1e-9
    assert got["window_s"] == pytest.approx(1000 * ns)
    assert got["devices"] == 1
    # busy: [100,400] + [580,650] + [800,900] + [1050,1100]
    assert got["busy_s"] == pytest.approx(520 * ns)
    assert dict(got["device_ops"]) == pytest.approx(
        {"opA": 270 * ns, "opB": 150 * ns, "opC": 100 * ns, "opD": 50 * ns})
    # gaps [400,580] under flush; [650,800] and [900,1050] under advance
    assert dict(got["idle_gaps"]) == pytest.approx(
        {"bench.flush": 180 * ns, "bench.advance": 300 * ns})
    assert got["modules"] == pytest.approx(
        {"jit_step": [1, 320 * ns], "jit_apply": [1, 120 * ns]})
    assert got["spans"]["bench.poll"] == pytest.approx([1, 200 * ns])


def test_recorded_tpu_trace():
    """A trace recorded on a TPU v5e by ``bench/fixtures/record_trace.py``:
    three jitted steps under ``bench.flush``, 50 ms of sleep under
    ``bench.poll``, one jitted apply under ``bench.advance``.  The device
    plane of this recording holds no event of the steps, which ran in the
    trace's first 1.3 ms: only the apply is read."""
    got = tr.reduce(os.path.join(os.path.dirname(__file__), "fixtures",
                                 "tpu_trace.xplane.pb"))
    assert got["devices"] == 1
    assert 0 < got["busy_s"] < got["window_s"]
    assert set(got["modules"]) == {"jit_apply"}
    assert got["modules"]["jit_apply"][0] == 1
    assert got["busy_s"] == pytest.approx(got["modules"]["jit_apply"][1],
                                          rel=1e-3)
    assert got["device_ops"] and all(" = " not in n
                                     for n, _ in got["device_ops"])
    idle = dict(got["idle_gaps"])
    assert max(idle, key=idle.get) == "bench.poll"
    assert idle["bench.poll"] >= 0.045
    assert {"bench.flush", "bench.poll", "bench.advance"} <= set(got["spans"])
