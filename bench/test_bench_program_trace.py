"""The reduction of the program's own spans (``bench/program_trace.py``)
and the five metrics that read it, on traces built by hand in the layout
``jax.profiler.ProfileData`` reads from a TPU, and on the small trace
recorded on a TPU (``bench/fixtures/``), whose reduction by
``bench/trace_reduce.py`` is pinned here as well."""
import collections
import os
import shutil

import numpy as np
import pytest

from bench import program_trace as pt
from bench import run
from bench import trace_reduce as tr

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "tpu_trace.xplane.pb")
NS = 1e-9


def _plane(name, **lines):
    ev = collections.namedtuple("Event", "name start_ns duration_ns")
    line = collections.namedtuple("Line", "name events")
    return collections.namedtuple("Plane", "name lines")(
        name, [line(k.replace("_", " "), [ev(n, s, e - s) for n, s, e in v])
               for k, v in lines.items()])


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_innermost_follows_the_linear_rule(seed):
    """Shortest covering span, ends included, ties to the first listed:
    the rule ``trace_reduce._attribute`` applies span by span."""
    g = np.random.default_rng(seed)
    spans = []
    for i in range(60):
        s = int(g.integers(0, 200))
        spans.append((f"s{i}", s, s + int(g.integers(0, 40))))
    who = pt.innermost(spans, "host.idle")
    for t in list(range(-2, 245)) + [x + 0.5 for x in range(-2, 245)]:
        assert who(t) == tr._attribute(t, spans), t


def test_program_spans_and_idle_by_hand():
    host = _plane("/host:CPU", python=[
        ("bench.window", 100, 1100), ("bench.flush", 150, 520),
        ("persafl.flush", 160, 500),
        ("persafl.batcher.drain", 170, 300),
        ("persafl.engine.cohort", 200, 280),
        ("persafl.ring.admit", 420, 480),
        ("bench.poll", 520, 700), ("persafl.poll", 530, 560),
        ("persafl.poll", 600, 690), ("bench.advance", 700, 1000),
        ("PjitFunction(step)", 205, 210)],
        other=[("persafl.flush", 50, 130)])       # a second host thread
    tpu = _plane("/device:TPU:0", XLA_Ops=[
        ("opA", 50, 300), ("opB", 250, 400), ("opA", 580, 650),
        ("opC", 800, 900), ("opD", 1050, 1200)])
    got = pt.reduce_planes([host, tpu, _plane("/device:TPU:1", XLA_Ops=[])])
    spans = got["program_spans"]
    assert set(spans) == {
        "persafl.flush", "persafl.flush/persafl.batcher.drain",
        "persafl.flush/persafl.batcher.drain/persafl.engine.cohort",
        "persafl.flush/persafl.ring.admit", "persafl.poll"}
    # the other thread's flush is clipped to the window [100, 1100]
    assert spans["persafl.flush"] == pytest.approx(
        [2, (340 + 30) * NS, (340 - 130 - 60 + 30) * NS])
    assert spans["persafl.flush/persafl.batcher.drain"] == pytest.approx(
        [1, 130 * NS, 50 * NS])
    assert spans["persafl.poll"] == pytest.approx([2, 120 * NS, 120 * NS])
    # gaps: [400,580] mid 490 in flush (past admit); [650,800] mid 725 in
    # bench.advance; [900,1050] mid 975 in bench.advance
    assert got["program_idle"] == pytest.approx(
        {"persafl.flush": 180 * NS, "bench.advance": 300 * NS})
    # a gap whose midpoint no span covers goes to host.idle
    lone = _plane("/host:CPU", python=[("bench.window", 0, 100)])
    dev = _plane("/device:TPU:0", XLA_Ops=[("op", 0, 10)])
    assert pt.reduce_planes([lone, dev])["program_idle"] == pytest.approx(
        {"host.idle": 90 * NS})


def test_recorded_tpu_trace_reduction_is_unchanged():
    """``trace_reduce.reduce`` of the recorded trace, pinned: every key the
    ledger's ``breakdown`` and the metrics read keeps its value."""
    got = tr.reduce(FIXTURE)
    assert got["devices"] == 1
    assert got["window_s"] == pytest.approx(0.052117779, rel=1e-12)
    assert got["busy_s"] == pytest.approx(3.803e-05, rel=1e-12)
    assert got["device_ops"] == [["%multiply_subtract_fusion",
                                  pytest.approx(3.803e-05, rel=1e-12)]]
    assert got["idle_gaps"] == [["bench.poll",
                                 pytest.approx(0.052079749, rel=1e-12)]]
    assert got["modules"] == {"jit_apply": [1, pytest.approx(3.8033e-05,
                                                             rel=1e-12)]}
    assert got["spans"] == {
        "bench.flush": [1, pytest.approx(0.00129779, rel=1e-12)],
        "bench.poll": [1, pytest.approx(0.050094529, rel=1e-12)],
        "bench.advance": [1, pytest.approx(0.00070582, rel=1e-12)]}
    # no program span in this recording: every gap falls back to bench.*
    mine = pt.reduce(FIXTURE)
    assert mine["program_spans"] == {}
    assert mine["program_idle"] == pytest.approx(dict(got["idle_gaps"]),
                                                 rel=1e-12)


def test_traced_run_is_read_from_its_trace_directory(tmp_path):
    tdir = tmp_path / ".bench_traces" / "cell"
    tdir.mkdir(parents=True)
    shutil.copy(FIXTURE, tdir / "t.xplane.pb")
    data = {"spec": {"root": str(tmp_path), "workload": "cell"},
            "trace": {"devices": 1}}
    assert pt.of_run(data)["program_idle"] == pytest.approx(
        {"bench.poll": 0.052079749}, rel=1e-12)
    assert pt.of_run({"spec": data["spec"], "trace": None}) is None
    assert pt.of_run({"spec": data["spec"],
                      "trace": {"devices": 0}}) is None


def _reader(name):
    return run.load_module(os.path.join(run.BENCH, "metrics",
                                        name + ".py"),
                           "bench_metric_" + name.replace(".", "_"))


def _data(spans, idle, modules=()):
    return {"window": {"completed": 8},
            "trace": {"devices": 1, "modules": dict(modules)},
            "program_trace": {"program_spans": spans,
                              "program_idle": idle}}


SERVE_SPANS = {"persafl.flush": [4, 0.4, 0.1],
               "persafl.flush/persafl.batcher.drain": [4, 0.2, 0.1],
               "persafl.poll": [8, 0.1, 0.1],
               "persafl.advance": [4, 0.3, 0.1],
               "persafl.advance/persafl.flush": [4, 0.01, 0.01]}
SERVE_IDLE = {"persafl.flush": 0.004,
              "persafl.flush/persafl.batcher.drain": 0.002,
              "persafl.advance/persafl.flush": 0.5, "persafl.poll": 0.008,
              "bench.flush": 1.0}
DECODE_SPANS = {"persafl.decode": [2, 9.0, 1.0],
                "persafl.decode/persafl.decode.prefill": [2, 1.0, 1.0],
                "persafl.decode/persafl.decode.step": [10, 5.0, 5.0]}
DECODE_IDLE = {"persafl.decode": 0.1,
               "persafl.decode/persafl.decode.prefill": 0.3,
               "persafl.decode/persafl.decode.step": 0.05,
               "bench.decode": 2.0}
READINGS = {
    "flush_idle_ms_per_req": (SERVE_SPANS, SERVE_IDLE, 1e3 * 0.006 / 8),
    "poll_idle_ms_per_req": (SERVE_SPANS, SERVE_IDLE, 1e3 * 0.008 / 8),
    "decode_call_idle_ms": (DECODE_SPANS, DECODE_IDLE, 1e3 * 0.4 / 2),
    "decode_step_idle_ms": (DECODE_SPANS, DECODE_IDLE, 1e3 * 0.05 / 10),
}


@pytest.mark.parametrize("name", sorted(READINGS))
def test_program_idle_readers(name):
    spans, idle, want = READINGS[name]
    read = _reader(name).read
    assert read(_data(spans, idle)) == pytest.approx(want)
    other = DECODE_SPANS if spans is SERVE_SPANS else SERVE_SPANS
    assert read(_data(other, idle)) is None          # its spans are absent
    assert read({"window": {"completed": 8},
                 "trace": {"devices": 0}}) is None   # no device ran


def test_head_gather_reader():
    read = _reader("head_gather_ms_per_req").read
    assert read(_data({}, {}, {"jit_head_rows": [4, 0.02],
                               "jit__lambda": [4, 1.0]})) == \
        pytest.approx(1e3 * 0.02 / 8)
    assert read(_data({}, {}, {"jit__lambda": [4, 1.0]})) is None
