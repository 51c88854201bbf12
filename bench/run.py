#!/usr/bin/env python3
"""Run one benchmark cell once, on the accelerator it is started on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up by name in ``BENCHMARK.json``; its configuration,
traffic mix and per-layer metric readers are files under ``bench/`` found
by the names given there, so a new cell, configuration, mix or metric is
new files plus new entries, not an edit here:

* ``bench/configs/<config>.json``   model sizes, deployment settings;
* ``bench/traffic/<traffic>.json``  the mix; its ``kind`` names the driver
  ``bench/drivers/<kind>.py`` that generates it and drives the program;
* ``bench/metrics/<metric>.py``     one reader per per-layer metric.

A run: set-up (weights from the seed, the program's server, the first
steps that ``correct`` replays, warm-up of every shape the window uses),
then ``--seconds`` of measured traffic, then the comparison with the plain
reference under ``bench/reference``.  With ``--trace 0`` the result line
carries the cell's end-to-end metrics, with ``--trace 1`` its per-layer
metrics, read from a profiler trace of the window.  The last line of
standard output is one JSON object; the compared numbers and their limits
are also the last lines of standard error.  Without a TPU, or with fewer
chips than the cell asks for, the run exits non-zero and prints no result.
"""
from __future__ import annotations

import time

PROCESS_START = time.time()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
# XLA backend compile seconds (tracing and lowering nest across jit levels
# and would double count, so only the backend compile is counted).  The
# event also wraps a load from the persistent cache, which records a hit.
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Import a benchmark file by path (metric names hold dots)."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _named(entries, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def cell_spec(workload: str, root: str = ROOT) -> dict:
    """Everything one cell needs, found by name from ``BENCHMARK.json``."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cell = _named(bench["workloads"], workload, "workload")
    conf_entry = _named(bench["configs"], cell["config"], "config")

    def mine(metric):
        return workload in metric.get("workloads", [workload])

    return {
        "workload": workload,
        "cell": cell,
        "config": load_json(os.path.join(root, conf_entry["file"])),
        "traffic": load_json(os.path.join(root, "bench", "traffic",
                                          cell["traffic"] + ".json")),
        "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
        "per_layer": [m for m in bench["per_layer"] if mine(m)],
        "root": root,
    }


def load_peaks(kind: str, root: str = ROOT) -> dict:
    """The chip's published peaks, keyed by JAX's ``device_kind``."""
    table = load_json(os.path.join(root, "bench", "peaks.json"))
    if kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {kind!r} in bench/"
                       f"peaks.json (have {sorted(table['devices'])})")
    return table["devices"][kind]


class Counters:
    """Compile, persistent-cache load and trace events of this process."""

    def __init__(self):
        self.programs = 0          # compiled or loaded from the cache
        self.cache_loads = 0
        self.traces = 0

    @property
    def compiles(self) -> int:
        return self.programs - self.cache_loads

    def on_duration(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.programs += 1
        elif event == TRACE_EVENT:
            self.traces += 1

    def on_event(self, event, **_):
        if event == CACHE_HIT_EVENT:
            self.cache_loads += 1


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def configure_jax():
    """The program's persistent compile cache (``<checkout>/.jax_cache``,
    or where ``JAX_COMPILATION_CACHE_DIR`` says), every program kept."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return jax


def device_info(jax, chips: int) -> dict:
    devs = jax.devices()[:chips]
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in devs]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": max(peaks)}


def run_cell(spec: dict, seed: int, seconds: float, trace: bool, *,
             require_chip: bool = True, driver_hook=None) -> dict:
    """One run; returns the result object (without printing it).

    ``require_chip=False`` skips the look for a TPU (the CPU rehearsal
    tests); ``driver_hook(driver)`` lets a test break the timed path.
    """
    root = spec["root"]
    for p in (os.path.join(root, "src"), root):
        if p not in sys.path:
            sys.path.insert(0, p)
    import jax
    chips = spec["cell"]["chips"]
    devs = jax.devices()
    if require_chip and (devs[0].platform != "tpu" or len(devs) < chips):
        raise SystemExit(f"bench: cell {spec['workload']} needs {chips} "
                         f"TPU chip(s); JAX found {len(devs)} "
                         f"{devs[0].platform!r} device(s)")
    peaks = None
    if require_chip:
        configure_jax()
        peaks = load_peaks(devs[0].device_kind, root)
    counters = Counters()
    jax.monitoring.register_event_duration_secs_listener(counters.on_duration)
    jax.monitoring.register_event_listener(counters.on_event)

    driver_mod = load_module(
        os.path.join(root, "bench", "drivers",
                     spec["traffic"]["kind"] + ".py"),
        "bench_driver_" + spec["traffic"]["kind"])
    driver = driver_mod.Driver(spec, seed)
    if driver_hook is not None:
        driver_hook(driver)
    driver.setup()
    setup_s = time.time() - PROCESS_START
    c0, l0, t0 = counters.compiles, counters.cache_loads, counters.traces
    reduced = None
    if trace:
        from bench import trace_reduce
        tdir = os.path.join(root, ".bench_traces", spec["workload"])
        shutil.rmtree(tdir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # host spans only, no per-call
        with jax.profiler.trace(tdir, profiler_options=opts):
            window = driver.window(seconds)
        reduced = trace_reduce.reduce(trace_reduce.find_xplane(tdir),
                                      window_span="bench.window")
        with open(os.path.join(tdir, "reduced.json"), "w") as f:
            json.dump(reduced, f, indent=1)
    else:
        window = driver.window(seconds)
    window["compiles"] = counters.compiles - c0
    window["cache_loads"] = counters.cache_loads - l0
    window["traces"] = counters.traces - t0
    device = device_info(jax, chips)
    driver.release()
    gc.collect()
    checks = driver.check()
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    if trace:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        metrics = {}
        data = {"spec": spec, "window": window, "trace": reduced,
                "peaks": peaks, "counts": driver.counts()}
        for m in spec["per_layer"]:
            reader = load_module(os.path.join(root, "bench", "metrics",
                                              m["name"] + ".py"),
                                 "bench_metric_" + m["name"].replace(".",
                                                                     "_"))
            value = reader.read(data)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = dict(driver.end_to_end(window), setup_s=setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in spec["end_to_end"]}
    result = {"correct": bool(correct), "attempted": window["attempted"],
              "failed": window["failed"], "metrics": metrics,
              "device": device,
              "window_compiles": window["compiles"],
              "window_cache_loads": window["cache_loads"],
              "window_traces": window["traces"],
              "observed": window.get("observed", {})}
    if trace:
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    args = parse_args(argv)
    try:
        spec = cell_spec(args.workload)
        result = run_cell(spec, args.seed, args.seconds, bool(args.trace))
    except SystemExit as e:
        print(str(e), file=sys.stderr, flush=True)
        return 2
    except Exception:  # the run failed: say why, print no result
        traceback.print_exc()
        return 1
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
