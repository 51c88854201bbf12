#!/usr/bin/env python3
"""Record the small TPU trace that ``bench/test_bench_trace_reduce.py``
reduces, on a machine with a TPU:

    python3 bench/fixtures/record_trace.py bench/fixtures/tpu_trace.xplane.pb

Inside a ``bench.window`` span: a ``bench.flush`` span runs a jitted step
three times, a ``bench.poll`` span sleeps 50 ms with the device idle, and
a ``bench.advance`` span runs a jitted apply once.  Both programs compile
before the trace starts.
"""
from __future__ import annotations

import glob
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation


@jax.jit
def step(x):
    return jnp.tanh(x @ x)


@jax.jit
def apply(x, y):
    return x - 0.1 * y


def main(out: str) -> int:
    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 2
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    apply(x, step(x)).block_until_ready()
    tdir = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    try:
        with jax.profiler.trace(tdir, profiler_options=opts):
            with TraceAnnotation("bench.window"):
                with TraceAnnotation("bench.flush"):
                    y = x
                    for _ in range(3):
                        y = step(y)
                    y.block_until_ready()
                with TraceAnnotation("bench.poll"):
                    time.sleep(0.05)
                with TraceAnnotation("bench.advance"):
                    apply(x, y).block_until_ready()
        found = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                          recursive=True)
        shutil.copy(found[0], out)
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
    print(f"wrote {out} ({os.path.getsize(out)} bytes)")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
