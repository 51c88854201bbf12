"""Named host spans at the program's layer boundaries.

``span("flush", window=3)`` is ``jax.profiler.TraceAnnotation(
"persafl.flush", window=3)``.  It records into whatever ``jax.profiler``
session is open (a traced benchmark run, ``launch/serve.py
--profile-dir``), on the same clock as the device planes, so a reader of
the trace can charge each idle stretch of the device to the span the host
was in.  With no session open it costs under a microsecond.  There is no
buffer, exporter or switch here: the profiler session records and writes.

Three rules keep the spans cheap and honest:

* a span never blocks and never reads a device value;
* one span per call at a layer boundary, never one per leaf or per row;
* metadata are small ints (window, rows, bucket), so that a reader can
  tie together the spans of one cohort.
"""
from __future__ import annotations

import jax

PREFIX = "persafl."


def span(name: str, **meta: int) -> jax.profiler.TraceAnnotation:
    """A context manager recording the span ``persafl.<name>``."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **meta)
