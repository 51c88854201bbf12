"""Named host spans at the program's layer boundaries, and named scopes
inside its device programs.

``span("flush", window=3)`` is ``jax.profiler.TraceAnnotation(
"persafl.flush", window=3)``.  It records into whatever ``jax.profiler``
session is open (a traced benchmark run, ``launch/serve.py
--profile-dir``), on the same clock as the device planes, so a reader of
the trace can charge each idle stretch of the device to the span the host
was in.  With no session open it costs under a microsecond.  There is no
buffer, exporter or switch here: the profiler session records and writes.

``scope("zamba2.shared")`` is ``jax.named_scope("persafl.zamba2.shared")``:
used while a program is traced, it puts the name into the ``op_name``
metadata of every operation traced inside it, so the lowered and compiled
program say which of the model's parts each operation belongs to.  It
costs nothing at run time.

Three rules keep the spans cheap and honest:

* a span never blocks and never reads a device value;
* one span per call at a layer boundary, never one per leaf or per row;
* metadata are small ints (window, rows, bucket), so that a reader can
  tie together the spans of one cohort.

This is the only module that opens annotations or scopes.
"""
from __future__ import annotations

import jax

PREFIX = "persafl."


def span(name: str, **meta: int) -> jax.profiler.TraceAnnotation:
    """A context manager recording the span ``persafl.<name>``."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **meta)


def scope(name: str):
    """A context manager naming the operations traced inside it
    ``persafl.<name>``."""
    return jax.named_scope(PREFIX + name)
