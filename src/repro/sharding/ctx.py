"""Mesh context + activation sharding.

This module owns how every tensor in the system is *placed*:

  * **The cohort/model mesh.**  :func:`cohort_mesh` is the 1-D
    ``("cohort",)`` layout; :func:`cohort_model_mesh` generalizes it to the
    2-D ``("cohort", "model")`` mesh that unifies the FL path with
    ``launch/mesh.py``'s ``("data", "model")`` production mesh and the
    ``rules.py`` param specs.  Which axis shards what:

      - the **"cohort" axis** carries everything with a leading per-client
        /per-user row dim: stacked batch buffers, DeltaBank/DeltaRing delta
        stacks, head banks, QuantStack codes + scales, stacked client
        state.  Row ``i`` of a ``[bucket, ...]`` buffer lands on cohort
        slice ``i // (bucket // cohort_axis_size)``, which is the layout
        contract behind the serving batcher's user→cohort-slice keying.
      - the **"model" axis** shards *storage*, not cohort compute: params
        at rest, retained window snapshots, and the model dims of every
        bank row, placed by ``rules.py``-style ``PartitionSpec``s (or any
        caller-provided ``param_shardings``).  ``CohortEngine`` shard_map
        bodies are Manual over ALL mesh axes with params replicated inside
        the region (a ``with_sharding_constraint`` gather right before the
        call), and the engine re-shards the delta stack to
        ``P("cohort", *param_spec)`` per leaf right after — a pure
        placement move, bits unchanged.  Two reasons compute stays
        model-replicated: (a) ``lax.scan``/``lax.map`` inside a
        partially-Auto shard_map crashed XLA on jax 0.4.x
        (``IsManualSubgroup`` check), and real archs scan internally; that
        crash has not been re-tested on jax 0.9.0; (b) model-sharded grads
        reassociate cross-class reductions (softmax) and break the
        bit-parity contract between mesh layouts.
        The masked cohort mean stays a single ``psum("cohort")`` per leaf
        that never crosses "model" (a cross-model reduction would
        re-reduce *within* each row — wrong math, not just wrong layout).

    Meshes are **memoized per (device set, shape)** — constructing a fresh
    ``jax.sharding.Mesh`` per call defeated jit caches keyed on sharding
    identity and leaked one mesh object per engine/batcher call.
    :func:`reset_mesh_cache` (owned by the mesh context) is the one
    invalidation point, for tests that fake out the device set.

  * **The mesh context.**  :func:`use_mesh` installs a mesh thread-locally;
    :func:`active_mesh` reads it back.  Engines and the serving stack
    consume the context when no explicit ``mesh=`` is passed, so
    ``launch/serve.py --model-axis 4`` re-homes the whole pipeline onto the
    2-D mesh without threading a mesh argument through every layer.

  * **Activation sharding.**  Models are mesh-agnostic; the launch layer
    may install a mapping from *logical* activation names ("ffn",
    "attn_out", "moe_dispatch", ...) to ``PartitionSpec``s.  When no
    context is installed (unit tests, CPU smoke), ``shard_activation`` is
    a no-op, keeping the model code pure.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Dict, Optional, Tuple

import jax

_state = threading.local()


# -- memoized mesh construction ---------------------------------------------

# (device ids, axis names, axis sizes) -> Mesh.  One mesh object per
# layout: jit caches and NamedSharding equality key on mesh identity, and
# the pre-memoization behavior (a fresh Mesh per cohort_mesh() call) both
# leaked and defeated those caches.
_MESH_CACHE: Dict[Tuple, "jax.sharding.Mesh"] = {}


def reset_mesh_cache() -> None:
    """Drop every memoized mesh.  The mesh context owns invalidation: call
    this when the device set changes under you (tests faking
    ``--xla_force_host_platform_device_count``, distributed re-init)."""
    _MESH_CACHE.clear()


def cohort_mesh(devices=None) -> "jax.sharding.Mesh":
    """The 1-D ``("cohort",)`` mesh over every addressable device
    (memoized — repeated engine/batcher calls share ONE mesh object).

    This is the layout contract shared by ``fl/engine.py``'s
    ``cohort_impl="shard_map"`` and the serving batcher's
    user→cohort-slice keying (``repro.serving.batcher``): row ``i`` of a
    ``[bucket, ...]`` cohort buffer lands on cohort slice
    ``i // (bucket // cohort_axis_size)``, so a batcher that places a user
    at a stable per-slice slot pins that user's delta rows to one cohort
    slice across windows.
    """
    return cohort_model_mesh(model_axis=None, devices=devices)


def cohort_model_mesh(model_axis: Optional[int] = None,
                      devices=None) -> "jax.sharding.Mesh":
    """The ``("cohort", "model")`` mesh: cohort-parallel × model-parallel.

    ``model_axis=None`` returns the 1-D ``("cohort",)`` mesh (the two
    spellings share one cache, so ``cohort_mesh()`` and
    ``cohort_model_mesh(None)`` are the same object).  With ``model_axis=m``
    the device grid is ``(n_devices // m, m)``: delta/head bank rows split
    over "cohort", each row's model dims split over "model" via the
    params' shardings (``rules.py`` specs or explicit ``param_shardings``).
    ``model_axis=1`` is the 2-D mesh with a degenerate model axis — same
    cohort split as the 1-D mesh, useful for parity checks.
    """
    import numpy as np
    from jax.sharding import Mesh
    devs = tuple(jax.devices()) if devices is None else tuple(devices)
    n = len(devs)
    if model_axis is None:
        key = (tuple(d.id for d in devs), ("cohort",), (n,))
        if key not in _MESH_CACHE:
            _MESH_CACHE[key] = Mesh(np.asarray(devs), ("cohort",))
        return _MESH_CACHE[key]
    m = int(model_axis)
    if m < 1 or n % m:
        raise ValueError(f"model_axis={m} must divide the device count "
                         f"({n})")
    key = (tuple(d.id for d in devs), ("cohort", "model"), (n // m, m))
    if key not in _MESH_CACHE:
        _MESH_CACHE[key] = Mesh(np.asarray(devs).reshape(n // m, m),
                                ("cohort", "model"))
    return _MESH_CACHE[key]


def cohort_axis_size(mesh: "jax.sharding.Mesh") -> int:
    """Number of cohort slices of a mesh — the row-dim shard count bank
    buffers and the batcher's user keying are laid out for.  A mesh
    without a "cohort" axis (the production ``("data", "model")`` mesh)
    has one cohort slice: every row lives on the model-parallel group."""
    return int(dict(zip(mesh.axis_names, mesh.devices.shape))
               .get("cohort", 1))


@contextlib.contextmanager
def use_mesh(mesh: "jax.sharding.Mesh"):
    """Install ``mesh`` as the ambient cohort/model mesh.  Engines and the
    serving stack pick it up when constructed without an explicit
    ``mesh=`` — the ``launch/serve.py --model-axis`` path wraps server
    construction in this context instead of threading a mesh through
    every constructor."""
    prev = getattr(_state, "mesh", None)
    _state.mesh = mesh
    try:
        yield mesh
    finally:
        _state.mesh = prev


def active_mesh() -> Optional["jax.sharding.Mesh"]:
    """The mesh installed by :func:`use_mesh`, or None."""
    return getattr(_state, "mesh", None)


# -- activation sharding ------------------------------------------------------


def _rules() -> Optional[Dict[str, "jax.sharding.PartitionSpec"]]:
    return getattr(_state, "rules", None)


@contextlib.contextmanager
def activation_sharding(rules: Dict[str, "jax.sharding.PartitionSpec"]):
    prev = _rules()
    _state.rules = rules
    try:
        yield
    finally:
        _state.rules = prev


def shard_activation(x, name: str):
    rules = _rules()
    if rules is None or name not in rules:
        return x
    spec = rules[name]
    # pad/trim the spec to the array rank
    parts = list(spec)
    if len(parts) < x.ndim:
        parts = parts + [None] * (x.ndim - len(parts))
    elif len(parts) > x.ndim:
        parts = parts[: x.ndim]
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.PartitionSpec(*parts))
