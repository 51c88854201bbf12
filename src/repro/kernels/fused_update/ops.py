"""jit'd wrappers for the fused-update kernel, pytree-aware."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.fused_update import kernel as K
from repro.kernels.fused_update import ref as R


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _dispatch(kernel_fn, ref_fn, mode):
    if mode == "ref" or (mode == "auto" and not _on_tpu()):
        return ref_fn
    return functools.partial(kernel_fn, interpret=not _on_tpu())


@functools.lru_cache(maxsize=None)
def _apply_delta_jit():
    @functools.partial(jax.jit, static_argnames=("mode",), donate_argnums=0)
    def apply(w_tree, d_tree, scale, mode: str = "auto"):
        fn = _dispatch(K.apply_scaled, R.apply_scaled_ref, mode)
        s = jnp.asarray(scale, jnp.float32)
        return jax.tree.map(lambda w, d: fn(w, d, s), w_tree, d_tree)
    return apply


def apply_delta_tree(w_tree, d_tree, scale, mode: str = "auto"):
    """Server apply w ← w − s·Δ over a pytree in one fused pass per leaf.

    ``scale`` is traced (β, β/M, or staleness-damped β/(1+τ)^a), so one
    compile serves every staleness value and buffer count; the params tree
    is donated (on every backend), so the apply is an in-place
    read-modify-write and the caller's input tree is deleted.
    """
    return _apply_delta_jit()(w_tree, d_tree, scale, mode=mode)


@functools.lru_cache(maxsize=None)
def _apply_rows_jit():
    @functools.partial(jax.jit, static_argnames=("mode",), donate_argnums=0)
    def apply(w_tree, stack_tree, weights, mode: str = "auto"):
        fn = _dispatch(K.apply_rows, R.apply_rows_ref, mode)
        s = jnp.asarray(weights, jnp.float32)
        return jax.tree.map(lambda w, d: fn(w, d, s), w_tree, stack_tree)
    return apply


def spans_devices(tree) -> bool:
    """True when any leaf is a committed array sharded over >1 device.
    Tracers (inside jit) report False — callers that jit the apply must
    resolve the dispatch mode on concrete arrays first."""
    for leaf in jax.tree.leaves(tree):
        try:
            sharding = getattr(leaf, "sharding", None)
        except Exception:
            continue
        if sharding is not None and len(sharding.device_set) > 1:
            return True
    return False


@functools.lru_cache(maxsize=None)
def _apply_rows_seq_jit():
    # order-invariant sequential twin of _apply_rows_jit: the dispatch for
    # device-spanning stacks, where a per-shard partial-sum reduction
    # would make the flush result depend on the mesh layout
    @functools.partial(jax.jit, donate_argnums=0)
    def apply(w_tree, stack_tree, weights, order):
        s = jnp.asarray(weights, jnp.float32)
        return jax.tree.map(
            lambda w, d: R.apply_rows_seq_ref(w, d, s, order),
            w_tree, stack_tree)
    return apply


@functools.lru_cache(maxsize=None)
def _apply_rows_q_seq_jit():
    @functools.partial(jax.jit, donate_argnums=0)
    def apply(w_tree, q_tree, scales_tree, weights, order):
        s = jnp.asarray(weights, jnp.float32)
        return jax.tree.map(
            lambda w, q, sc: R.apply_rows_q_seq_ref(w, q, sc, s, order),
            w_tree, q_tree, scales_tree)
    return apply


def _default_order(stack_tree):
    import numpy as np
    return np.arange(jax.tree.leaves(stack_tree)[0].shape[0],
                     dtype=np.int32)


@functools.lru_cache(maxsize=None)
def _apply_rows_q_jit():
    @functools.partial(jax.jit, static_argnames=("mode",), donate_argnums=0)
    def apply(w_tree, q_tree, scales_tree, weights, mode: str = "auto"):
        fn = _dispatch(K.apply_rows_q, R.apply_rows_q_ref, mode)
        s = jnp.asarray(weights, jnp.float32)
        return jax.tree.map(lambda w, q, sc: fn(w, q, sc, s),
                            w_tree, q_tree, scales_tree)
    return apply


def apply_rows_q_tree(w_tree, q_tree, scales_tree, weights,
                      mode: str = "auto", order=None):
    """Quantized twin of :func:`apply_rows_tree`: the stack arrives as an
    int8 ``q_tree`` (leaves ``[M, ...]``) + f32 ``scales_tree`` (leaves
    ``[M]``, per row per leaf — the :class:`repro.core.quant.QuantStack`
    components) and each leaf's apply folds dequant × admission weight ×
    accumulate into one fused pass — no fp32 copy of the bank is ever
    materialized.  Sharded stacks force the sequential oracle path for
    the same reason as :func:`apply_rows_tree` (mesh-invariant reduction
    order).
    """
    if mode == "auto" and spans_devices(q_tree):
        mode = "seq"
    if mode == "seq":
        if order is None:
            order = _default_order(q_tree)
        return _apply_rows_q_seq_jit()(w_tree, q_tree, scales_tree,
                                       weights, order)
    return _apply_rows_q_jit()(w_tree, q_tree, scales_tree, weights,
                               mode=mode)


def apply_rows_tree(w_tree, stack_tree, weights, mode: str = "auto",
                    order=None):
    """Stacked server apply w ← w − Σ_i weights[i]·Δ_i per leaf, fused.

    ``stack_tree`` is a DeltaBank buffer: params-shaped pytree whose leaves
    carry a leading ``[M]`` cohort axis and never leave the device;
    ``weights`` is the traced ``[M]`` f32 row-weight vector (β/M, staleness
    damping, padding masks).  One compile per (bucket, leaf-shape) serves
    every flush.

    A device-spanning stack (``cohort_impl="shard_map"`` banks, on the 1-D
    or 2-D mesh alike) forces ``mode="seq"``: the sequential oracle
    (:func:`repro.kernels.fused_update.ref.apply_rows_seq_ref`)
    accumulates rows one at a time — in ``order`` when given, row order
    otherwise — so the flush result is bit-identical across mesh layouts.
    The Pallas kernel has no partitioning rule (it would gather the whole
    multi-GB buffer onto every device), and a ``jnp.sum`` reduction would
    reassociate per cohort split.
    """
    if mode == "auto" and spans_devices(stack_tree):
        mode = "seq"
    if mode == "seq":
        if order is None:
            order = _default_order(stack_tree)
        return _apply_rows_seq_jit()(w_tree, stack_tree, weights, order)
    return _apply_rows_jit()(w_tree, stack_tree, weights, mode=mode)
