"""Symmetric int8 absmax quantization for delta banking + error feedback.

The serving ring's residency and the wire's SUBMIT/HEAD bodies both scale
linearly with delta precision.  This module provides the one codec both
reuse: **symmetric absmax int8** — per ROW per LEAF for stacked bank
buffers (:class:`QuantStack`), per LEAF for retained snapshots
(:class:`QuantTree`) —

    scale = absmax / 127          (0 for an all-zero row: dequant is exact)
    q     = clip(round(x / scale), -127, 127)  int8
    deq   = scale * q                          f32

plus **error feedback** (:func:`ef_quantize_stack`): the quantization
error of a user's banked delta is carried on device and added to that
user's *next* delta before re-quantizing, so banking noise stays a bounded
residual instead of a bias that accumulates across aggregation windows.

Handle types consumed by the serving stack:

  * :class:`QuantStack` — the quantized twin of a DeltaBank's ``stacked``
    buffer: int8 ``q`` leaves ``[capacity, ...]`` + f32 ``scales`` leaves
    ``[capacity]``.  A NamedTuple, hence a pytree: ``jax.tree`` utilities,
    shard_map and ``row_nbytes`` accounting all see both components.
  * :class:`QuantTree` — a quantized params(-subset) snapshot: int8
    leaves + one f32 scalar scale per leaf.
  * :class:`QuantizedBank` — duck-types the DeltaBank surface the
    :class:`repro.serving.bank.DeltaRing` needs (``stacked`` /
    ``capacity`` / ``k`` / ``__len__``) while never holding fp32 rows;
    ``rows()`` is a fused dequantizing gather.
  * :class:`QuantizedHeads` — a *lazy* head bank: ``head = snapshot −
    scale·q`` computed per gather, so quantized serving stores NO separate
    head bank at all (the residency win the ``quant`` bench gates).

The global window apply never materializes fp32 rows either:
``repro.core.apply_admitted_rows`` dispatches a :class:`QuantStack` to the
fused dequant-×-weight-×-accumulate kernel
``repro.kernels.fused_update.apply_rows_q``.
"""
from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np


class QuantStack(NamedTuple):
    """Quantized stacked bank buffer: int8 rows + per-row-per-leaf scales."""
    q: Any        # int8 pytree, leaves [capacity, ...]
    scales: Any   # f32 pytree, leaves [capacity]


class QuantTree(NamedTuple):
    """Quantized params(-subset) tree: int8 leaves + per-leaf scalar scale."""
    q: Any        # int8 pytree, param-shaped
    scales: Any   # f32 pytree, scalar per leaf


def _row_scale(x):
    """Per-row absmax/127 over all trailing axes; shape ``[capacity]``."""
    x32 = x.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(x32), axis=tuple(range(1, x.ndim))) \
        if x.ndim > 1 else jnp.abs(x32)
    return absmax / 127.0


def _bcast(scale, ndim):
    return scale.reshape(scale.shape + (1,) * (ndim - scale.ndim))


def _q(x32, scale):
    safe = jnp.where(scale > 0, scale, 1.0)
    return jnp.clip(jnp.round(x32 / safe), -127, 127).astype(jnp.int8)


def _quantize_stack(tree) -> QuantStack:
    leaves, treedef = jax.tree.flatten(tree)
    qs, scs = [], []
    for x in leaves:
        sc = _row_scale(x)
        qs.append(_q(x.astype(jnp.float32), _bcast(sc, x.ndim)))
        scs.append(sc)
    return QuantStack(jax.tree.unflatten(treedef, qs),
                      jax.tree.unflatten(treedef, scs))


def _dequantize_stack(qstack: QuantStack):
    return jax.tree.map(
        lambda q, s: q.astype(jnp.float32) * _bcast(s, q.ndim),
        qstack.q, qstack.scales)


_quantize_stack_jit = jax.jit(_quantize_stack)
_dequantize_stack_jit = jax.jit(_dequantize_stack)


def quantize_stack(tree) -> QuantStack:
    """``[capacity, ...]`` fp stacked pytree → :class:`QuantStack`."""
    return _quantize_stack_jit(tree)


def dequantize_stack(qstack: QuantStack):
    """:class:`QuantStack` → fp32 stacked pytree (scale·q per row)."""
    return _dequantize_stack_jit(qstack)


@jax.jit
def quantize_tree(tree) -> QuantTree:
    """Params(-subset) pytree → :class:`QuantTree` (per-leaf scalar scale).
    Used for retained ring snapshots of already-closed windows."""
    leaves, treedef = jax.tree.flatten(tree)
    qs, scs = [], []
    for x in leaves:
        sc = jnp.max(jnp.abs(x.astype(jnp.float32))) / 127.0
        qs.append(_q(x.astype(jnp.float32), sc))
        scs.append(sc)
    return QuantTree(jax.tree.unflatten(treedef, qs),
                     jax.tree.unflatten(treedef, scs))


@jax.jit
def dequantize_tree(qtree: QuantTree):
    return jax.tree.map(lambda q, s: q.astype(jnp.float32) * s,
                        qtree.q, qtree.scales)


# -- error feedback ---------------------------------------------------------

def _ef_body(adj):
    qstack = _quantize_stack(adj)
    err = jax.tree.map(lambda a, d: a.astype(jnp.float32) - d,
                       adj, _dequantize_stack(qstack))
    return qstack, _quantize_stack(err)


@jax.jit
def ef_quantize(raw):
    return _ef_body(raw)


@jax.jit
def ef_quantize_res(raw, res):
    return _ef_body(jax.tree.map(
        lambda x, r: x.astype(jnp.float32) + r, raw, res))


def ef_quantize_stack(raw, residual=None):
    """One fused error-feedback quantization step over a stacked buffer.

    ``adj = raw + residual`` (per row; ``residual`` is the carried
    quantization error of each row's user, zeros where absent), then
    ``adj`` is quantized and the NEW error ``adj − dequant`` is itself
    quantized for storage.  Returns ``(delta QuantStack, residual
    QuantStack)`` — the second is what the caller banks per user and feeds
    back on that user's next submission.  Quantizing the stored residual
    adds only a second-order error (≤ scale/254 of an already-small
    tensor), which the EF property test bounds.
    """
    if residual is None:
        return ef_quantize(raw)
    return ef_quantize_res(raw, residual)


# -- gathers ----------------------------------------------------------------

@jax.jit
def gather_rows(qstack: QuantStack, rows):
    """Dequantized fp32 ``[len(rows), ...]`` rows of a stack."""
    return jax.tree.map(
        lambda q, s: jnp.take(q, rows, axis=0).astype(jnp.float32)
        * _bcast(jnp.take(s, rows, axis=0), q.ndim),
        qstack.q, qstack.scales)


@jax.jit
def head_rows(snap, qstack: QuantStack, rows):
    """Head rows ``snapshot − scale·q``: f32 compute, param dtype out."""
    def one(p, q, s):
        d = jnp.take(q, rows, axis=0).astype(jnp.float32) \
            * _bcast(jnp.take(s, rows, axis=0), q.ndim)
        return (p[None].astype(jnp.float32) - d).astype(p.dtype)
    return jax.tree.map(one, snap, qstack.q, qstack.scales)


class QuantizedBank:
    """DeltaBank-shaped handle over a :class:`QuantStack`.

    Presents exactly the surface :class:`repro.serving.bank.DeltaRing`
    and ``apply_admitted_rows`` touch (``stacked``/``capacity``/``k``);
    there is deliberately no host-materializing ``row()`` — quantized
    banking never leaves the device, so ``host_materializations`` cannot
    move.
    """

    def __init__(self, qstack: QuantStack, k: int,
                 stats: Optional[Dict] = None):
        self.stacked = qstack
        self.k = k
        self._stats = stats if stats is not None else {}

    @property
    def capacity(self) -> int:
        return jax.tree.leaves(self.stacked.q)[0].shape[0]

    def __len__(self) -> int:
        return self.k

    def rows(self, rows):
        """Dequantized fp32 ``[len(rows), ...]`` gather (device-side)."""
        return gather_rows(self.stacked, jnp.asarray(rows, jnp.int32))

    def row(self, i: int):
        return jax.tree.map(lambda x: x[0], self.rows([int(i)]))


class QuantizedHeads:
    """Lazy quantized head bank: ``head_row = snapshot − scale·q``.

    Nothing is stored beyond a reference to the flush's snapshot tree and
    its delta :class:`QuantizedBank` — the fp32 head bank of the fp32
    serving path simply does not exist here, which is where quantized
    serving's ≥ 3.5x per-user residency win comes from.  ``rows``/``row``
    fuse the dequant and the subtraction into one jitted device gather
    (same output dtype discipline as the eager head bank: compute f32,
    store the param dtype).
    """

    def __init__(self, snapshot, qbank: QuantizedBank):
        self.snapshot = snapshot
        self.qbank = qbank

    @property
    def k(self) -> int:
        return self.qbank.k

    def rows(self, rows):
        return head_rows(self.snapshot, self.qbank.stacked,
                         jnp.asarray(rows, jnp.int32))

    def row(self, i: int):
        return jax.tree.map(lambda x: x[0], self.rows([int(i)]))


def fp32_row_nbytes(qstack: QuantStack) -> int:
    """Bytes ONE row of this stack would occupy as fp32 — the baseline the
    ``ring_bytes_saved_per_user`` stat and quant bench gate compare
    against (scales excluded: fp32 banking has none)."""
    return int(sum(int(np.prod(x.shape[1:])) * 4
                   for x in jax.tree.leaves(qstack.q)))
