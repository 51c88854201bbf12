"""Pallas TPU kernels: fused PersA-FL local updates and bank applies.

The paper's client loop applies η/λ-scaled parameter updates every local
step; at multi-billion-parameter scale each unfused update costs 3–4 HBM
round-trips (read w, read g, write w, plus the λ(θ−w) temporary for
Option C).  This module fuses each chain into one read-modify-write pass,
tiled as flat (block,) VMEM rows.  Math in f32, storage dtype preserved.

Two stacked-bank apply kernels close every aggregation window:

  * ``apply_rows``   — fp32 banking: ``w ← w − Σ_i weights[i]·Δ_i`` over a
    ``[M, n]`` delta stack, the weight vector folding β/M, per-row FedAsync
    staleness damping ``(1+τ)^{-a}`` and bucket-padding masks.
  * ``apply_rows_q`` — **int8 banking**: the stack arrives quantized
    (symmetric absmax, ``repro.core.quant``) as int8 rows + per-row f32
    scales, and the kernel folds dequantization × admission weight ×
    accumulate into the SAME one-pass read-modify-write: the coefficient
    ``weights[i]·scales[i]`` multiplies ``int8→f32`` rows in VMEM, so a
    straggler re-admission never materializes an fp32 delta row anywhere.
    Scales ride alongside the traced weight vector as a second ``[rows,1]``
    operand block — identical padding-mask and pow2-row-bucket discipline,
    so one compile per bucket serves every window composition.

Both have jnp oracles in ``ref.py`` (bit-comparable in interpret mode) and
pytree-aware jitted fronts in ``ops.py``.  Every entry takes ``interpret``
with no default: ``ops.py`` is the one place that decides (the compiled
kernel on a TPU, the Pallas interpreter elsewhere).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK = 64 * 1024  # 256 KiB f32 per operand per step — comfortably VMEM


def _sgd_kernel(w_ref, g_ref, o_ref, *, eta):
    w = w_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    o_ref[...] = (w - eta * g).astype(o_ref.dtype)


def _prox_inner_kernel(t_ref, g_ref, w_ref, o_ref, *, eta_in, lam):
    t = t_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    o_ref[...] = (t - eta_in * (g + lam * (t - w))).astype(o_ref.dtype)


def _prox_outer_kernel(w_ref, t_ref, o_ref, *, eta, lam):
    w = w_ref[...].astype(jnp.float32)
    t = t_ref[...].astype(jnp.float32)
    o_ref[...] = (w - eta * lam * (w - t)).astype(o_ref.dtype)


def _run_flat(kernel, out_dtype, *arrays, interpret: bool):
    """Pad to a BLOCK multiple, run the 1-D grid, unpad."""
    flat = [a.reshape(-1) for a in arrays]
    n = flat[0].shape[0]
    pad = (-n) % BLOCK
    if pad:
        flat = [jnp.pad(a, (0, pad)) for a in flat]
    total = n + pad
    grid = (total // BLOCK,)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((BLOCK,), lambda i: (i,)) for _ in flat],
        out_specs=pl.BlockSpec((BLOCK,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((total,), out_dtype),
        interpret=interpret,
    )(*flat)
    return out[:n].reshape(arrays[0].shape)


def sgd_step(w, g, eta: float, *, interpret: bool):
    return _run_flat(functools.partial(_sgd_kernel, eta=eta), w.dtype, w, g,
                     interpret=interpret)


def prox_inner(theta, g, w, eta_in: float, lam: float, *,
               interpret: bool):
    return _run_flat(functools.partial(_prox_inner_kernel, eta_in=eta_in,
                                       lam=lam),
                     theta.dtype, theta, g, w, interpret=interpret)


def prox_outer(w, theta, eta: float, lam: float, *, interpret: bool):
    return _run_flat(functools.partial(_prox_outer_kernel, eta=eta, lam=lam),
                     w.dtype, w, theta, interpret=interpret)


def _apply_scaled_kernel(w_ref, d_ref, s_ref, o_ref):
    # s lives in SMEM as a (1, 1) scalar so the scale (β, β/M, or the
    # staleness-damped β/(1+τ)^a) stays a traced value — one compile
    # serves every staleness/buffer-count the scheduler produces.
    s = s_ref[0, 0]
    w = w_ref[...].astype(jnp.float32)
    d = d_ref[...].astype(jnp.float32)
    o_ref[...] = (w - s * d).astype(o_ref.dtype)


# apply_rows tiles: ROW_BLOCK×COL_BLOCK f32 delta tiles must fit VMEM next
# to the w/o blocks — 128×8192×4 = 4 MiB.  Cohort buckets are pow2, so for
# M ≤ 128 (every realistic cohort) the whole reduction is ONE grid pass per
# column block and the f32 accumulator never round-trips through the output
# dtype; beyond that the row-chunk grid dim revisits the output block.
ROW_BLOCK = 128
COL_BLOCK = 8192


def _apply_rows_kernel(w_ref, d_ref, s_ref, o_ref):
    # partial reduction over this row chunk: s is [rows, 1] f32 in VMEM so
    # the weight vector (β/M · damping · padding mask per row) stays traced
    r = pl.program_id(1)
    part = jnp.sum(s_ref[...] * d_ref[...].astype(jnp.float32), axis=0)

    @pl.when(r == 0)
    def _init():
        o_ref[...] = (w_ref[...].astype(jnp.float32) - part).astype(o_ref.dtype)

    @pl.when(r > 0)
    def _accum():
        o_ref[...] = (o_ref[...].astype(jnp.float32) - part).astype(o_ref.dtype)


def apply_rows(w, d_stack, weights, *, interpret: bool):
    """Stacked server apply w ← w − Σ_i weights[i]·Δ_i, one fused pass.

    ``d_stack``: ``[M, *w.shape]`` stacked delta buffer (a DeltaBank's
    device buffer); ``weights``: traced ``[M]`` f32 — β/M, per-row FedAsync
    staleness damping and padding masks are all just rows of this vector,
    so one compile serves every buffer composition.  The column grid axis
    is major and the row-chunk axis minor, so each output block is visited
    on consecutive iterations (the Pallas revisiting contract).
    """
    m = d_stack.shape[0]
    flat_w = w.reshape(-1)
    flat_d = d_stack.reshape(m, -1)
    n = flat_w.shape[0]
    pad = (-n) % COL_BLOCK
    if pad:
        flat_w = jnp.pad(flat_w, (0, pad))
        flat_d = jnp.pad(flat_d, ((0, 0), (0, pad)))
    row_blk = min(1 << max(m - 1, 0).bit_length(), ROW_BLOCK)
    rpad = (-m) % row_blk
    s = jnp.asarray(weights, jnp.float32).reshape(m, 1)
    if rpad:  # zero-weight, zero-delta padding rows: contribute nothing
        flat_d = jnp.pad(flat_d, ((0, rpad), (0, 0)))
        s = jnp.pad(s, ((0, rpad), (0, 0)))
    total = n + pad
    grid = (total // COL_BLOCK, (m + rpad) // row_blk)
    out = pl.pallas_call(
        _apply_rows_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((COL_BLOCK,), lambda c, r: (c,)),
                  pl.BlockSpec((row_blk, COL_BLOCK), lambda c, r: (r, c)),
                  pl.BlockSpec((row_blk, 1), lambda c, r: (r, 0))],
        out_specs=pl.BlockSpec((COL_BLOCK,), lambda c, r: (c,)),
        out_shape=jax.ShapeDtypeStruct((total,), w.dtype),
        interpret=interpret,
    )(flat_w, flat_d, s)
    return out[:n].reshape(w.shape)


def _apply_rows_q_kernel(w_ref, q_ref, s_ref, sc_ref, o_ref):
    # fused dequant × admission-weight × accumulate: the per-row coefficient
    # weights[i]·scales[i] (both [rows, 1] f32, traced) multiplies the
    # int8→f32 rows in VMEM, so the fp32 delta row never exists in memory —
    # only the partial sums do
    r = pl.program_id(1)
    coeff = s_ref[...] * sc_ref[...]
    part = jnp.sum(coeff * q_ref[...].astype(jnp.float32), axis=0)

    @pl.when(r == 0)
    def _init():
        o_ref[...] = (w_ref[...].astype(jnp.float32) - part).astype(o_ref.dtype)

    @pl.when(r > 0)
    def _accum():
        o_ref[...] = (o_ref[...].astype(jnp.float32) - part).astype(o_ref.dtype)


def apply_rows_q(w, q_stack, scales, weights, *, interpret: bool):
    """Quantized stacked apply ``w ← w − Σ_i weights[i]·scales[i]·q_i``.

    ``q_stack``: ``[M, *w.shape]`` int8 rows (symmetric absmax quantized);
    ``scales``: ``[M]`` f32 per-row dequantization scales; ``weights``: the
    same traced ``[M]`` f32 admission-weight vector as :func:`apply_rows`.
    Same grid, padding and pow2-row-bucket discipline — zero-weight
    zero-scale padding rows contribute nothing — with the dequant folded
    into the reduction coefficient, so the bank's int8 rows are read once
    and no fp32 copy of the stack is ever materialized.
    """
    m = q_stack.shape[0]
    flat_w = w.reshape(-1)
    flat_q = q_stack.reshape(m, -1)
    n = flat_w.shape[0]
    pad = (-n) % COL_BLOCK
    if pad:
        flat_w = jnp.pad(flat_w, (0, pad))
        flat_q = jnp.pad(flat_q, ((0, 0), (0, pad)))
    row_blk = min(1 << max(m - 1, 0).bit_length(), ROW_BLOCK)
    rpad = (-m) % row_blk
    s = jnp.asarray(weights, jnp.float32).reshape(m, 1)
    sc = jnp.asarray(scales, jnp.float32).reshape(m, 1)
    if rpad:  # zero-weight, zero-scale padding rows: contribute nothing
        flat_q = jnp.pad(flat_q, ((0, rpad), (0, 0)))
        s = jnp.pad(s, ((0, rpad), (0, 0)))
        sc = jnp.pad(sc, ((0, rpad), (0, 0)))
    total = n + pad
    grid = (total // COL_BLOCK, (m + rpad) // row_blk)
    out = pl.pallas_call(
        _apply_rows_q_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((COL_BLOCK,), lambda c, r: (c,)),
                  pl.BlockSpec((row_blk, COL_BLOCK), lambda c, r: (r, c)),
                  pl.BlockSpec((row_blk, 1), lambda c, r: (r, 0)),
                  pl.BlockSpec((row_blk, 1), lambda c, r: (r, 0))],
        out_specs=pl.BlockSpec((COL_BLOCK,), lambda c, r: (c,)),
        out_shape=jax.ShapeDtypeStruct((total,), w.dtype),
        interpret=interpret,
    )(flat_w, flat_q, s, sc)
    return out[:n].reshape(w.shape)


def apply_scaled(w, d, scale, *, interpret: bool):
    """Server apply w ← w − s·Δ in one read-modify-write pass."""
    flat_w, flat_d = w.reshape(-1), d.reshape(-1)
    n = flat_w.shape[0]
    pad = (-n) % BLOCK
    if pad:
        flat_w = jnp.pad(flat_w, (0, pad))
        flat_d = jnp.pad(flat_d, (0, pad))
    total = n + pad
    s = jnp.asarray(scale, jnp.float32).reshape(1, 1)
    out = pl.pallas_call(
        _apply_scaled_kernel,
        grid=(total // BLOCK,),
        in_specs=[pl.BlockSpec((BLOCK,), lambda i: (i,)),
                  pl.BlockSpec((BLOCK,), lambda i: (i,)),
                  pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=pl.BlockSpec((BLOCK,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((total,), w.dtype),
        interpret=interpret,
    )(flat_w, flat_d, s)
    return out[:n].reshape(w.shape)
