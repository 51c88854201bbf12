"""Pure-jnp oracles for the fused server-apply kernels.

Server apply:    w ← w − s Δ   (s a *traced* scalar: β, β/M, or the
                 staleness-damped β/(1+τ)^a — no recompile per staleness)
Stacked apply:   w ← w − Σ_i s_i Δ_i          (fp32 bank rows)
Quantized apply: w ← w − Σ_i s_i·scale_i·q_i  (int8 bank rows + per-row
                 f32 scales: dequant folded into the reduction coefficient)

All of these are memory-bound elementwise chains over multi-GB parameter
tensors on the assigned architectures; the kernel fuses each into a single
HBM round-trip (DESIGN.md §6).
The stacked applies come in two reduction orders: the free-association
``jnp.sum`` forms below (fastest single-device lowering), and sequential
``*_seq_ref`` twins that accumulate rows one at a time in an explicit
order — the order-invariant oracle the sharded path uses so a flush's
result is bit-identical on the 1-D ``("cohort",)`` and 2-D
``("cohort", "model")`` meshes (a per-shard partial-sum reduction would
reassociate differently per cohort split).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def apply_scaled_ref(w, d, scale):
    """Server apply w ← w − s·Δ; ``scale`` may be a traced jnp scalar."""
    s = jnp.asarray(scale, jnp.float32)
    return (w.astype(jnp.float32) - s * d.astype(jnp.float32)).astype(w.dtype)


def apply_rows_ref(w, d_stack, weights):
    """Stacked server apply w ← w − Σ_i s_i·Δ_i in one reduction.

    ``d_stack`` is the on-device DeltaBank buffer ``[M, *w.shape]``;
    ``weights`` a traced ``[M]`` f32 vector carrying β/M, per-row staleness
    damping, and padding masks (zero rows contribute nothing).
    """
    s = jnp.asarray(weights, jnp.float32).reshape((-1,) + (1,) * w.ndim)
    acc = jnp.sum(s * d_stack.astype(jnp.float32), axis=0)
    return (w.astype(jnp.float32) - acc).astype(w.dtype)


def apply_rows_q_ref(w, q_stack, scales, weights):
    """Quantized stacked apply w ← w − Σ_i s_i·scale_i·q_i, one reduction.

    ``q_stack`` is the int8 ``[M, *w.shape]`` bank buffer and ``scales``
    its ``[M]`` f32 per-row dequant scales (``repro.core.quant``);
    ``weights`` the same traced admission-weight vector as
    :func:`apply_rows_ref`.  The dequant is folded into the per-row
    coefficient, so the oracle matches the kernel's arithmetic exactly
    (never dequantize-then-apply as two passes).
    """
    coeff = (jnp.asarray(weights, jnp.float32)
             * jnp.asarray(scales, jnp.float32)
             ).reshape((-1,) + (1,) * w.ndim)
    acc = jnp.sum(coeff * q_stack.astype(jnp.float32), axis=0)
    return (w.astype(jnp.float32) - acc).astype(w.dtype)


def apply_rows_seq_ref(w, d_stack, weights, order):
    """Order-invariant stacked apply: rows accumulate SEQUENTIALLY.

    ``order`` is an int32 ``[M]`` row permutation; the accumulation chain
    is ``((w − s_{o0}Δ_{o0}) − s_{o1}Δ_{o1}) − ...`` regardless of how the
    stack is sharded — every step is elementwise, so XLA SPMD partitions
    it spatially without reassociating the row chain.  This is what makes
    a serving-window flush bit-identical across mesh layouts: callers pass
    the *admission order* (a mesh-independent total order on the window's
    rows) and the result no longer depends on which cohort slice a row
    landed on.  Zero-weight padding rows contribute an exact ``+0``.
    """
    s = jnp.asarray(weights, jnp.float32)
    order = jnp.asarray(order, jnp.int32)

    def body(i, acc):
        j = order[i]
        return acc + s[j] * d_stack[j].astype(jnp.float32)

    acc = jax.lax.fori_loop(0, d_stack.shape[0], body,
                            jnp.zeros(w.shape, jnp.float32))
    return (w.astype(jnp.float32) - acc).astype(w.dtype)


def apply_rows_q_seq_ref(w, q_stack, scales, weights, order):
    """Quantized twin of :func:`apply_rows_seq_ref`: dequant folded into
    the per-row coefficient, rows accumulated sequentially in ``order``."""
    coeff = jnp.asarray(weights, jnp.float32) \
        * jnp.asarray(scales, jnp.float32)
    order = jnp.asarray(order, jnp.int32)

    def body(i, acc):
        j = order[i]
        return acc + coeff[j] * q_stack[j].astype(jnp.float32)

    acc = jax.lax.fori_loop(0, q_stack.shape[0], body,
                            jnp.zeros(w.shape, jnp.float32))
    return (w.astype(jnp.float32) - acc).astype(w.dtype)
