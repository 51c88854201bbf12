"""Pallas TPU flash-attention (forward) kernel.

TPU adaptation (DESIGN.md §2): q is tiled into ``block_q``-row VMEM blocks
on a (batch, q-head, q-block) grid; K/V stream through VMEM in ``block_k``
chunks inside a ``fori_loop`` with the online-softmax running (m, l, acc)
state kept in VMEM scratch.  MXU alignment: block sizes are multiples of
128 and the contraction runs in f32.  GQA is expressed in the K/V
BlockSpec index maps (q-head h reads kv-head h // group), so no k/v
repetition ever hits HBM.  Supports causal masking, sliding windows
(gemma2/zamba2) and logit softcap (gemma2).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, scale, block_q, block_k,
                  seq_k, causal, window, softcap):
    iq = pl.program_id(2)
    q = q_ref[0, :, 0, :].astype(jnp.float32) * scale       # (bq, hd)
    q_start = iq * block_q

    nk = seq_k // block_k
    if causal:
        # only stream k-blocks that intersect the causal cone
        nk_live = (q_start + block_q + block_k - 1) // block_k
        nk = min(nk, nk_live) if isinstance(nk_live, int) else nk

    def body(ik, carry):
        m_prev, l_prev, acc = carry
        k_start = ik * block_k
        k = k_ref[0, pl.ds(k_start, block_k), 0, :].astype(jnp.float32)
        v = v_ref[0, pl.ds(k_start, block_k), 0, :].astype(jnp.float32)
        logits = q @ k.T                                     # (bq, bk)
        if softcap:
            logits = softcap * jnp.tanh(logits / softcap)
        qi = q_start + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 0)
        kj = k_start + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
        mask = jnp.ones_like(logits, dtype=jnp.bool_)
        if causal:
            mask = mask & (kj <= qi)
        if window:
            mask = mask & (kj > qi - window)
        logits = jnp.where(mask, logits, NEG_INF)
        m_cur = jnp.max(logits, axis=1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(logits - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=1)
        acc = acc * alpha[:, None] + p @ v
        return m_new, l_new, acc

    hd = q_ref.shape[-1]
    init = (jnp.full((block_q,), NEG_INF, jnp.float32),
            jnp.zeros((block_q,), jnp.float32),
            jnp.zeros((block_q, hd), jnp.float32))
    m, l, acc = jax.lax.fori_loop(0, nk, body, init)
    out = acc / jnp.maximum(l, 1e-30)[:, None]
    o_ref[0, :, 0, :] = out.astype(o_ref.dtype)


def flash_attention_fwd(q, k, v, *, causal: bool = True, window: int = 0,
                        softcap: float = 0.0, block_q: int = 128,
                        block_k: int = 128, interpret: bool):
    """q: (B,S,Hq,hd); k,v: (B,T,Hkv,hd) -> (B,S,Hq,hd).

    ``interpret`` has no default: ``ops.flash_attention`` decides it
    (``True`` runs the kernel body in Python on CPU).  The v5e compiler
    refuses this kernel: the ``(1, block_q, 1, hd)`` block breaks the
    last-two-dims tiling rule.
    """
    B, S, Hq, hd = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    group = Hq // Hkv
    block_q = min(block_q, S)
    block_k = min(block_k, T)
    assert S % block_q == 0 and T % block_k == 0, (S, T, block_q, block_k)
    grid = (B, Hq, S // block_q)

    kernel = functools.partial(
        _flash_kernel, scale=hd ** -0.5, block_q=block_q, block_k=block_k,
        seq_k=T, causal=causal, window=window, softcap=softcap)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, 1, hd),
                         lambda b, h, iq: (b, iq, h, 0)),
            pl.BlockSpec((1, T, 1, hd),
                         lambda b, h, iq, g=group: (b, 0, h // g, 0)),
            pl.BlockSpec((1, T, 1, hd),
                         lambda b, h, iq, g=group: (b, 0, h // g, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, 1, hd),
                               lambda b, h, iq: (b, iq, h, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
    )(q, k, v)
