"""Pallas TPU kernel for the Mamba2 SSD chunked scan.

TPU adaptation (DESIGN.md §2): the GPU Mamba2 kernel leans on warp-level
parallel prefix scans; on TPU we instead exploit the *sequential* grid —
the grid's innermost dimension iterates chunks in order, so the inter-chunk
recurrent state lives in a VMEM scratch accumulator that persists across
grid steps (reset at chunk 0).  Intra-chunk work is the quadratic
attention-like form, which maps onto the MXU as (chunk × chunk) matmuls.

Grid: (batch, n_chunks) — chunks innermost/sequential per batch row.
Blocks: one chunk of x/dt/B/C per step; all heads resident (head_dim ≤ 64,
state ≤ 128 keeps VMEM ≈ a few MB for the assigned configs).

Status on TPU v5e: the compiler refuses this kernel (``jnp.cumsum`` has no
Mosaic lowering), so it runs only interpreted.  ``interpret`` has no
default; ``ops.ssd`` decides it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, alog_ref, b_ref, c_ref, y_ref, state_ref, *,
                chunk: int, n_heads: int, head_dim: int, n_state: int,
                n_groups: int):
    ic = pl.program_id(1)
    f32 = jnp.float32

    @pl.when(ic == 0)
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0].astype(f32)                      # (l, H, P)
    dt = dt_ref[0].astype(f32)                    # (l, H)
    a_log = alog_ref[...].astype(f32)             # (H,)
    bmat = b_ref[0].astype(f32)                   # (l, G, N)
    cmat = c_ref[0].astype(f32)                   # (l, G, N)

    rep = n_heads // n_groups
    bm = jnp.repeat(bmat, rep, axis=1)            # (l, H, N)
    cm = jnp.repeat(cmat, rep, axis=1)

    A = -jnp.exp(a_log)                           # (H,)
    a = dt * A[None, :]                           # (l, H)
    a_cum = jnp.cumsum(a, axis=0)                 # (l, H)
    x_dt = x * dt[..., None]                      # (l, H, P)

    # intra-chunk: L[l,s] = exp(acum_l - acum_s) for l >= s
    seg = a_cum[:, None, :] - a_cum[None, :, :]   # (l, s, H)
    tri = jnp.tril(jnp.ones((chunk, chunk), jnp.bool_))
    seg = jnp.where(tri[:, :, None], seg, -jnp.inf)
    L = jnp.exp(seg)                              # (l, s, H)
    # scores: (l,s,H) = sum_n C[l,h,n] B[s,h,n]
    scores = jnp.einsum("lhn,shn->lsh", cm, bm) * L
    y = jnp.einsum("lsh,shp->lhp", scores, x_dt)

    # inter-chunk: contribution of the carried state
    decay_in = jnp.exp(a_cum)                     # (l, H)
    state = state_ref[...].astype(f32)            # (H, P, N)
    y += jnp.einsum("lhn,hpn,lh->lhp", cm, state, decay_in)

    # update carried state for the next chunk
    decay_out = jnp.exp(a_cum[-1:, :] - a_cum)    # (l, H)
    chunk_state = jnp.einsum("lhn,lh,lhp->hpn", bm, decay_out, x_dt)
    total_decay = jnp.exp(jnp.sum(a, axis=0))     # (H,)
    state_ref[...] = (state * total_decay[:, None, None]
                      + chunk_state).astype(state_ref.dtype)

    y_ref[0] = y.astype(y_ref.dtype)


def ssd_fwd(x, dt, a_log, B_mat, C_mat, *, chunk: int = 128,
            interpret: bool):
    """x: (B,S,H,P); dt: (B,S,H); a_log: (H,); B/C: (B,S,G,N) -> (B,S,H,P) f32."""
    Bb, S, H, P = x.shape
    G, N = B_mat.shape[2], B_mat.shape[3]
    chunk = min(chunk, S)
    assert S % chunk == 0, (S, chunk)
    nc = S // chunk
    grid = (Bb, nc)

    kernel = functools.partial(_ssd_kernel, chunk=chunk, n_heads=H,
                               head_dim=P, n_state=N, n_groups=G)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, H, P), lambda b, c: (b, c, 0, 0)),
            pl.BlockSpec((1, chunk, H), lambda b, c: (b, c, 0)),
            pl.BlockSpec((H,), lambda b, c: (0,)),
            pl.BlockSpec((1, chunk, G, N), lambda b, c: (b, c, 0, 0)),
            pl.BlockSpec((1, chunk, G, N), lambda b, c: (b, c, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, H, P), lambda b, c: (b, c, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((Bb, S, H, P), jnp.float32),
        scratch_shapes=[pltpu.VMEM((H, P, N), jnp.float32)],
        interpret=interpret,
    )(x, dt, a_log, B_mat, C_mat)
