"""Production step functions — what the dry-run lowers and a real cluster
would run.

``make_train_step`` builds one PersA-FL *client round* under pjit:
Q scanned local steps (Option A/B/C) computed at the STALE parameters
w^{Ω(t)}, followed by the server apply w^{t+1} = w^t − β Δ (Algorithm 1).
Carrying the stale copy as an explicit input materializes Assumption-1
staleness in the compute graph (DESIGN.md §2).

SPMD semantics: the batch is sharded over the (pod, data) axes while the
stale params are replicated across them, so the gradient's implicit psum
over data axes makes the same graph serve both the paper-faithful mode
(the batch is one client's data) and the beyond-paper buffered-cohort mode
(the batch spans M clients — FedBuff-style aggregation for free).

Training memory: the client delta is accumulated (Δ = η Σ ∇̃, exact
telescoping of Algorithm 2) instead of keeping a second moving parameter
copy, and microbatching wraps the loss in remat'd gradient accumulation.
"""
from __future__ import annotations

import functools
from typing import Callable, Dict

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, InputShape
from repro.core import PersAFLConfig, client_update
from repro.models import api


def microbatched(loss_fn: Callable, n_mb: int) -> Callable:
    """grad(microbatched(loss)) == grad-accumulation over n_mb slices with
    one-microbatch activation memory (each slice is remat'd)."""
    if n_mb <= 1:
        return loss_fn

    def loss(params, batch):
        b = jax.tree.map(
            lambda x: x.reshape((n_mb, x.shape[0] // n_mb) + x.shape[1:]),
            batch)

        def body(acc, mb):
            return acc + jax.checkpoint(loss_fn)(params, mb), None

        total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), b)
        return total / n_mb

    return loss


def make_loss(cfg: ArchConfig, n_mb: int = 1) -> Callable:
    base = lambda p, b: api.loss_fn(cfg, p, b)
    return microbatched(base, n_mb)


def _q_batches(pcfg: PersAFLConfig, batch: Dict):
    """Broadcast the round's batch across the Q local steps (the dry-run
    feeds one batch; a real deployment streams fresh D_{i,q} per step —
    identical graph)."""
    q = pcfg.q_local

    def rep(x):
        return jnp.broadcast_to(x[None], (q,) + x.shape)

    tiled = jax.tree.map(rep, batch)
    if pcfg.option == "B":
        return {"d": tiled, "dp": tiled, "dpp": tiled}
    return tiled


def make_train_step(cfg: ArchConfig, pcfg: PersAFLConfig,
                    n_microbatches: int = 0) -> Callable:
    n_mb = n_microbatches or cfg.train_microbatches
    loss = make_loss(cfg, n_mb)

    def train_step(server_params, stale_params, batch):
        delta, metrics = client_update(pcfg, loss, stale_params,
                                       _q_batches(pcfg, batch))
        new_params = jax.tree.map(
            lambda w, d: (w.astype(jnp.float32)
                          - pcfg.beta * d).astype(w.dtype),
            server_params, delta)
        return new_params, metrics

    return train_step


def make_cohort_train_step(cfg: ArchConfig, pcfg: PersAFLConfig, mesh,
                           n_microbatches: int = 0,
                           cohort_axes=None) -> Callable:
    """Beyond-paper §Perf variant: FedBuff-style cohort round via shard_map.

    Each slice along the (pod, data) axes is an *independent client* running
    its own Q local steps on replicated params (no per-gradient psum); the
    deltas are averaged ONCE at the end (Algorithm 1 buffered apply,
    [51,63]).  Collective cost per round drops from one psum per gradient
    evaluation (Q·(K+1) with Option C) to a single delta pmean.

    Requires replicated (non-FSDP) parameter sharding — pair with
    ``--sharding dp``.
    """
    from jax.sharding import PartitionSpec as P

    n_mb = n_microbatches or cfg.train_microbatches
    loss = make_loss(cfg, n_mb)
    if cohort_axes is not None:
        d_axes = tuple(cohort_axes)
    else:
        d_axes = ("pod", "data") if "pod" in mesh.axis_names else ("data",)

    def _local_round(server_params, stale_params, batch):
        delta, metrics = client_update(pcfg, loss, stale_params,
                                       _q_batches(pcfg, batch))
        delta = jax.tree.map(lambda d: jax.lax.pmean(d, d_axes), delta)
        metrics = jax.tree.map(lambda m: jax.lax.pmean(m, d_axes), metrics)
        new_params = jax.tree.map(
            lambda w, d: (w.astype(jnp.float32)
                          - pcfg.beta * d.astype(jnp.float32)).astype(w.dtype),
            server_params, delta)
        return new_params, metrics

    def specs_like(tree, spec):
        return jax.tree.map(lambda _: spec, tree)

    def train_step(server_params, stale_params, batch):
        batch_spec = jax.tree.map(
            lambda _: P(d_axes if len(d_axes) > 1 else d_axes[0]), batch)
        return jax.shard_map(
            _local_round,
            mesh=mesh,
            in_specs=(specs_like(server_params, P()),
                      specs_like(stale_params, P()), batch_spec),
            out_specs=(specs_like(server_params, P()),
                       {"grad_norm_mean": P(), "delta_norm": P(),
                        "nu_mean": P()}),
            # manual only over the cohort axes — the model axis stays Auto,
            # so tensor parallelism keeps working INSIDE each cohort member
            axis_names=frozenset(d_axes), check_vma=False,
        )(server_params, stale_params, batch)

    return train_step


def make_prefill_step(cfg: ArchConfig) -> Callable:
    def prefill_step(params, batch):
        return api.prefill_logits(cfg, params, batch)

    return prefill_step


def make_serve_step(cfg: ArchConfig) -> Callable:
    """One-token decode with KV/SSM cache (decode_32k / long_500k)."""
    def serve_step(params, cache, tokens, pos):
        return api.decode_step(cfg, params, cache, tokens, pos)

    return serve_step
