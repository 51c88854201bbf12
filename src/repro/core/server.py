"""Algorithm 1 — asynchronous server.

Paper-faithful mode: on receiving Δ from any client, immediately
``w ← w − β Δ`` and bump the version counter t.  Staleness bookkeeping
(Assumption 1) tracks τ = t − Ω(t) per applied update.

Beyond-paper (FedBuff [51]; unbounded-gradient analysis [63]): a buffered
variant aggregates M deltas then applies their mean once — on the TPU mesh
this is one psum over the cohort axes per round (DESIGN.md §2/§5).  The
event-driven counterpart is ``FLRun(schedule=buffered(M))``
(:mod:`repro.fl.api`), which feeds :func:`apply_buffered_rows` one stacked
bank + weight vector per flush.

Every apply routes through ``kernels/fused_update.apply_delta_tree`` — a
single read-modify-write pass per leaf with a *traced* scale, so one compile
serves every staleness value, buffer count, and the optional FedAsync-style
polynomial staleness damping β/(1+τ)^a (``PersAFLConfig.staleness_damping``).

Server state is the typed :class:`repro.core.types.ServerState` pytree
(params, t, Σ τ, max τ) — every apply takes one and returns one; the raw
dict spelling survives only as ``state["..."]`` read compatibility.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.types import PersAFLConfig, ServerState
from repro.core.quant import QuantStack
from repro.core.subset import merge_subset, subset_like
from repro.kernels.fused_update.ops import (apply_delta_tree,
                                            apply_rows_q_tree,
                                            apply_rows_tree, spans_devices)


def init_server_state(params) -> ServerState:
    return ServerState(
        params=params,
        t=jnp.zeros((), jnp.int32),
        staleness_sum=jnp.zeros((), jnp.float32),
        staleness_max=jnp.zeros((), jnp.int32),
    )


# the whole apply — fused param update AND the counter/staleness
# bookkeeping — is one jitted call: the schedulers invoke it once per
# server round, and a handful of eager scalar ops per round used to cost
# as much as the update itself.  beta/staleness/damping stay traced, so
# one compile serves the entire run.  The jits are built lazily (cached)
# so importing repro.core never initializes the JAX backend.

@functools.lru_cache(maxsize=None)
def _apply_update_jit():
    @functools.partial(jax.jit, donate_argnums=0)
    def apply(state, delta, beta, staleness, damping):
        staleness = jnp.asarray(staleness, jnp.int32)
        scale = jnp.asarray(beta, jnp.float32) \
            * (1.0 + staleness.astype(jnp.float32)) ** (-damping)
        return ServerState(
            params=apply_delta_tree(state.params, delta, scale),
            t=state.t + 1,
            staleness_sum=state.staleness_sum
            + staleness.astype(jnp.float32),
            staleness_max=jnp.maximum(state.staleness_max, staleness),
        )
    return apply


def apply_update(state: ServerState, delta, beta: float, staleness,
                 damping: float = 0.0) -> ServerState:
    """Paper-faithful single-delta apply (Algorithm 1 step 4).

    ``damping`` > 0 enables the FedAsync-style polynomial staleness
    discount s(τ) = (1+τ)^(-damping) on the server stepsize (beyond-paper;
    0 keeps the theorems' constant β).
    """
    return _apply_update_jit()(state, delta, beta, staleness,
                               jnp.float32(damping))


@functools.lru_cache(maxsize=None)
def _apply_buffered_jit():
    @functools.partial(jax.jit, donate_argnums=0)
    def apply(state, delta_sum, count, beta, staleness_max, staleness_sum):
        count = jnp.asarray(count)
        scale = beta / jnp.maximum(count.astype(jnp.float32), 1.0)
        return ServerState(
            params=apply_delta_tree(state.params, delta_sum, scale),
            t=state.t + count.astype(jnp.int32),
            staleness_sum=state.staleness_sum
            + jnp.asarray(staleness_sum, jnp.float32),
            staleness_max=jnp.maximum(state.staleness_max,
                                      jnp.asarray(staleness_max,
                                                  jnp.int32)),
        )
    return apply


def apply_buffered(state: ServerState, delta_sum, count, beta: float,
                   staleness_max, staleness_sum=0.0) -> ServerState:
    """FedBuff-style buffered apply: w ← w − β/M Σ Δ (one server round).

    ``delta_sum`` is typically the result of a psum over the cohort mesh
    axes; ``count`` the number of contributing clients M.  ``staleness_sum``
    is the Σ τ over the buffer's M contributing deltas — the version counter
    advances by M per flush, so omitting it under-reports ``mean_staleness``
    in :func:`staleness_stats` (each buffered delta is one applied update of
    Assumption 1's bookkeeping).
    """
    return _apply_buffered_jit()(state, delta_sum, count, beta,
                                 staleness_max, staleness_sum)


@functools.lru_cache(maxsize=None)
def _apply_rows_state_jit(donate: bool):
    # one body serves both stacked-apply overloads; only donation differs
    # (the serving ring must keep the pre-apply params alive as a window
    # snapshot, the simulators need not)
    @functools.partial(jax.jit, static_argnames=("mode",),
                       donate_argnums=0 if donate else ())
    def apply(state, delta_stack, weights, order, count, staleness_max,
              staleness_sum, mode: str = "auto"):
        params = state.params
        if (jax.tree_util.tree_structure(delta_stack)
                == jax.tree_util.tree_structure(params)):
            # full-model stack: the original path, bit-for-bit
            new_params = apply_rows_tree(params, delta_stack, weights,
                                         mode=mode, order=order)
        else:
            # personal_subset stack (pruned structure, core.subset): apply
            # only the subset leaves and pass the backbone through
            # untouched.  The structure comparison is a trace-time Python
            # branch — jit already caches per treedef, so no static args.
            new_sub = apply_rows_tree(subset_like(params, delta_stack),
                                      delta_stack, weights, mode=mode,
                                      order=order)
            new_params = merge_subset(params, new_sub)
        return ServerState(
            params=new_params,
            t=state.t + jnp.asarray(count, jnp.int32),
            staleness_sum=state.staleness_sum
            + jnp.asarray(staleness_sum, jnp.float32),
            staleness_max=jnp.maximum(state.staleness_max,
                                      jnp.asarray(staleness_max,
                                                  jnp.int32)),
        )
    return apply


@functools.lru_cache(maxsize=None)
def _apply_rows_q_state_jit(donate: bool):
    # quantized twin of _apply_rows_state_jit: the stack arrives as a
    # QuantStack (int8 rows + per-row-per-leaf f32 scales) and the apply
    # routes through the fused dequant×weight×accumulate kernel — an fp32
    # copy of the bank never exists, not even transiently inside the jit
    @functools.partial(jax.jit, static_argnames=("mode",),
                       donate_argnums=0 if donate else ())
    def apply(state, q_stack, weights, order, count, staleness_max,
              staleness_sum, mode: str = "auto"):
        params = state.params
        if (jax.tree_util.tree_structure(q_stack.q)
                == jax.tree_util.tree_structure(params)):
            new_params = apply_rows_q_tree(params, q_stack.q,
                                           q_stack.scales, weights,
                                           mode=mode, order=order)
        else:
            # personal_subset stack: apply the subset leaves only, pass
            # the backbone through untouched (same trace-time branch as
            # the fp32 overload)
            new_sub = apply_rows_q_tree(subset_like(params, q_stack.q),
                                        q_stack.q, q_stack.scales,
                                        weights, mode=mode, order=order)
            new_params = merge_subset(params, new_sub)
        return ServerState(
            params=new_params,
            t=state.t + jnp.asarray(count, jnp.int32),
            staleness_sum=state.staleness_sum
            + jnp.asarray(staleness_sum, jnp.float32),
            staleness_max=jnp.maximum(state.staleness_max,
                                      jnp.asarray(staleness_max,
                                                  jnp.int32)),
        )
    return apply


@functools.lru_cache(maxsize=None)
def _row_norms_jit():
    @jax.jit
    def norms(stack):
        tot = None
        for leaf in jax.tree_util.tree_leaves(stack):
            s = jnp.sum(jnp.square(leaf.astype(jnp.float32))
                        .reshape(leaf.shape[0], -1), axis=1)
            tot = s if tot is None else tot + s
        return jnp.sqrt(tot)
    return norms


@functools.lru_cache(maxsize=None)
def _row_norms_q_jit():
    @jax.jit
    def norms(q_tree, scales_tree):
        tot = None
        for q, sc in zip(jax.tree_util.tree_leaves(q_tree),
                         jax.tree_util.tree_leaves(scales_tree)):
            s = jnp.square(sc.astype(jnp.float32)) \
                * jnp.sum(jnp.square(q.astype(jnp.float32))
                          .reshape(q.shape[0], -1), axis=1)
            tot = s if tot is None else tot + s
        return jnp.sqrt(tot)
    return norms


def bank_row_norms(delta_stack) -> np.ndarray:
    """Per-row L2 norms of a stacked bank, computed ON DEVICE.

    One fused reduction over the whole stack per call; the only host
    transfer is the ``[capacity]`` f32 norm vector — never a delta row
    (the robust-admission path preserves ``host_materializations == 0``).
    QuantStacks reduce in the quantized domain (per-leaf
    ``|scale| · ‖q‖₂``, exact for the symmetric codec) without ever
    materializing an fp32 row.  Rows holding NaN/Inf report non-finite
    norms, which is how :func:`robust_admission_weights` detects poisoned
    deltas.
    """
    if isinstance(delta_stack, QuantStack):
        return np.asarray(_row_norms_q_jit()(delta_stack.q,
                                             delta_stack.scales))
    return np.asarray(_row_norms_jit()(delta_stack))


@functools.lru_cache(maxsize=None)
def _mask_rows_jit():
    @jax.jit
    def mask(stack, keep):
        def one(x):
            k = keep.reshape((-1,) + (1,) * (x.ndim - 1))
            return jnp.where(k, x, jnp.zeros((), x.dtype))
        return jax.tree_util.tree_map(one, stack)
    return mask


def mask_rows(delta_stack, keep):
    """Zero out the rows of a stacked bank where ``keep`` is False.

    Weight-zeroing alone cannot neutralize a poisoned row: the fused
    apply computes ``Σ w_j · Δ_j`` and ``0 · NaN = NaN``, so a NaN row
    survives any weight vector.  This ``where``-based mask rewrites the
    row storage itself (on device, one pass) and is applied before
    :func:`apply_admitted_rows` whenever :func:`robust_admission_weights`
    reports non-finite rows.  QuantStacks mask codes and scales alike.
    """
    keep = jnp.asarray(keep, bool)
    if isinstance(delta_stack, QuantStack):
        return QuantStack(q=_mask_rows_jit()(delta_stack.q, keep),
                          scales=_mask_rows_jit()(delta_stack.scales,
                                                  keep))
    return _mask_rows_jit()(delta_stack, keep)


@functools.lru_cache(maxsize=None)
def _scale_rows_jit():
    @jax.jit
    def scale(stack, factors):
        def one(x):
            f = factors.reshape((-1,) + (1,) * (x.ndim - 1))
            return (x.astype(jnp.float32) * f).astype(x.dtype)
        return jax.tree_util.tree_map(one, stack)
    return scale


def scale_rows(delta_stack, factors):
    """Per-row scaling of a stacked bank (one on-device pass).

    The adversarial-corruption injection point of the scenario engine
    (:mod:`repro.fl.scenario`): a ``[capacity]`` f32 factor vector (1.0
    honest, ±magnitude scaled/sign-flipped, NaN poisoned) multiplies each
    row in place of per-row host traffic.  QuantStacks scale their f32
    scale vectors only — int8 codes are untouched, so corruption
    round-trips the codec exactly like any other amplitude change.
    """
    factors = jnp.asarray(factors, jnp.float32)
    if isinstance(delta_stack, QuantStack):
        return QuantStack(q=delta_stack.q,
                          scales=_scale_rows_jit()(delta_stack.scales,
                                                   factors))
    return _scale_rows_jit()(delta_stack, factors)


def admission_weights(capacity: int, rows: List[Tuple[int, int]], *,
                      beta: float, count: int, damping: float = 0.0,
                      tau_max: Optional[int] = None) -> np.ndarray:
    """``[capacity]`` f32 row-weight vector for a stacked-bank server apply.

    ``rows`` is ``[(row_index, staleness τ), ...]``; every listed row gets
    ``β/count · (1+τ)^(-damping)`` and every other slot (bucket padding,
    unadmitted rows) gets 0.  With ``tau_max`` set, rows staler than the
    bound are zeroed — the bounded-staleness admission rule (Assumption 1's
    τ ≤ τ_max): a straggler delta is *re-weighted into a later window's
    apply* instead of corrupting it, and dropped only past the bound.
    Shared by the buffered scheduler (no bound: the simulator's event order
    can't exceed it) and the serving ring (bound enforced per window).
    """
    w = np.zeros(capacity, np.float32)
    for idx, tau in rows:
        if tau_max is not None and tau > tau_max:
            continue
        wt = beta / count
        if damping:
            wt *= (1.0 + tau) ** (-damping)
        # accumulate, don't overwrite: a row admitted twice in one window
        # (user_cap >= 2, transport re-submits) contributes twice while the
        # version counter t advances per admission — `w[idx] = wt` silently
        # under-applied the duplicate and skewed mean_staleness
        w[idx] += wt
    return w


def robust_admission_weights(
        capacity: int, rows: List[Tuple[int, int]], norms, *, beta: float,
        count: int, damping: float = 0.0, tau_max: Optional[int] = None,
        method: str = "clip", clip_norm: Optional[float] = None,
        trim_frac: float = 0.1) -> Tuple[np.ndarray, np.ndarray, Dict]:
    """Byzantine-robust variants of :func:`admission_weights`.

    ``norms`` is the ``[capacity]`` per-row L2 norm vector from
    :func:`bank_row_norms` (the only statistic the defense needs — delta
    rows never cross to the host).  Two methods:

      * ``"clip"`` — norm clipping: an admission whose row norm exceeds
        ``clip_norm`` keeps its direction but is scaled down by
        ``clip_norm / norm``; with ``clip_norm=None`` the bound is
        2 × median of the finite admitted norms (self-calibrating — an
        honest-majority buffer sets the scale, adversarially inflated
        rows can't move a median).  Base weight is β/count, like the
        plain path.
      * ``"trim"`` — norm-based trimmed mean: admissions are sorted by
        row norm and ``ceil(trim_frac · k)`` are discarded from EACH
        tail (sign-flipped or inflated rows live in the tails); the
        survivors split β evenly (β/|survivors| — ``count`` is ignored),
        so the flush stays a mean over what it kept.  At least one
        admission always survives.

    Both methods drop admissions on non-finite rows (NaN/Inf) outright.
    Staleness handling matches the plain path: rows past ``tau_max`` are
    zeroed, ``damping`` applies ``(1+τ)^-a`` per admission.

    Returns ``(weights, keep, info)``: the ``[capacity]`` f32 weight
    vector; a ``[capacity]`` bool row mask that is False on non-finite
    rows — the caller MUST route the stack through :func:`mask_rows`
    when ``keep`` isn't all-True, because ``0 · NaN = NaN`` means a
    zero weight alone cannot neutralize a poisoned row; and an ``info``
    dict (``clipped`` / ``trimmed`` / ``nonfinite`` admission counts and
    the effective ``clip_norm``) for the schedulers' stats surface.
    """
    if method not in ("clip", "trim"):
        raise ValueError(f"robust method must be 'clip' or 'trim', "
                         f"got {method!r}")
    norms = np.asarray(norms, np.float64)
    keep = np.isfinite(norms)
    admissible = [(idx, tau) for idx, tau in rows
                  if tau_max is None or tau <= tau_max]
    finite = [(idx, tau) for idx, tau in admissible if keep[idx]]
    info = {"clipped": 0, "trimmed": 0,
            "nonfinite": len(admissible) - len(finite), "clip_norm": 0.0}
    w = np.zeros(capacity, np.float32)
    if not finite:
        return w, keep, info
    a_norms = np.array([norms[idx] for idx, _ in finite])
    if method == "clip":
        c = float(clip_norm) if clip_norm is not None \
            else 2.0 * float(np.median(a_norms))
        info["clip_norm"] = c
        for (idx, tau), nrm in zip(finite, a_norms):
            wt = beta / count
            if damping:
                wt *= (1.0 + tau) ** (-damping)
            if nrm > c and nrm > 0.0:
                wt *= c / nrm
                info["clipped"] += 1
            w[idx] += wt
    else:
        k = len(finite)
        cut = int(np.ceil(trim_frac * k))
        if 2 * cut >= k:
            cut = (k - 1) // 2
        order = np.argsort(a_norms, kind="stable")
        survivors = order[cut: k - cut]
        info["trimmed"] = k - len(survivors)
        for j in survivors:
            idx, tau = finite[j]
            wt = beta / len(survivors)
            if damping:
                wt *= (1.0 + tau) ** (-damping)
            w[idx] += wt
    return w, keep, info


def robust_flush_weights(
        groups, *, beta: float, count: int, damping: float = 0.0,
        tau_max: Optional[int] = None, method: str = "clip",
        clip_norm: Optional[float] = None,
        trim_frac: float = 0.1) -> Tuple[Dict, Dict]:
    """:func:`robust_admission_weights` for ONE flush spanning several
    banks.

    The flush — not the bank — is the statistical population.  A buffered
    scheduler's M admissions (and a serving window's) split across banks:
    in-flight clients were computed in an earlier window's bank, so a
    group can hold just 1–2 rows — and a 1-row group cannot see that its
    own row is the outlier (the median of a single corrupted norm IS
    that norm, so self-calibrating clip never fires; a 2-row group
    clamps trim's cut to zero).  Calibrating per group let most
    adversarial rows through; calibrating here, over all of the flush's
    admissions, restores the honest-majority assumption the defenses
    rest on.

    ``groups`` maps a bank key to ``(bank, rows)`` where ``bank`` has
    ``.stacked`` / ``.capacity`` and ``rows`` is the ``(idx, tau)``
    admission list (the grouping both callers already build).  Clip
    computes ONE bound — ``clip_norm`` or 2 × median of the flush's
    finite admitted norms — and delegates per bank with that explicit
    bound; trim ranks the flush's admissions globally, cuts
    ``ceil(trim_frac · k)`` from each tail, and splits β over the global
    survivor set.  Per-row math (β/count base weight for clip,
    ``(1+τ)^-damping``, ``tau_max`` zeroing, non-finite drops) matches
    the per-bank function exactly.

    Returns ``({key: (weights, keep)}, info)`` — per-bank weight vectors
    and non-finite row masks under the same mask-don't-zero contract
    (route the stack through :func:`mask_rows` when ``keep`` isn't
    all-True), plus one aggregated ``info`` dict.
    """
    if method not in ("clip", "trim"):
        raise ValueError(f"robust method must be 'clip' or 'trim', "
                         f"got {method!r}")
    norms_by = {key: np.asarray(bank_row_norms(bank.stacked), np.float64)
                for key, (bank, _) in groups.items()}
    info = {"clipped": 0, "trimmed": 0, "nonfinite": 0, "clip_norm": 0.0}
    out = {}
    if method == "clip":
        admitted = np.array([norms_by[key][idx]
                             for key, (_, rows) in groups.items()
                             for idx, tau in rows
                             if tau_max is None or tau <= tau_max])
        finite = admitted[np.isfinite(admitted)]
        c = float(clip_norm) if clip_norm is not None \
            else (2.0 * float(np.median(finite)) if finite.size else 0.0)
        info["clip_norm"] = c
        for key, (bank, rows) in groups.items():
            w, keep, gi = robust_admission_weights(
                bank.capacity, rows, norms_by[key], beta=beta,
                count=count, damping=damping, tau_max=tau_max,
                method="clip", clip_norm=c)
            for stat in ("clipped", "trimmed", "nonfinite"):
                info[stat] += gi[stat]
            out[key] = (w, keep)
        return out, info
    entries = [(key, idx, tau, norms_by[key][idx])
               for key, (_, rows) in groups.items()
               for idx, tau in rows
               if tau_max is None or tau <= tau_max]
    finite_e = [e for e in entries if np.isfinite(e[3])]
    info["nonfinite"] = len(entries) - len(finite_e)
    survivors = []
    if finite_e:
        k = len(finite_e)
        cut = int(np.ceil(trim_frac * k))
        if 2 * cut >= k:
            cut = (k - 1) // 2
        order = np.argsort([e[3] for e in finite_e], kind="stable")
        survivors = [finite_e[j] for j in order[cut: k - cut]]
        info["trimmed"] = k - len(survivors)
    w_by = {key: np.zeros(bank.capacity, np.float32)
            for key, (bank, _) in groups.items()}
    for key, idx, tau, _ in survivors:
        wt = beta / len(survivors)
        if damping:
            wt *= (1.0 + tau) ** (-damping)
        w_by[key][idx] += wt
    return {key: (w_by[key], np.isfinite(norms_by[key]))
            for key in groups}, info


def _row_order(delta_stack, order) -> jnp.ndarray:
    """Resolve a flush's row-accumulation order to a traced int32 vector
    (identity when the caller has no admission order to impose)."""
    if order is None:
        if isinstance(delta_stack, QuantStack):
            delta_stack = delta_stack.q
        cap = jax.tree_util.tree_leaves(delta_stack)[0].shape[0]
        order = np.arange(cap, dtype=np.int32)
    return jnp.asarray(order, jnp.int32)


def apply_buffered_rows(state: ServerState, delta_stack, weights, count,
                        staleness_max, staleness_sum=0.0,
                        order=None) -> ServerState:
    """Stacked-buffer overload of :func:`apply_buffered`.

    ``delta_stack`` is a DeltaBank buffer — a params-shaped pytree whose
    leaves carry a leading ``[M]`` cohort axis and never left the device;
    ``weights`` the ``[M]`` f32 row-weight vector folding β/M, per-delta
    FedAsync staleness damping ``(1+τ_j)^{-a}`` and padding masks.  The
    whole flush is one fused read-modify-write pass per leaf
    (``apply_rows``) instead of M host-side ``tree.map``s; ``count`` is the
    number of *non-zero-weight* rows, which the version counter advances
    by.  Weights stay traced, so one compile per bucket size serves every
    staleness/damping composition.  The Pallas-vs-oracle dispatch is
    resolved HERE, on the concrete stack — a device-spanning buffer (the
    shard_map banks, 1-D or 2-D mesh alike) must take the sequential
    oracle path (``mode="seq"``: a mesh-invariant row-accumulation order,
    optionally the caller's ``order``), and inside the jit the leaves are
    tracers that can't reveal their sharding.
    """
    mode = "seq" if spans_devices(delta_stack) else "auto"
    return _apply_rows_state_jit(True)(state, delta_stack,
                                       jnp.asarray(weights, jnp.float32),
                                       _row_order(delta_stack, order),
                                       count, staleness_max, staleness_sum,
                                       mode=mode)


def apply_admitted_rows(state: ServerState, delta_stack, weights, count,
                        staleness_max, staleness_sum=0.0,
                        order=None) -> ServerState:
    """Serving-window overload of :func:`apply_buffered_rows`.

    Same fused stacked apply, but the incoming state is NOT donated: the
    caller (``repro.serving.bank.DeltaRing``) retains the pre-apply params
    as the closed window's snapshot, which straggler rows admitted into a
    *later* window are computed against (τ ≤ τ_max) — donating the old
    buffer (in-place on TPU) would invalidate exactly those snapshots.
    ``weights`` normally comes from :func:`admission_weights`.

    ``delta_stack`` may also be a *personal-subset* stack (the pruned
    structure of ``repro.core.subset``): only the subset leaves are
    rewritten and the shared backbone passes through bit-identically.

    With int8 delta banking the stack arrives as a
    :class:`repro.core.quant.QuantStack` and the apply dispatches to the
    fused dequant×weight×accumulate kernel (``apply_rows_q``) — straggler
    re-admission never materializes fp32 rows.

    ``order`` (from the serving ring) is the window's admission order — a
    mesh-independent total order on the rows.  On device-spanning stacks
    the apply accumulates rows sequentially in that order, so the
    post-advance params are bit-identical between the 1-D ``("cohort",)``
    and 2-D ``("cohort", "model")`` layouts even though the two meshes
    place the same users at different bank rows.
    """
    mode = "seq" if spans_devices(delta_stack) else "auto"
    ordv = _row_order(delta_stack, order)
    if isinstance(delta_stack, QuantStack):
        return _apply_rows_q_state_jit(False)(
            state, delta_stack, jnp.asarray(weights, jnp.float32),
            ordv, count, staleness_max, staleness_sum, mode=mode)
    return _apply_rows_state_jit(False)(state, delta_stack,
                                        jnp.asarray(weights, jnp.float32),
                                        ordv, count, staleness_max,
                                        staleness_sum, mode=mode)


def staleness_stats(state: ServerState) -> Dict:
    t = jnp.maximum(state.t.astype(jnp.float32), 1.0)
    return {"mean_staleness": state.staleness_sum / t,
            "max_staleness": state.staleness_max,
            "server_rounds": state.t}
