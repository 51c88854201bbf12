"""Declarative Strategy/Scheduler API: one run surface for simulation,
baselines, and serving (PR 4 tentpole).

The paper's Options A/B/C and its §5 baselines (Per-FedAvg, pFedMe, FedProx,
SCAFFOLD) are all "a local update rule plus a server apply policy".  This
module makes that factoring literal:

  * :class:`Strategy` — the local update rule.  ``init_client_state(params)``
    and ``local_update(params, batches, cstate) -> (delta, cstate, metrics)``;
    instances come from the registry (``strategy("fedprox", mu=0.1)``,
    ``strategy("persafl", option="B")``, …).  Client state is a *stacked
    pytree threaded through the cohort vmap/shard_map*, so stateful
    baselines (SCAFFOLD control variates) ride the exact same
    :class:`repro.fl.engine.CohortEngine` fast path as everyone else and
    their deltas land in the on-device DeltaBank.
  * :class:`ApplyPolicy` — the server apply schedule.  ``immediate()`` is
    Algorithm 1's paper-faithful per-arrival apply, ``buffered(M)`` the
    FedBuff-style M-deltas-per-round flush consumed straight from the bank
    through the fused ``apply_rows`` weight vector, ``sync_barrier(m)``
    FedAvg-family rounds that wait for the slowest of m sampled clients.
  * :class:`FLRun` — the one event-loop core replacing the three legacy
    simulator classes.  Strategy and schedule compose freely:
    ``FLRun(strategy="scaffold", schedule=sync_barrier(10), ...)`` is the
    old ``SyncSimulator(algo="scaffold")``;
    ``FLRun(strategy=strategy("persafl", option="C"),
    schedule=buffered(8), ...)`` is the old ``BufferedAsyncSimulator``.
    All schedules share the History / active-ratio / staleness bookkeeping
    and the typed :class:`repro.core.ServerState`.

Every new strategy automatically inherits the DeltaBank / ``apply_rows`` /
shard_map machinery — register it once and it runs under all three
schedules, the tuner's sweeps, and (stateless ones) the serving
micro-batcher.

The legacy class names (``AsyncSimulator``, ``BufferedAsyncSimulator``,
``SyncSimulator``) were removed in PR 10 after their one-release
deprecation window; :mod:`repro.fl.simulator` keeps ImportError
breadcrumbs with the exact FLRun spelling for each.
"""
from __future__ import annotations

import dataclasses
import functools
import heapq
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (PersAFLConfig, admission_weights,
                        apply_buffered_rows, apply_update, client_update,
                        init_server_state, mask_rows,
                        robust_flush_weights, scale_rows,
                        split_batches_for_option)
from repro.core.moreau import solve_prox
from repro.core.server import staleness_stats
from repro.data.federated import sample_batches
from repro.fl.algorithms import fedprox_update, scaffold_update
from repro.fl.engine import CohortEngine, DeltaBank


@dataclasses.dataclass
class History:
    """Run trace shared by every schedule: accuracy-vs-simulated-time,
    active-client ratio on a time grid (paper Figure 2a), and per-applied-
    update staleness (Assumption 1 bookkeeping; empty for sync rounds).

    ``loss`` is recorded alongside ``acc`` whenever the run's ``eval_fn``
    reports one (a ``(acc, loss)`` pair or an ``{"acc":, "loss":}`` dict —
    scalar returns stay acc-only, so pre-existing eval functions keep
    their exact behavior).  When present it is parallel to ``times`` /
    ``rounds`` / ``acc``; the :mod:`repro.tune` stop rules read it live
    through the ``on_eval`` callback.
    """
    times: List[float] = dataclasses.field(default_factory=list)
    rounds: List[int] = dataclasses.field(default_factory=list)
    acc: List[float] = dataclasses.field(default_factory=list)
    loss: List[float] = dataclasses.field(default_factory=list)
    active_times: List[float] = dataclasses.field(default_factory=list)
    active_ratio: List[float] = dataclasses.field(default_factory=list)
    staleness: List[int] = dataclasses.field(default_factory=list)
    # simulated time at which the run actually stopped — NOT the
    # 5s-grid-quantized last active_times entry; equal-simulated-time
    # comparisons must budget on this.  When a max_time budget binds the
    # event loop, end_time is clamped to exactly max_time (the first event
    # past the budget never runs and never advances the clock)
    end_time: float = 0.0

    def as_dict(self) -> Dict:
        return dataclasses.asdict(self)


def _normalize_eval(result) -> Tuple[float, Optional[float]]:
    """Normalize an ``eval_fn`` return to ``(acc, loss-or-None)``.

    Accepted spellings: a bare scalar (accuracy only — the historical
    contract), a 2-sequence ``(acc, loss)``, or a dict with ``"acc"`` and
    an optional ``"loss"``.
    """
    if isinstance(result, dict):
        loss = result.get("loss")
        return float(result["acc"]), None if loss is None else float(loss)
    if isinstance(result, (tuple, list)):
        if len(result) != 2:
            raise ValueError(f"eval_fn returned a {len(result)}-sequence; "
                             f"expected (acc, loss)")
        return float(result[0]), float(result[1])
    return float(result), None


def _own_copy(params):
    """Private copy of the caller's params: server applies donate the old
    buffer (in-place on TPU), which must never invalidate caller arrays."""
    return jax.tree.map(lambda x: jnp.array(x), params)


# ---------------------------------------------------------------------------
# Strategy protocol + registry
# ---------------------------------------------------------------------------

class Strategy:
    """A local update rule with a client-state lifecycle.

    Protocol (all jit-traceable in ``local_update``):

      * ``init_client_state(params)`` — the per-client state carried across
        rounds (None for stateless rules; SCAFFOLD returns its control
        variate c_i).
      * ``local_update(params, batches, cstate) -> (delta, cstate, metrics)``
        — one client's contribution against a frozen params snapshot.  The
        delta is params-shaped f32 (bank-row compatible); metrics are
        dead-code-eliminated on the cohort path.
      * ``dispatch_state(cstate)`` — host-side hook run right before a
        cohort dispatch (per-client pre-processing; identity by default).
      * ``shared_state()`` / ``assemble_state(cstate, shared)`` — the
        strategy-shared server-side input.  ``shared_state()`` is read once
        per cohort call and passed *replicated* (vmap in-axis None /
        shard_map ``P()``), and ``assemble_state`` recombines it with each
        client's row inside the traced cohort body — SCAFFOLD ships ONE
        c_global per call instead of one copy per cohort row.
      * ``post_round(updates, n_clients)`` — host-side hook run after the
        cohort's states are written back; ``updates`` is
        ``[(client_index, old_cstate, new_cstate), ...]`` in dispatch
        order (SCAFFOLD folds Δc into c_global here).

    Instances are single-run objects: :meth:`bind` attaches the run's
    (pcfg, loss_fn) and resets any strategy-shared state.

    ``personal_subset`` declares the *partial-model personalization* split
    (arXiv 2309.17409): any :class:`repro.core.SubsetSpec` spelling — path
    prefixes like ``("fc/#1",)`` or a pytree bool mask — naming the
    personal leaves.  A strategy that honors it returns deltas in the
    pruned subset structure (``SubsetSpec.extract``), so bank rows, ring
    snapshots and wire frames shrink to the subset while the shared
    backbone flows untouched; None (the default) keeps full-model deltas.
    """

    name = "strategy"
    option = "A"        # batch-split layout, for introspection
    stateful = False
    personal_subset = None   # SubsetSpec spelling, or None = full model

    def bind(self, pcfg: PersAFLConfig, loss_fn: Callable) -> "Strategy":
        self.pcfg = pcfg
        self.loss_fn = loss_fn
        return self

    def init_client_state(self, params):
        return None

    def dispatch_state(self, cstate):
        return cstate

    def shared_state(self):
        """Strategy-shared cohort input, replicated (not stacked) across
        the cohort axis; None for strategies without one."""
        return None

    def assemble_state(self, cstate, shared):
        """Recombine one client's state row with the shared input inside
        the traced cohort body (identity for shared-less strategies)."""
        return cstate

    def local_update(self, params, batches, cstate):
        raise NotImplementedError

    def post_round(self, updates: List[Tuple[int, object, object]],
                   n_clients: int) -> None:
        pass


_REGISTRY: Dict[str, Callable[..., Strategy]] = {}


def register_strategy(*names):
    """Class decorator: ``@register_strategy("fedprox")`` makes the rule
    constructible as ``strategy("fedprox", **kw)`` everywhere — FLRun, the
    tuner's sweeps, and the serving micro-batcher."""
    def deco(factory):
        for nm in names:
            _REGISTRY[nm] = factory
        return factory
    return deco


def strategy(name: str, **kw) -> Strategy:
    """Construct a registered strategy: ``strategy("persafl", option="B")``,
    ``strategy("fedprox", mu=0.1)``, ``strategy("scaffold")``, …"""
    try:
        factory = _REGISTRY[name]
    except KeyError:
        raise ValueError(f"unknown strategy {name!r}; "
                         f"have {sorted(_REGISTRY)}") from None
    return factory(**kw)


def strategy_names() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def resolve_strategy(s) -> Strategy:
    if isinstance(s, str):
        return strategy(s)
    if isinstance(s, Strategy):
        return s
    raise TypeError(f"strategy must be a name or a Strategy, got {type(s)}")


@register_strategy("persafl")
class PersAFLStrategy(Strategy):
    """Algorithm 2, Options A/B/C (this paper): Q local steps of plain SGD
    (A), MAML (B, Per-FedAvg's rule) or Moreau-envelope prox grads (C,
    pFedMe's rule).  ``option=None`` takes the bound pcfg's option."""

    name = "persafl"

    def __init__(self, option: Optional[str] = None):
        self._option = option

    def bind(self, pcfg, loss_fn):
        self.option = self._option or pcfg.option
        return super().bind(dataclasses.replace(pcfg, option=self.option),
                            loss_fn)

    def local_update(self, params, batches_3q, cstate):
        delta, metrics = client_update(
            self.pcfg, self.loss_fn, params,
            split_batches_for_option(self.option, batches_3q))
        return delta, None, metrics


# the §5 baseline names are option presets of the same rule
for _nm, _opt in (("fedavg", "A"), ("fedasync", "A"),
                  ("perfedavg", "B"), ("pfedme", "C")):
    _REGISTRY[_nm] = functools.partial(PersAFLStrategy, option=_opt)


@register_strategy("fedprox")
class FedProxStrategy(Strategy):
    """FedProx [42]: local SGD on f_i(w) + μ/2 ‖w − w^t‖² (Option A
    batches).  Stateless; formerly exiled to a sequential per-client jit
    loop in SyncSimulator, now a plain cohort citizen."""

    name = "fedprox"

    def __init__(self, mu: float = 0.1):
        self.mu = mu

    def bind(self, pcfg, loss_fn):
        return super().bind(dataclasses.replace(pcfg, option="A"), loss_fn)

    def local_update(self, params, batches_3q, cstate):
        q = self.pcfg.q_local
        delta, metrics = fedprox_update(
            self.pcfg, self.loss_fn, params,
            jax.tree.map(lambda x: x[:q], batches_3q), mu=self.mu)
        return delta, None, metrics


@register_strategy("scaffold")
class ScaffoldStrategy(Strategy):
    """SCAFFOLD [34] (Option I): the first *stateful* registry strategy.

    Per-client state is the control variate c_i (params-shaped f32,
    stacked over the cohort axis); the shared c_global is injected into
    every dispatch via :meth:`dispatch_state` and updated host-side in
    :meth:`post_round` — c_global += (c_i⁺ − c_i)/n per participating
    client, in dispatch order (the legacy sequential path's exact fold).
    """

    name = "scaffold"
    stateful = True

    def bind(self, pcfg, loss_fn):
        self.c_global = None
        return super().bind(dataclasses.replace(pcfg, option="A"), loss_fn)

    def init_client_state(self, params):
        zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                             params)
        if self.c_global is None:
            self.c_global = zeros
        return zeros

    def shared_state(self):
        return self.c_global

    def assemble_state(self, cstate, shared):
        return {"c": shared, "c_i": cstate}

    def local_update(self, params, batches_3q, dstate):
        q = self.pcfg.q_local
        delta, c_new, metrics = scaffold_update(
            self.pcfg, self.loss_fn, params,
            jax.tree.map(lambda x: x[:q], batches_3q),
            dstate["c"], dstate["c_i"])
        return delta, c_new, metrics

    def post_round(self, updates, n_clients):
        for _, c_old, c_new in updates:
            self.c_global = jax.tree.map(
                lambda cg, cn, co: cg + (cn - co) / n_clients,
                self.c_global, c_new, c_old)


@register_strategy("personalize")
class PersonalizeStrategy(Strategy):
    """Serving-side personalization delta (head = w − delta).

    mode "B": delta = α ∇f(w; D)   (head = the one-step MAML fine-tune)
    mode "C": delta = w − θ̃(w)     (head = the Moreau prox solution θ̃)

    ``batches`` here is the user's raw request batch (no leading-Q axis).
    Deltas accumulate in f32 like training deltas, so bank rows are
    directly consumable by the fused ``apply_rows`` server pass — this is
    the strategy behind :class:`repro.serving.PersonalizationServer`,
    replacing the old ``CohortEngine(client_fn=...)`` override.

    With ``personal_subset`` set, only the subset is personalized: the
    gradient / prox solve runs over the subset leaves with the backbone
    *frozen* at the global params (partial-model personalization, arXiv
    2309.17409), and the delta comes back in the pruned subset structure —
    a bank row shrinks from full-model to head-only bytes.
    """

    name = "personalize"

    def __init__(self, mode: str = "C", personal_subset=None):
        if mode not in ("B", "C"):
            raise ValueError(f"unknown personalization mode {mode!r}; "
                             f"have ('B', 'C')")
        self.mode = mode
        self.option = mode
        from repro.core.subset import SubsetSpec
        self.personal_subset = SubsetSpec.resolve(personal_subset)

    def local_update(self, params, batch, cstate):
        from repro.core.subset import merge_subset
        spec = self.personal_subset
        if spec is None:
            sub0, loss_fn = params, self.loss_fn
        else:
            # personalize the subset against a frozen backbone: grad/prox
            # run over the pruned subset tree, the closure re-merges it
            # into the full params for the loss
            sub0 = spec.extract(params)
            loss_fn = lambda s, b: self.loss_fn(merge_subset(params, s), b)
        if self.mode == "B":
            g = jax.grad(loss_fn)(sub0, batch)
            delta = jax.tree.map(
                lambda gg: self.pcfg.alpha * gg.astype(jnp.float32), g)
        else:
            theta, _ = solve_prox(loss_fn, sub0, batch,
                                  self.pcfg.lam, self.pcfg.inner_eta,
                                  self.pcfg.inner_steps)
            delta = jax.tree.map(
                lambda w, t: w.astype(jnp.float32) - t.astype(jnp.float32),
                sub0, theta)
        return delta, None, {}


# ---------------------------------------------------------------------------
# Apply policies (server schedules)
# ---------------------------------------------------------------------------

class ApplyPolicy:
    """Server apply schedule.  ``kind="event"`` policies plug into the
    async discrete-event loop via :meth:`on_upload`; ``kind="round"``
    policies drive barrier rounds.  Instances hold per-run state — create
    one per FLRun."""

    kind = "event"
    default_eval_every = 50

    def start(self, run: "FLRun") -> None:
        """Reset per-run policy state (called at the top of ``run()``)."""

    def on_upload(self, run: "FLRun", now: float, rid: int, version: int,
                  hist: History, eval_fn, eval_every: int) -> None:
        raise NotImplementedError


class Immediate(ApplyPolicy):
    """Paper-faithful Algorithm 1: apply each delta the moment it lands
    (staleness τ measured per update)."""

    def on_upload(self, run, now, rid, version, hist, eval_fn, eval_every):
        run._flush()
        bank, idx = run._computed.pop(rid)
        # per-row host materialization keeps exact single-delta semantics
        # (one transfer of the whole bank, numpy views per row after that)
        delta = bank.row(idx)
        # _t mirrors state.t host-side: reading the device scalar every
        # event would force a sync per event — O(n) stalls per window
        staleness = run._t - version
        hist.staleness.append(staleness)
        run.state = apply_update(run.state, delta, run.pcfg.beta, staleness,
                                 damping=run.pcfg.staleness_damping)
        run._record_window(now, 1, [staleness])
        run._t += 1
        if eval_fn is not None and run._t % eval_every == 0:
            run._record_eval(hist, now, eval_fn, run._t)


class Buffered(ApplyPolicy):
    """FedBuff-style buffered apply (beyond-paper [51,63]): arrivals
    accumulate in a size-M buffer; a full buffer flushes as ONE
    w ← w − β/M ΣΔ server round consumed straight from the on-device
    DeltaBank through the fused ``apply_rows`` weight vector (β/M,
    per-delta FedAsync damping ``(1+τ)^{-a}`` and padding masks are rows
    of one ``[bucket]`` array) — flushes never move per-client deltas to
    the host.  t advances in M-sized jumps; staleness Σ/max are accounted
    per contributing delta.

    ``robust="clip"`` / ``"trim"`` routes the flush through
    :func:`repro.core.robust_flush_weights` instead — per-row norm
    clipping or a norm-based trimmed mean calibrated over the WHOLE
    buffer, across the banks its rows live in (row norms reduced on
    device via :func:`repro.core.bank_row_norms`; non-finite rows zeroed
    through :func:`repro.core.mask_rows`), the defense
    against the scenario engine's adversarial clients
    (:class:`repro.fl.scenario.ChurnModel`).  Under trim, t still
    advances by the full buffer size — trimmed admissions contribute a
    zero weight, exactly like tau_max-dropped rows on the plain path."""

    def __init__(self, m: Optional[int] = None, *,
                 robust: Optional[str] = None,
                 clip_norm: Optional[float] = None,
                 trim_frac: float = 0.1):
        if robust not in (None, "clip", "trim"):
            raise ValueError(f"robust must be None, 'clip' or 'trim', "
                             f"got {robust!r}")
        self.m = m                # configured; None = the run's pcfg M
        self.robust = robust
        self.clip_norm = clip_norm
        self.trim_frac = trim_frac

    def start(self, run):
        # resolved per run — m=None must re-read each run's buffer_size
        self.m_effective = self.m if self.m is not None \
            else max(int(run.pcfg.buffer_size), 1)
        self._buffer: List[Tuple[int, int]] = []  # (rid, staleness)

    def on_upload(self, run, now, rid, version, hist, eval_fn, eval_every):
        staleness = run._t - version
        hist.staleness.append(staleness)
        self._buffer.append((rid, staleness))
        if len(self._buffer) < self.m_effective:
            return
        run._flush()  # compute buffered AND in-flight pending deltas
        m = len(self._buffer)
        damping = run.pcfg.staleness_damping
        # group the buffer's rows by owning DeltaBank (in-flight clients
        # were computed in an earlier window's bank) and consume each bank
        # on device: β/M and the per-delta FedAsync discount (1+τ)^{-a} —
        # which must act BEFORE the sum, a post-sum scale could not tell
        # fresh deltas from stale ones — are rows of ONE weight vector,
        # and the whole flush is one fused apply_rows pass per bank
        # instead of M host-side tree.maps.
        groups: Dict[int, Tuple[DeltaBank, List[Tuple[int, int]]]] = {}
        for r, s in self._buffer:
            bank, idx = run._computed.pop(r)
            groups.setdefault(id(bank), (bank, []))[1].append((idx, s))
        t_old = run._t
        robust_info = {"clipped": 0, "trimmed": 0, "nonfinite": 0}
        if self.robust is not None:
            # one call for the whole flush: the defense calibrates over
            # ALL m admissions, not per owning bank — a corrupted row
            # alone in its 1-row group would set its own clip median
            per_bank, robust_info = robust_flush_weights(
                groups, beta=run.pcfg.beta, count=m, damping=damping,
                method=self.robust, clip_norm=self.clip_norm,
                trim_frac=self.trim_frac)
        for key, (bank, rows) in groups.items():
            if self.robust is not None:
                weights, keep = per_bank[key]
                # non-finite rows are masked out of the stack, not just
                # zero-weighted: 0 × NaN = NaN
                stack = bank.stacked if bool(keep.all()) \
                    else mask_rows(bank.stacked, keep)
            else:
                weights = admission_weights(bank.capacity, rows,
                                            beta=run.pcfg.beta, count=m,
                                            damping=damping)
                stack = bank.stacked
            run.state = apply_buffered_rows(
                run.state, stack, weights, len(rows),
                staleness_max=max(s for _, s in rows),
                staleness_sum=float(sum(s for _, s in rows)))
        run._record_window(now, m, [s for _, s in self._buffer],
                           robust_clipped=robust_info["clipped"],
                           robust_trimmed=robust_info["trimmed"],
                           robust_nonfinite=robust_info["nonfinite"])
        self._buffer = []
        run._t = t_old + m
        # t jumps by M per flush: eval whenever a multiple of eval_every
        # is crossed (the immediate-apply modulo test would skip most)
        if eval_fn is not None \
                and run._t // eval_every > t_old // eval_every:
            run._record_eval(hist, now, eval_fn, run._t)


class SyncBarrier(ApplyPolicy):
    """FedAvg-family synchronous rounds: sample m clients, wait for the
    slowest, fold the cohort's bank into the params with one fused
    ``apply_rows`` pass (weights = β/m on real rows, 0 on padding)."""

    kind = "round"
    default_eval_every = 5

    def __init__(self, m: int = 10):
        self.m = m


def immediate() -> Immediate:
    return Immediate()


def buffered(m: Optional[int] = None, *, robust: Optional[str] = None,
             clip_norm: Optional[float] = None,
             trim_frac: float = 0.1) -> Buffered:
    """``m=None`` takes ``pcfg.buffer_size`` at run time.  ``robust=``
    selects the Byzantine-robust flush ("clip" / "trim"; see
    :class:`Buffered`)."""
    return Buffered(m, robust=robust, clip_norm=clip_norm,
                    trim_frac=trim_frac)


def sync_barrier(m: int = 10) -> SyncBarrier:
    return SyncBarrier(m)


_SCHEDULES: Dict[str, Callable[[], ApplyPolicy]] = {
    "immediate": immediate, "buffered": buffered,
    "sync": sync_barrier, "sync_barrier": sync_barrier,
}


def resolve_schedule(s) -> ApplyPolicy:
    if isinstance(s, str):
        try:
            return _SCHEDULES[s]()
        except KeyError:
            raise ValueError(f"unknown schedule {s!r}; "
                             f"have {sorted(_SCHEDULES)}") from None
    if isinstance(s, ApplyPolicy):
        return s
    raise TypeError(f"schedule must be a name or an ApplyPolicy, "
                    f"got {type(s)}")


# ---------------------------------------------------------------------------
# FLRun — the one event-loop core
# ---------------------------------------------------------------------------

class FLRun:
    """One federated run = a Strategy × an ApplyPolicy × a DelayModel.

    Replaces AsyncSimulator / BufferedAsyncSimulator / SyncSimulator with a
    single core sharing the engine dispatch, the typed
    :class:`repro.core.ServerState`, and the History / active-ratio /
    staleness bookkeeping.  Per-client compute is *deferred* exactly as
    before: batches are recorded when a download completes and materialized
    lazily — in one :class:`CohortEngine` cohort call, client state stacked
    alongside — right before the next server apply, so every delta is
    computed on the snapshot the per-event path would have used.

    ``vectorized=False`` keeps the per-event sequential dispatch (the
    baseline the ``engine`` benchmark row measures against).
    """

    def __init__(self, *, clients: List, loss_fn: Callable, init_params,
                 pcfg: PersAFLConfig, delays,
                 strategy="persafl", schedule="immediate",
                 batch_size: int = 32, seed: int = 0,
                 vectorized: bool = True, cohort_impl: str = "auto",
                 scheduler: str = "auto", mesh=None, param_shardings=None):
        if scheduler not in ("auto", "heap", "device"):
            raise ValueError(f"scheduler must be 'auto', 'heap' or "
                             f"'device', got {scheduler!r}")
        self.clients = clients
        self.pcfg = pcfg
        self.delays = delays
        self.batch_size = batch_size
        self.rng = np.random.RandomState(seed)
        self.loss_fn = loss_fn
        self.strategy = resolve_strategy(strategy).bind(pcfg, loss_fn)
        self.schedule = resolve_schedule(schedule)
        self.scheduler = scheduler
        self.state = init_server_state(_own_copy(init_params))
        # mesh / param_shardings thread straight to the engine: on a 2-D
        # ("cohort", "model") mesh the run's banks come back sharded on
        # both axes (see repro.sharding.ctx.cohort_model_mesh)
        self.engine = CohortEngine(self.strategy.pcfg, loss_fn,
                                   vectorized=vectorized,
                                   cohort_impl=cohort_impl,
                                   strategy=self.strategy, mesh=mesh,
                                   param_shardings=param_shardings)
        self._cstates: List = [None] * len(clients)
        self._on_eval: Optional[Callable] = None
        self._stop = False
        self.final_stats: Optional[Dict] = None
        # per-window scheduler observability (see _record_window)
        self.scheduler_stats: Dict = {
            "scheduler": scheduler, "windows": 0, "cohort_fill_sum": 0,
            "cohort_fill_max": 0, "dropouts": 0, "corrupted_rows": 0,
            "robust_clipped": 0, "robust_trimmed": 0,
            "robust_nonfinite": 0}
        self.window_log: List[Dict] = []
        self._window_log_cap = 1024

    # -- shared plumbing ---------------------------------------------------

    @property
    def params(self):
        """The current global model w."""
        return self.state.params

    def _sample(self, i: int):
        return sample_batches(self.clients[i], self.rng,
                              3 * self.pcfg.q_local, self.batch_size)

    def _cstate_for_dispatch(self, i: int):
        if not self.strategy.stateful:
            return None
        if self._cstates[i] is None:
            self._cstates[i] = self.strategy.init_client_state(
                self.state.params)
        return self.strategy.dispatch_state(self._cstates[i])

    def _write_back(self, client_ids: List[int], bank: DeltaBank) -> None:
        """Store the cohort's updated client states and run the strategy's
        shared-state fold (SCAFFOLD's c_global)."""
        if not self.strategy.stateful:
            return
        updates = []
        for row, i in enumerate(client_ids):
            new = bank.client_state(row)
            updates.append((i, self._cstates[i], new))
            self._cstates[i] = new
        self.strategy.post_round(updates, len(self.clients))

    @property
    def stats(self) -> Dict:
        """Engine + per-window scheduler counters, one machine-readable
        dict (churn sweeps consume this; ``window_log`` holds the
        per-window records)."""
        s = dict(self.engine.stats)
        s.update(self.scheduler_stats)
        s["mean_cohort_fill"] = (
            self.scheduler_stats["cohort_fill_sum"]
            / max(self.scheduler_stats["windows"], 1))
        return s

    def _record_window(self, now: float, fill: int, taus: List[int],
                       **extra: int) -> None:
        """Per-server-apply scheduler bookkeeping: cohort fill, staleness
        spread, robust-admission actions.  Aggregates accumulate in
        ``scheduler_stats``; the first ``_window_log_cap`` windows also
        get a per-window record in ``window_log``."""
        st = self.scheduler_stats
        st["windows"] += 1
        st["cohort_fill_sum"] += fill
        st["cohort_fill_max"] = max(st["cohort_fill_max"], fill)
        for key, val in extra.items():
            st[key] = st.get(key, 0) + val
        if len(self.window_log) < self._window_log_cap:
            self.window_log.append({
                "window": st["windows"], "time": float(now),
                "fill": int(fill),
                "tau_mean": float(np.mean(taus)) if taus else 0.0,
                "tau_max": int(max(taus)) if taus else 0,
                "dropouts": st["dropouts"],
                "corrupted_rows": st["corrupted_rows"], **extra})

    def _flush(self) -> None:
        """Materialize every pending client update in one cohort call.

        Called right before any server apply: params have not changed since
        these clients' downloads completed, so the whole cohort shares one
        snapshot and the cohort call is exact.  Deltas are recorded as
        (DeltaBank, row) handles — the stacked buffer stays on device and a
        bank outlives its window for clients whose upload lands after the
        next apply.

        Adversarial clients (a ChurnModel with an adversarial population)
        corrupt their rows HERE, right after the cohort computes them —
        one on-device ``scale_rows`` pass over the bank, exactly where a
        malicious client would hand the server a doctored delta."""
        if not self._pending:
            return
        stateful = self.strategy.stateful
        bank = self.engine.update_cohort(
            self.state.params, [b for _, _, b, _ in self._pending],
            cstate_list=[c for _, _, _, c in self._pending]
            if stateful else None)
        ids = [i for _, i, _, _ in self._pending]
        factors = self.delays.corruption_factors(np.asarray(ids)) \
            if hasattr(self.delays, "corruption_factors") else None
        if factors is not None and bool(np.any(factors != 1.0)):
            vec = np.ones(bank.capacity, np.float32)
            vec[:len(ids)] = factors
            if bank._stacked is not None or bank._rows is None:
                bank._stacked = scale_rows(bank.stacked, vec)
            else:
                # per-event (vectorized=False) banks hold per-row trees
                bank._rows = [
                    jax.tree.map(lambda x: x * jnp.float32(f), r)
                    for r, f in zip(bank._rows, vec[:len(bank._rows)])]
            self.scheduler_stats["corrupted_rows"] += \
                int(np.sum(factors != 1.0))
        for idx, (rid, _, _, _) in enumerate(self._pending):
            self._computed[rid] = (bank, idx)
        if stateful:
            self._write_back(ids, bank)
        self._pending = []

    def _on_upload(self, now: float, rid: int, version: int, hist: History,
                   eval_fn, eval_every: int) -> None:
        self.schedule.on_upload(self, now, rid, version, hist, eval_fn,
                                eval_every)

    def _record_eval(self, hist: History, now: float, eval_fn,
                     t: int, notify: bool = True) -> None:
        """Run one evaluation and append it to the History (acc, and loss
        when the eval_fn reports one — see :func:`_normalize_eval`).

        With ``notify=True`` the run's ``on_eval`` callback (if any) sees
        the updated History; a ``"stop"`` return raises the stop flag the
        event/round loops check after every server apply — the clean
        mid-run abort path the :mod:`repro.tune` runner halts arms with.
        """
        acc, loss = _normalize_eval(eval_fn(self.state.params))
        hist.times.append(now)
        hist.rounds.append(int(t))
        hist.acc.append(acc)
        if loss is not None:
            hist.loss.append(loss)
        if notify and self._on_eval is not None \
                and self._on_eval(hist) == "stop":
            self._stop = True

    # -- the run surface ---------------------------------------------------

    def run(self, *, max_rounds: Optional[int] = None,
            max_server_rounds: Optional[int] = None,
            eval_every: Optional[int] = None,
            eval_fn: Optional[Callable] = None,
            record_active_every: float = 5.0,
            max_time: Optional[float] = None,
            on_eval: Optional[Callable[[History], Optional[str]]] = None,
            final_eval: bool = False) -> History:
        """Drive the run to ``max_rounds`` server rounds (or ``max_time``
        simulated seconds, whichever first).  ``max_server_rounds`` is an
        accepted alias.  Returns the :class:`History`.

        ``on_eval(hist)`` is called after every recorded evaluation with
        the live History; returning ``"stop"`` halts the event loop
        cleanly after the current server apply (History stays well-formed:
        the active-ratio grid is closed out and ``end_time`` is the true
        stop time).  This is the abort path self-stopping sweeps
        (:mod:`repro.tune`) kill diverging or plateaued arms through.

        ``final_eval=True`` forces one evaluation at the actual stop time
        if the last recorded one is stale (or none was recorded at all) —
        "final accuracy" reads (``hist.acc[-1]``) are then never a stale
        grid point, even when ``eval_every`` exceeds the round count or a
        ``max_time`` budget bites between grid points.
        """
        if max_rounds is None:
            max_rounds = max_server_rounds
        if max_rounds is None:
            raise TypeError("run() needs max_rounds=")
        if eval_every is None:
            eval_every = self.schedule.default_eval_every
        self._on_eval = on_eval
        self._stop = False
        self.schedule.start(self)
        if self.schedule.kind == "round":
            hist = self._run_rounds(max_rounds, eval_every, eval_fn,
                                    record_active_every, max_time)
        else:
            hist = self._run_events(max_rounds, eval_every, eval_fn,
                                    record_active_every, max_time)
        if final_eval and eval_fn is not None:
            t_now = int(np.asarray(self.state.t))
            # params only move with the round counter: a last eval at the
            # current t already IS the end-time accuracy, re-running it
            # would burn an eval to recompute an identical value
            if not hist.rounds or hist.rounds[-1] != t_now:
                # the forced final eval never re-enters on_eval: the run
                # is already over, a "stop" could not mean anything
                self._record_eval(hist, hist.end_time, eval_fn, t_now,
                                  notify=False)
        self.final_stats = jax.tree.map(np.asarray,
                                        staleness_stats(self.state))
        return hist

    # -- event-driven core (immediate / buffered schedules) ----------------

    def _heap_events(self):
        """Per-event heap scheduler as an infinite event generator.

        Yields ``(t, client, kind, dropped, t_up)`` with kind 0 = download
        complete, 1 = upload complete.  Heap keys are ``(t, client, kind)``
        — the documented deterministic total order on events (download
        sorts before upload at equal time for the same client), identical
        to the ``np.lexsort`` order :class:`repro.fl.scenario.EventStream`
        emits, which is what makes the two sources bit-equal.  The old
        insertion-``seq`` tie-break depended on *push* order, which no
        vectorized scheduler can reproduce.

        A dropped download (ChurnModel mid-round dropout: the client
        vanishes after its download completes, before uploading) yields
        with ``dropped=True`` and schedules the client's next download at
        the time its upload *would* have finished — realized timelines are
        drop-independent, so heap and device paths stay aligned.
        """
        heap: List[Tuple[float, int, int]] = []
        for i in range(len(self.clients)):
            heapq.heappush(heap,
                           (self.delays.sample_download(i, 0.0), i, 0))
        while True:
            now, i, kind = heapq.heappop(heap)
            if kind == 0:
                dropped = self.delays.drops(i)
                t_up = now + self.delays.sample_upload(i, now)
                if dropped:
                    heapq.heappush(
                        heap,
                        (t_up + self.delays.sample_download(i, t_up), i, 0))
                else:
                    heapq.heappush(heap, (t_up, i, 1))
                yield now, i, 0, dropped, t_up
            else:
                heapq.heappush(
                    heap,
                    (now + self.delays.sample_download(i, now), i, 0))
                yield now, i, 1, False, now

    def _run_events(self, max_rounds, eval_every, eval_fn,
                    record_active_every, max_time) -> History:
        from repro.fl.scenario.sched import EventStream
        hist = History()
        n = len(self.clients)
        mode = self.scheduler
        if mode == "auto":
            # the Python heap wins at small n (no chunk overhead); past a
            # few thousand clients the vectorized stream takes over
            mode = "device" if n >= 4096 else "heap"
        self.scheduler_stats["scheduler"] = mode
        events = EventStream(self.delays).events() if mode == "device" \
            else self._heap_events()
        now = 0.0
        next_active_t = 0.0
        busy_up = {i: None for i in range(n)}  # upload finish times
        inflight: Dict[int, Tuple[int, int]] = {}  # client -> (rid, version)
        # (rid, client, batches, dispatch-ready cstate or None)
        self._pending: List[Tuple[int, int, Dict, object]] = []
        self._computed: Dict[int, Tuple] = {}   # rid -> (DeltaBank, row)
        self._t = int(self.state.t)             # host-side round mirror
        next_rid = 0

        for now_e, i, kind, dropped, t_up in events:
            if self._t >= max_rounds:
                break
            if max_time is not None and now_e > max_time:
                # this event lies PAST the budget: it must not run, and
                # the clock stops AT the budget — end_time must never
                # overshoot max_time or equal-simulated-time comparisons
                # (experiments/sweeps/buffered_vs_immediate.py) would hand
                # the overshooting run extra simulated seconds
                now = max_time
                break
            now = now_e
            # record active ratio on a time grid: active = comp./uploading
            while next_active_t <= now:
                up_now = sum(1 for v in busy_up.values()
                             if v is not None and v > next_active_t)
                hist.active_times.append(next_active_t)
                hist.active_ratio.append(up_now / n)
                next_active_t += record_active_every
            if kind == 0:                       # download complete
                if dropped:
                    # mid-round dropout: the client vanished before its
                    # upload — no dispatch, no bank row, just a counter
                    self.scheduler_stats["dropouts"] += 1
                    continue
                rid = next_rid
                next_rid += 1
                self._pending.append((rid, i, self._sample(i),
                                      self._cstate_for_dispatch(i)))
                busy_up[i] = t_up
                inflight[i] = (rid, self._t)
            else:                               # upload complete
                rid, version = inflight.pop(i)
                self._on_upload(now, rid, version, hist, eval_fn,
                                eval_every)
                busy_up[i] = None
                if self._stop:
                    # on_eval requested a stop: halt cleanly after this
                    # apply — the grid closeout below and end_time keep
                    # the History well-formed
                    break
        # close out the active-ratio grid to the actual stop time: on a
        # max_time break the in-loop recording stopped at the last
        # *executed* event, leaving the grid short of the boundary
        while next_active_t <= now:
            up_now = sum(1 for v in busy_up.values()
                         if v is not None and v > next_active_t)
            hist.active_times.append(next_active_t)
            hist.active_ratio.append(up_now / n)
            next_active_t += record_active_every
        hist.end_time = now
        return hist

    # -- barrier-round core (sync_barrier schedule) ------------------------

    def _run_rounds(self, max_rounds, eval_every, eval_fn,
                    record_active_every, max_time) -> History:
        hist = History()
        n = len(self.clients)
        m = self.schedule.m
        now = 0.0
        next_active_t = 0.0
        for rnd in range(max_rounds):
            sel = self.rng.choice(n, m, replace=False)
            batches = [self._sample(int(i)) for i in sel]
            cstates = [self._cstate_for_dispatch(int(i)) for i in sel] \
                if self.strategy.stateful else None
            # the m sampled clients share the round's params by definition:
            # one cohort call, deltas land in the bank, client state rides
            # the stacked pytree
            bank = self.engine.update_cohort(self.state.params, batches,
                                             cstate_list=cstates)
            finish = [self.delays.sample_download(int(i), now)
                      + self.delays.sample_upload(int(i), now) for i in sel]
            round_len = max(finish)
            # active-ratio grid: client i is busy until its own finish time
            while next_active_t <= now + round_len:
                rel = next_active_t - now
                busy = sum(1 for f in finish if f > rel)
                hist.active_times.append(next_active_t)
                hist.active_ratio.append(busy / n)
                next_active_t += record_active_every
            now += round_len
            # the mean AND the β-scaled apply fuse into one apply_rows pass
            # (weights = β/m on real rows, 0 on bucket padding); one server
            # round per barrier, staleness 0 by construction
            weights = np.zeros(bank.capacity, np.float32)
            weights[:len(batches)] = self.pcfg.beta / len(batches)
            self.state = apply_buffered_rows(self.state, bank.stacked,
                                             weights, 1, staleness_max=0,
                                             staleness_sum=0.0)
            self._write_back([int(i) for i in sel], bank)
            if eval_fn is not None and (rnd + 1) % eval_every == 0:
                self._record_eval(hist, now, eval_fn, rnd + 1)
            if self._stop or (max_time is not None and now >= max_time):
                break
        hist.end_time = now
        return hist
