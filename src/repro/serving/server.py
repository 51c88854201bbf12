"""PersonalizationServer — submit/poll front-end over the cohort engine.

Request lifecycle::

    t = server.submit(user, batch, mode="C")   # queued, stamped w/ window
    server.flush()                             # micro-batch -> cohort call
    head = server.poll(t)                      # device-resident head pytree
    ...
    server.advance_window()                    # fold deltas into global w

``flush`` turns the queue into pow2-bucketed cohort calls (one per
(mode, window-stamp) group), computes a stacked *head bank*
``heads = w_stamp − delta_stack`` in one jitted pass, admits every real row
into the :class:`repro.serving.bank.DeltaRing`, and caches per-user head
handles.  ``advance_window`` closes the aggregation window: admitted rows
(including stragglers re-weighted from earlier windows) are applied to the
global params with one fused ``apply_rows`` pass per bank.

Steady-state guarantee: submit → flush → poll/head → advance never moves a
tensor to the host — heads are device-side gathers from stacked head banks
and ``stats["host_materializations"]`` stays 0 (pinned by tests and the
``serve`` benchmark row).

Result handles are **per ticket**: every "done" ticket owns the
(heads bank, row) pair its flush produced, so polling an older ticket
after a newer flush returns that ticket's head — never silently the
newest one — and a ticket whose window has retired from the ring fails
explicitly as superseded-and-retired.

Partial-model personalization: construct with ``personal_subset=`` (any
``repro.core.SubsetSpec`` spelling, e.g. ``("fc/#1",)``) and only the
personal leaves are banked — delta rows, head rows, ring snapshots and
the head cache all shrink to the subset while the shared backbone flows
once on the buffered path (``stats["ring_bytes_per_user"]`` reports the
per-user ring residency this buys; the ``partial`` bench gates it).
Served heads are subset pytrees; callers merge them over the global
backbone with ``repro.core.merge_subset``.

Quantized delta banking: construct with ``delta_dtype="int8"`` and every
flush quantizes its cohort's delta stack to int8 rows + per-row-per-leaf
f32 scales (symmetric absmax) with **error feedback** — each user's
quantization error is banked as an int8 residual and added to that user's
next delta before re-quantizing, so banking noise stays a bounded residual
instead of a bias.  Heads become *lazy*: no fp32 head bank is stored at
all; ``poll``/``head`` gather ``snapshot − scale·q`` on device
(:class:`repro.core.quant.QuantizedHeads`), the window apply dispatches
the :class:`repro.core.quant.QuantStack` through the fused
``apply_rows_q`` kernel, and per-user ring residency drops ~4x
(``stats["ring_bytes_per_user"]`` vs ``ring_bytes_per_user_fp32``; the
``quant`` bench gates ≥ 3.5x at equal convergence).

This surface is in-process; other processes reach it over the socket
front-end (:class:`repro.serving.transport.TransportServer` bridges
concurrent connections into submit/flush/poll with deadline-driven flush
timers and explicit backpressure — see that module for the wire protocol;
subset-serving servers require clients to declare ``subset_ok`` and stamp
replies with the subset descriptor, and int8 bodies are sent only to
clients that negotiated the ``codec``).
"""
from __future__ import annotations

import collections
from typing import Callable, Dict, Iterable, List, Optional

import jax
import jax.numpy as jnp

from repro.checkpoint.store import load_meta, load_pytree, save_pytree
from repro.core import init_server_state, staleness_stats
from repro.core.quant import (QuantStack, QuantTree, QuantizedBank,
                              QuantizedHeads, ef_quantize_stack)
from repro.core.subset import SubsetSpec
from repro.core.types import PersAFLConfig, ServerState
from repro.fl.engine import CohortEngine, DeltaBank
from repro.serving.bank import DeltaRing
from repro.serving.batcher import (MODES, MicroBatcher, Ticket,
                                   personalize_strategy)
from repro.spans import span


def _own_copy(params):
    return jax.tree.map(lambda x: jnp.array(x), params)


@jax.jit
def heads_fp32(snapshot, stacked):
    """The fp32 head bank ``snapshot − delta`` of a cohort: f32 compute,
    param dtype out; one compile per stacked shape."""
    return jax.tree.map(
        lambda p, d: (p[None].astype(jnp.float32) - d).astype(p.dtype),
        snapshot, stacked)


def _row_of(handle, row: int):
    """One head row from a ticket/cache handle: device-side either way —
    an eager gather for fp32 head banks, a fused dequantizing gather for
    lazy :class:`QuantizedHeads` views."""
    if isinstance(handle, QuantizedHeads):
        return handle.row(row)
    return jax.tree.map(lambda x: x[row], handle.stacked)


def _rows_of(handle, rows):
    if isinstance(handle, QuantizedHeads):
        return handle.rows(rows)
    return jax.tree.map(lambda x: jnp.take(x, rows, axis=0),
                        handle.stacked)


class PersonalizationServer:
    """Live-traffic serving of personalized heads (Options B and C).

    Parameters
    ----------
    init_params : global model w (copied; the server owns its state)
    loss_fn     : (params, batch) -> scalar, the per-user objective f_i
    pcfg        : personalization hyper-params (α for mode B, λ/K/η_in for
                  mode C, β/damping for the window apply)
    cohort_impl : forwarded to :class:`CohortEngine` — ``"shard_map"``
                  splits user cohorts over the mesh's "cohort" axis and
                  the batcher keys users to cohort slices
    mesh        : optional explicit mesh for the shard_map engines — a 1-D
                  ``("cohort",)`` mesh or a 2-D ``("cohort", "model")``
                  mesh from :func:`repro.sharding.ctx.cohort_model_mesh`;
                  defaults to the ambient :func:`use_mesh` context, else
                  the memoized 1-D cohort mesh
    param_shardings : optional pytree of ``NamedSharding`` matching the
                  params — placement constraint for the model axis of a
                  2-D mesh; forwarded to every mode's engine, and the
                  server's own params/snapshots are device_put to it so
                  delta banks, head rows and ring snapshots inherit
                  model-axis sharding (gather-not-transfer serving)
    windows     : ring depth W (banks + params snapshots retained)
    tau_max     : bounded-staleness admission (≤ W−1; default W−1)
    max_pending : auto-flush threshold for the request queue
    head_cache  : max cached per-user head handles (LRU)
    user_cap    : fairness bound — max delta rows one user may have
                  admitted into a single aggregation window (None = off)
    personal_subset : the personal param subset (SubsetSpec spelling);
                  None = full-model personalization
    delta_dtype : ``"fp32"`` (exact banking) or ``"int8"`` (quantized
                  banking with per-user error feedback; see the module
                  docstring)
    robust      : Byzantine-robust window apply — ``None`` (plain),
                  ``"clip"`` (per-row norm clipping) or ``"trim"``
                  (norm-trimmed mean); forwarded to the ring together
                  with ``clip_norm``/``trim_frac`` (see
                  :func:`repro.core.robust_admission_weights`)

    Each mode's cohort engine is driven by the registry strategy
    ``repro.fl.api.strategy("personalize", mode=...)`` — the serving rules
    are plain Strategy citizens, not a ``client_fn`` special case.
    """

    def __init__(self, init_params, loss_fn: Callable,
                 pcfg: PersAFLConfig, *, cohort_impl: str = "auto",
                 modes: Iterable[str] = MODES, windows: int = 4,
                 tau_max: Optional[int] = None, max_pending: int = 64,
                 head_cache: int = 4096, user_cap: Optional[int] = None,
                 personal_subset=None, delta_dtype: str = "fp32",
                 robust: Optional[str] = None,
                 clip_norm: Optional[float] = None,
                 trim_frac: float = 0.1,
                 mesh=None, param_shardings=None):
        self.pcfg = pcfg
        self.loss_fn = loss_fn
        params0 = _own_copy(init_params)
        if param_shardings is not None:
            # model-axis placement up front: every downstream artifact
            # (snapshots, delta banks, head rows) derives its sharding
            # from the params it was computed against
            params0 = jax.device_put(params0, param_shardings)
        self.state = init_server_state(params0)
        self.max_pending = max_pending
        self.head_cache = head_cache
        self.delta_dtype = delta_dtype
        self.personal_subset = SubsetSpec.resolve(personal_subset,
                                                 self.state.params)

        engines: Dict[str, CohortEngine] = {}
        shared_stats = None
        for mode in modes:
            eng = CohortEngine(
                pcfg, loss_fn, cohort_impl=cohort_impl,
                mesh=mesh, param_shardings=param_shardings,
                strategy=personalize_strategy(
                    pcfg, loss_fn, mode,
                    personal_subset=self.personal_subset))
            if shared_stats is None:
                shared_stats = eng.stats
            else:
                eng.stats = shared_stats  # one counter set across modes
            engines[mode] = eng
        if not engines:
            raise ValueError("need at least one personalization mode")
        self.engines = engines
        self._engine_stats = shared_stats

        self.ring = DeltaRing(self.state.params, windows=windows,
                              tau_max=tau_max, user_cap=user_cap,
                              subset=self.personal_subset,
                              delta_dtype=delta_dtype, robust=robust,
                              clip_norm=clip_norm, trim_frac=trim_frac)
        if delta_dtype == "fp32":
            for eng in engines.values():
                eng.add_bank_hook(self.ring.retain)   # bank handoff
        # int8 banking: the raw fp32 cohort bank must NOT be pinned — the
        # flush quantizes it (with the per-user EF residual folded in) and
        # retains only the QuantizedBank, so the fp32 stack is transient
        n_shards = max(eng._ndev for eng in engines.values())
        self.batcher = MicroBatcher(engines, n_shards=n_shards,
                                    user_cap=user_cap)

        # user -> (head DeltaBank, row): device-resident, LRU-evicted
        self._heads: "collections.OrderedDict" = collections.OrderedDict()
        # user -> (residual QuantizedBank, row): the quantization error of
        # the user's last banked delta, added to their next delta before
        # re-quantizing (error feedback); LRU-evicted like the head cache
        self._residuals: "collections.OrderedDict" = \
            collections.OrderedDict()

    # -- request path ------------------------------------------------------

    @property
    def params(self):
        """The current global model w (post last window apply)."""
        return self.state.params

    @property
    def window(self) -> int:
        return self.ring.current

    def submit(self, user, batch, mode: str = "C") -> Ticket:
        """Queue one personalization request; stamps the current window."""
        ticket = self.batcher.submit(
            Ticket(user=user, mode=mode, stamp=self.ring.current), batch)
        if len(self.batcher) >= self.max_pending:
            self.flush()
        return ticket

    def flush(self) -> int:
        """Drain the queue into cohort calls; returns #requests served.

        Per (mode, stamp) group: ONE cohort call against the stamped
        snapshot, ONE jitted stacked-head computation, then per-row ring
        admission + head-cache insertion (all device handles, no
        transfers).
        """
        with span("flush", window=self.ring.current):
            return self._flush()

    def _flush(self) -> int:
        served = 0
        for mode, stamp, bank, placed in self.batcher.drain(
                self.ring.current, self.ring.snapshot,
                tau_max=self.ring.tau_max):
            resbank = None
            if self.delta_dtype == "int8":
                # quantize the cohort's fp32 delta stack (adding each
                # user's banked EF residual first) and pin ONLY the int8
                # bank; heads become a lazy snapshot − scale·q view over
                # it — no fp32 head bank is ever stored
                bank, resbank = self._quantize_bank(bank, placed)
                self.ring.retain(bank)
                heads = QuantizedHeads(self.ring.subset_snapshot(stamp),
                                       bank)
            else:
                # subset mode: the delta stack is subset-shaped, so the
                # head subtraction runs against the snapshot's stored
                # subset tree (same pruned structure) — heads are subset
                # pytrees
                heads = DeltaBank(
                    stacked=heads_fp32(
                        self.ring.subset_snapshot(stamp), bank.stacked),
                    k=bank.k, stats=self._engine_stats)
                self.ring.retain(heads)  # head rows live with the bank
            with span("ring.admit", window=self.ring.current,
                      rows=len(placed)):
                for ticket, row in placed:
                    # the ring is the admission authority: the batcher's
                    # drain bound normally pre-filters, but a refusal here
                    # must not serve a head whose delta never reached the
                    # global apply.  The refusal CAUSE must survive to
                    # poll: a fairness-cap refusal is "capped" (re-submit
                    # next window), never "dropped" (which poll reports as
                    # a tau_max violation)
                    verdict = self.ring.admit_row(ticket.user, bank, row,
                                                  ticket.tau)
                    if verdict != "admitted":
                        ticket.status = verdict
                        continue
                    self._cache_head(ticket.user, heads, row)
                    if resbank is not None:
                        # the NEW residual (this row's quantization error)
                        # replaces the user's banked one — consumed-and-
                        # replaced is exactly the EF recurrence.  Refused
                        # rows never apply, so their user keeps the old
                        # residual.
                        self._cache_residual(ticket.user, resbank, row)
                    # the ticket owns its result: poll resolves THIS
                    # handle, not whatever head the user's latest flush
                    # produced
                    ticket.head = (heads, row)
                    ticket.window = self.ring.current
                    ticket.status = "done"
                    served += 1
        return served

    # -- quantized banking (error feedback) --------------------------------

    def _quantize_bank(self, bank: DeltaBank, placed):
        """int8-quantize a cohort's delta stack with error feedback.

        Each placed user's banked residual (the quantization error of
        their previous delta) is added to their row before re-quantizing;
        the new per-row error comes back as an int8 residual bank whose
        rows replace the users' entries after admission.  Returns
        ``(delta QuantizedBank, residual QuantizedBank)``.
        """
        with span("ef.residual", rows=len(placed)):
            residual = self._residual_stack(bank.stacked, placed)
        with span("ef.quantize", rows=len(placed)):
            qstack, resstack = ef_quantize_stack(bank.stacked, residual)
        qbank = QuantizedBank(qstack, k=bank.k, stats=self._engine_stats)
        resbank = QuantizedBank(resstack, k=bank.k,
                                stats=self._engine_stats)
        return qbank, resbank

    def _residual_stack(self, raw, placed):
        """fp32 residual stack row-aligned with ``raw`` (None if no placed
        user has a banked residual).  One dequantizing gather per source
        residual bank; a user appearing twice in one cohort gets their
        residual credited once (first row) — crediting both rows would
        double-apply the error."""
        seen = set()
        groups: Dict[int, list] = {}
        for ticket, row in placed:
            user = ticket.user
            if user in seen or user not in self._residuals:
                continue
            seen.add(user)
            src_bank, src_row = self._residuals[user]
            groups.setdefault(id(src_bank), [src_bank, [], []])
            groups[id(src_bank)][1].append(src_row)
            groups[id(src_bank)][2].append(row)
        if not groups:
            return None
        out = jax.tree.map(
            lambda x: jnp.zeros(x.shape, jnp.float32), raw)
        for src_bank, src_rows, dst_rows in groups.values():
            vals = src_bank.rows(jnp.asarray(src_rows, jnp.int32))
            dst = jnp.asarray(dst_rows, jnp.int32)
            out = jax.tree.map(
                lambda o, v: o.at[dst].set(v.astype(jnp.float32)),
                out, vals)
        return out

    def _cache_residual(self, user, resbank: QuantizedBank,
                        row: int) -> None:
        self._residuals[user] = (resbank, row)
        self._residuals.move_to_end(user)
        while len(self._residuals) > self.head_cache:
            self._residuals.popitem(last=False)

    def poll(self, ticket: Ticket):
        """None while queued; THIS ticket's head pytree once served.

        The head comes from the ticket's own (bank, row) handle — polling
        an older ticket after a newer flush for the same user returns the
        older head, it is never silently aliased to the newest one.  Raises
        on dropped tickets (staleness bound exceeded), capped tickets
        (fairness), and superseded-and-retired tickets (the ticket's ring
        window rotated out: its bank is gone) — all mean the user must
        re-submit against a fresh snapshot.
        """
        with span("poll", window=ticket.window):
            return self._poll(ticket)

    def _poll(self, ticket: Ticket):
        if ticket.status == "queued":
            return None
        if ticket.status == "dropped":
            raise RuntimeError(
                f"request for {ticket.user!r} exceeded tau_max="
                f"{self.ring.tau_max} (tau={ticket.tau}); re-submit")
        if ticket.status == "capped":
            raise RuntimeError(
                f"request for {ticket.user!r} exceeded the per-window "
                f"fairness cap (user_cap={self.batcher.user_cap}); "
                f"re-submit next window")
        if ticket.head is None:
            # handle-less done ticket (constructed by hand / pre-restart):
            # the cache is the only resolver left
            if ticket.user not in self._heads:
                raise RuntimeError(
                    f"head for {ticket.user!r} was evicted from the cache "
                    f"(head_cache={self.head_cache}); re-submit")
            return self.head(ticket.user)
        horizon = self.ring.current - self.ring.windows + 1
        if ticket.window < horizon:
            ticket.head = None   # the bank is gone; drop our pin on it
            raise RuntimeError(
                f"ticket for {ticket.user!r} was superseded and retired: "
                f"served in window {ticket.window}, ring horizon is "
                f"{horizon} (windows={self.ring.windows}); re-submit")
        heads, row = ticket.head
        return _row_of(heads, row)

    def _cache_head(self, user, heads: DeltaBank, row: int) -> None:
        self._heads[user] = (heads, row)
        self._heads.move_to_end(user)
        while len(self._heads) > self.head_cache:
            self._heads.popitem(last=False)

    def head(self, user):
        """The user's personalized head — a device-side row gather from the
        stacked head bank (never a host materialization)."""
        heads, row = self._heads[user]
        self._heads.move_to_end(user)
        return _row_of(heads, row)

    def stacked_heads(self, users: List):
        """``[len(users), ...]`` stacked heads (batched decode input).

        One ``jnp.take`` gather when every user sits in the same head bank
        (the steady-state micro-batch case), row-stack fallback otherwise.
        """
        handles = [self._heads[u] for u in users]
        first = handles[0][0]
        if all(h is first for h, _ in handles):
            rows = jnp.asarray([r for _, r in handles], jnp.int32)
            return _rows_of(first, rows)
        return jax.tree.map(lambda *xs: jnp.stack(xs),
                            *[self.head(u) for u in users])

    # -- window boundary ---------------------------------------------------

    def advance_window(self, *, flush: bool = True) -> None:
        """Close the aggregation window: every admitted delta row
        (stragglers included, re-weighted by ``admission_weights``) is
        folded into the global params and the ring rotates.

        ``flush=False`` models a timer-driven boundary firing while
        requests are still queued — those requests become stragglers: the
        next flush computes them against their *stamped* (retained)
        snapshot and admits them into the new window's weight vector.
        """
        with span("advance", window=self.ring.current):
            if flush:
                self.flush()
            self.state = self.ring.advance(
                self.state, beta=self.pcfg.beta,
                damping=self.pcfg.staleness_damping)

    # -- restart warm-start ------------------------------------------------

    @staticmethod
    def _ckpt_snap(snap):
        """NamedTuples flatten as anonymous tuples in the checkpoint
        layout, so an int8-demoted snapshot is stored as an explicit
        marker dict — ``{"__q8__": q, "__q8s__": scales}`` — and re-typed
        on restore.  Bit-exact: the int8 codes and f32 scales round-trip
        untouched."""
        if isinstance(snap, QuantTree):
            return {"__q8__": snap.q, "__q8s__": snap.scales}
        return snap

    @staticmethod
    def _unckpt_snap(snap):
        if isinstance(snap, dict) and set(snap) == {"__q8__", "__q8s__"}:
            return QuantTree(
                q=jax.tree.map(jnp.asarray, snap["__q8__"]),
                scales=jax.tree.map(jnp.asarray, snap["__q8s__"]))
        return jax.tree.map(jnp.asarray, snap)

    def _gathered_residuals(self):
        """(stacked residual QuantStack, users) — one row per cached user,
        gathered from the source residual banks WITHOUT dequantizing (the
        int8 codes themselves persist, so save→restore is bit-exact)."""
        users = list(self._residuals)
        if not users:
            return None, []
        groups: Dict[int, list] = {}
        for i, user in enumerate(users):
            src_bank, src_row = self._residuals[user]
            groups.setdefault(id(src_bank), [src_bank, [], []])
            groups[id(src_bank)][1].append(src_row)
            groups[id(src_bank)][2].append(i)
        template = next(iter(groups.values()))[0].stacked
        n = len(users)
        q = jax.tree.map(
            lambda x: jnp.zeros((n,) + x.shape[1:], x.dtype), template.q)
        s = jax.tree.map(
            lambda x: jnp.zeros((n,) + x.shape[1:], x.dtype),
            template.scales)
        for src_bank, src_rows, dst_rows in groups.values():
            src = jnp.asarray(src_rows, jnp.int32)
            dst = jnp.asarray(dst_rows, jnp.int32)
            q = jax.tree.map(
                lambda o, x: o.at[dst].set(jnp.take(x, src, axis=0)),
                q, src_bank.stacked.q)
            s = jax.tree.map(
                lambda o, x: o.at[dst].set(jnp.take(x, src, axis=0)),
                s, src_bank.stacked.scales)
        return QuantStack(q=q, scales=s), users

    def save(self, path: str) -> None:
        """Checkpoint the serving state through ``repro.checkpoint.store``:
        the typed ServerState, the ring's retained params snapshots +
        window counter + cumulative admission stats, and the head cache as
        ONE stacked head bank.  Under int8 banking the demoted snapshots
        and the per-user EF residuals persist *quantized* (codes + scales,
        bit-exact), so a restored server continues the error-feedback
        recurrence exactly where the saved one left off.

        A restart restored from this no longer rebuilds the ring empty —
        users keep their cached heads and straggler *requests* stamped
        before the restart still find their snapshots.  In-flight delta
        rows (unapplied bank admissions) are the one thing lost; affected
        users re-personalize against the restored snapshots.
        """
        users = list(self._heads)
        res_stack, res_users = self._gathered_residuals()
        tree = {
            "server_state": self.state.as_dict(),
            "ring_snapshots": {f"w{w}": self._ckpt_snap(snap)
                               for w, snap in self.ring._snapshots.items()},
            "head_stack": self.stacked_heads(users) if users else None,
            "residuals": ({"q": res_stack.q, "scales": res_stack.scales}
                          if res_stack is not None else None),
        }
        # tau_max persists as REQUESTED, not as clamped to this ring's
        # depth: restoring into a deeper ring must widen back to the
        # request (the clamp is a property of the ring, not of the config)
        meta = {"users": users, "ring_current": self.ring.current,
                "windows": self.ring.windows,
                "tau_max": self.ring.tau_max_requested,
                "user_cap": self.ring.user_cap,
                "delta_dtype": self.delta_dtype,
                "residual_users": res_users,
                "personal_subset":
                    self.personal_subset.descriptor(self.state.params)
                    if self.personal_subset is not None else None,
                "ring_stats": {k: int(v)
                               for k, v in self.ring.stats.items()}}
        save_pytree(path, tree, meta=meta)

    @classmethod
    def restore(cls, path: str, loss_fn: Callable, pcfg: PersAFLConfig,
                **kw) -> "PersonalizationServer":
        """Rebuild a server from :meth:`save`'s checkpoint (warm start).

        Ring depth / staleness bound / fairness cap / personal subset come
        from the checkpoint, but any of them may be overridden through
        ``**kw`` (e.g. restore into a deeper ring with ``windows=8`` — the
        checkpointed *requested* ``tau_max`` then re-clamps against the new
        depth, not the old one).  ``**kw`` otherwise forwards the
        process-local knobs (``cohort_impl``, ``modes``, ``max_pending``,
        ``head_cache``).  Head-cache users must be JSON-serializable keys
        (strings in practice) — they round-trip through the sidecar meta.
        """
        tree = load_pytree(path)
        meta = load_meta(path)
        state = ServerState.from_dict(
            jax.tree.map(jnp.asarray, tree["server_state"]))
        windows = kw.pop("windows", meta["windows"])
        tau_max = kw.pop("tau_max", meta.get("tau_max"))
        user_cap = kw.pop("user_cap", meta.get("user_cap"))
        subset = kw.pop("personal_subset", meta.get("personal_subset"))
        delta_dtype = kw.pop("delta_dtype",
                             meta.get("delta_dtype", "fp32"))
        srv = cls(state.params, loss_fn, pcfg, windows=windows,
                  tau_max=tau_max, user_cap=user_cap,
                  personal_subset=subset, delta_dtype=delta_dtype, **kw)
        srv.state = state
        snapshots = {int(k[1:]): cls._unckpt_snap(snap)
                     for k, snap in tree["ring_snapshots"].items()}
        srv.ring.load(snapshots, meta["ring_current"],
                      stats=meta.get("ring_stats"))
        users = meta["users"]
        if users:
            heads = DeltaBank(
                stacked=jax.tree.map(jnp.asarray, tree["head_stack"]),
                k=len(users), stats=srv._engine_stats)
            if delta_dtype == "fp32":
                # device residency across windows; under int8 banking this
                # restored bank is a MATERIALIZED fp32 head stack — the
                # cache handles pin it, and retaining it would poison the
                # ring's quantized row_nbytes accounting
                srv.ring.retain(heads)
            for row, user in enumerate(users):
                srv._cache_head(user, heads, row)
        res_users = meta.get("residual_users") or []
        if res_users and tree.get("residuals") is not None:
            stack = QuantStack(
                q=jax.tree.map(jnp.asarray, tree["residuals"]["q"]),
                scales=jax.tree.map(jnp.asarray,
                                    tree["residuals"]["scales"]))
            resbank = QuantizedBank(stack, k=len(res_users),
                                    stats=srv._engine_stats)
            for row, user in enumerate(res_users):
                srv._cache_residual(user, resbank, row)
        return srv

    # -- observability -----------------------------------------------------

    @property
    def stats(self) -> Dict:
        s = dict(self._engine_stats)
        s.update({f"ring_{k}": v for k, v in self.ring.stats.items()})
        s.update({f"batcher_{k}": v for k, v in self.batcher.stats.items()})
        s["live_banks"] = self.ring.live_banks
        s["cached_heads"] = len(self._heads)
        # per-user steady-state ring residency, 2 rows per served user per
        # window: fp32 banking retains a delta row + a head row; int8
        # banking retains a delta row + an EF residual row (heads are lazy
        # views, they add no storage) — both cases 2x the bank row bytes.
        # The partial bench gates the subset shrink, the quant bench the
        # codec shrink (vs ``ring_bytes_per_user_fp32``).
        row = self.ring.row_nbytes or 0
        row_fp32 = self.ring.row_nbytes_fp32 or row
        s["ring_row_bytes"] = row
        s["ring_bytes_per_user"] = 2 * row
        s["ring_bytes_per_user_fp32"] = 2 * row_fp32
        s["ring_bytes_saved_per_user"] = 2 * (row_fp32 - row)
        s["delta_codec"] = self.delta_dtype
        return s

    def staleness(self) -> Dict:
        return staleness_stats(self.state)
