"""Vectorized cohort execution engine with an on-device DeltaBank.

The discrete-event simulators used to dispatch one jitted ``client_update``
per event — simulating n concurrent clients cost O(n) sequential device
calls.  This engine restores the data-parallelism the paper's setting has by
construction: between two server applies the global params are *frozen*, so
every client whose compute window falls in that interval sees the same
weights and their Q-step local updates are embarrassingly parallel.

Architecture (DESIGN.md §2 extension):

  * :class:`CohortEngine` compiles ONE cohort-mapped jitted kernel and
    reuses it for the whole run — ``jax.vmap`` over clients on TPU (SIMD
    batching), ``lax.map`` on CPU (dispatch amortization without XLA-CPU's
    poor batched-GEMM lowering), or ``shard_map`` splitting the cohort axis
    over every addressable device; see ``cohort_impl``.  Cohorts are padded
    up to power-of-two buckets so the jit cache stays O(log max_cohort)
    instead of one compile per cohort size.
  * The cohort call is a single device round-trip regardless of cohort
    size.  (The stacked batch buffer is not donated: no output shares its
    shape, so XLA could not reuse it and would only warn.)
  * Simulators defer per-client compute: batches are recorded at
    download-completion time and materialized lazily in one cohort call
    right before the next server apply.  Every delta is therefore computed
    on exactly the params snapshot the sequential per-event path would have
    used — the vectorized path is a performance transform, not a semantics
    change (``tests/test_engine.py`` pins the equivalence for options
    A/B/C).

DeltaBank contract:

  * ``update_cohort`` returns a :class:`DeltaBank` — a handle to the
    stacked ``[bucket, ...]`` per-client delta buffer that STAYS ON DEVICE.
    The bank owns the buffer; the engine never touches it again after
    returning it, and the caller keeps it alive for exactly as long as any
    of its rows is still unapplied (the buffered scheduler holds banks
    across flush windows for in-flight clients).
  * Bulk consumers (buffered/sync applies) read ``bank.stacked`` and reduce
    it on device through ``kernels/fused_update.apply_rows_tree`` with a
    per-row weight vector — β/M, staleness damping and padding masks are
    all rows of one ``[bucket]`` array, so no per-client delta ever crosses
    to the host (``stats["host_materializations"]`` counts the banks that
    did; a buffered run keeps it at 0).
  * Row consumers (the paper-faithful immediate apply) call ``bank.row(i)``
    /iterate the bank: the FIRST access performs one device→host transfer
    of the whole stack, after which every row is a free numpy view — the
    same single round-trip the pre-bank engine paid, now lazy.
  * In ``cohort_impl="shard_map"`` the buffer is sharded over the cohort
    mesh axis; ``row()`` gathers (host materialization), while
    ``apply_rows_tree``/``update_cohort_mean`` reduce it on device.  On
    the 2-D ``("cohort", "model")`` mesh the bank's model dims are
    additionally split along "model" (an explicit post-cohort reshard to
    ``P("cohort", *param_spec)`` per leaf, derived from the params'
    shardings; the cohort compute itself runs model-replicated — see
    ``repro.sharding.ctx``), and per-bank gathers (``client_state``,
    ``stacked``) stay sharded — gather-not-transfer on both axes.

Strategy contract (PR 4, ``repro.fl.api``):

  * The local update rule is pluggable: pass a bound
    :class:`repro.fl.api.Strategy` and the engine cohort-maps
    ``strategy.local_update(params, batches, cstate)`` instead of the
    built-in Algorithm-2 ``client_update``.  Stateful strategies (SCAFFOLD
    control variates) thread a *stacked client-state pytree* through the
    same vmap/lax.map/shard_map machinery: ``update_cohort(...,
    cstate_list=...)`` stacks the per-client states along the cohort axis
    and the returned bank carries the updated stack
    (:meth:`DeltaBank.client_state`).  FedProx/SCAFFOLD are thereby
    first-class cohort-engine citizens — their deltas land in the
    DeltaBank like everyone else's.  (The pre-PR-4 ``client_fn=``
    override was removed in PR 10: wrap the rule in a Strategy.)
  * A strategy with ``personal_subset`` set returns deltas in the pruned
    subset structure (``repro.core.subset``): the bank's stacked buffer —
    and everything downstream of it (ring rows, head cache, wire frames) —
    carries only the personal leaves.  The engine is structure-agnostic:
    vmap/lax.map stack whatever the rule returns, and the shard_map path
    uses pytree-prefix out_specs for the same reason.

The per-event sequential path is kept behind ``vectorized=False`` as the
baseline the ``engine`` benchmark row measures against.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import client_update, split_batches_for_option
from repro.core.types import PersAFLConfig
from repro.sharding.ctx import active_mesh, cohort_axis_size, cohort_mesh
from repro.spans import span


def _stack(batch_list: List):
    """Stack per-client batch pytrees along a new cohort axis.

    Host (numpy) leaves — the data pipeline's native output — are stacked
    host-side in one memcpy per leaf; device leaves fall back to jnp.stack.
    """
    if all(isinstance(leaf, np.ndarray)
           for leaf in jax.tree.leaves(batch_list[0])):
        return jax.tree.map(lambda *xs: np.stack(xs), *batch_list)
    return jax.tree.map(lambda *xs: jnp.stack(xs), *batch_list)


class DeltaBank:
    """Handle to a stacked ``[capacity, ...]`` per-client delta buffer.

    ``stacked`` is the on-device buffer (rows ≥ ``k`` are bucket padding);
    rows cross to the host only through :meth:`row` — one transfer of the
    whole stack on first access, numpy views afterwards.  Iterating yields
    the ``k`` real rows in cohort order.
    """

    def __init__(self, stacked=None, k: int = 0,
                 stats: Optional[Dict] = None, rows: Optional[List] = None,
                 cstates=None, cstate_rows: Optional[List] = None):
        self._stacked = stacked
        self._rows = rows          # per-event path: one delta tree per row
        self.k = k if rows is None else len(rows)
        self._stats = stats if stats is not None else {}
        self._host = None
        # stateful-strategy runs: the updated per-client states, stacked
        # along the same cohort axis (or one tree per row, per-event path)
        self._cstates = cstates
        self._cstate_rows = cstate_rows

    @property
    def capacity(self) -> int:
        if self._rows is not None:
            return self.k
        tree = self._stacked if self._stacked is not None else self._host
        return jax.tree.leaves(tree)[0].shape[0]

    @property
    def stacked(self):
        """The ``[capacity, ...]`` device buffer (stacks lazily when the
        bank was built from per-event row deltas; re-uploads if host
        materialization already released the device copy)."""
        if self._stacked is None:
            if self._rows is not None:
                self._stacked = jax.tree.map(lambda *xs: jnp.stack(xs),
                                             *self._rows)
            else:
                self._stacked = jax.device_put(self._host)
        return self._stacked

    def row(self, i: int):
        """Materialize row ``i`` on the host (lazy, whole-stack-at-once)."""
        if self._rows is not None:
            return self._rows[i]
        if self._host is None:
            self._stats["host_materializations"] = \
                self._stats.get("host_materializations", 0) + 1
            self._host = jax.device_get(self._stacked)
            # release the device buffer — rows serve from host views now,
            # and holding both copies would double delta residency exactly
            # where the bank was meant to shrink it
            self._stacked = None
        return jax.tree.map(lambda x: x[i], self._host)

    def client_state(self, i: int):
        """Row ``i``'s updated client state (stateful strategies only) — a
        lazy device-side gather from the stacked state buffer; never a host
        materialization."""
        if self._cstate_rows is not None:
            return self._cstate_rows[i]
        if self._cstates is None:
            raise ValueError("bank carries no client states "
                             "(stateless strategy)")
        return jax.tree.map(lambda x: x[i], self._cstates)

    def __len__(self) -> int:
        return self.k

    def __getitem__(self, i: int):
        if not -self.k <= i < self.k:
            raise IndexError(i)
        return self.row(i % self.k)

    def __iter__(self):
        return (self.row(i) for i in range(self.k))


class CohortEngine:
    """Batched ``client_update`` over a cohort of clients.

    One engine instance owns the jit caches; simulators create it once per
    run so recompiles never land on the event loop's hot path.

    ``cohort_impl`` picks how the cohort axis is mapped inside the single
    jitted call:
      * ``"vmap"`` — SIMD batching over clients (default on TPU: the MXU
        eats the extra batch dim for free and the whole cohort is one
        kernel launch).
      * ``"map"``  — ``lax.map`` over clients (default on CPU: one dispatch
        amortized over the cohort, but per-client compute stays sequential
        — XLA-CPU lowers batched GEMMs poorly, so vmap can *lose* to
        per-event dispatch there).
      * ``"shard_map"`` — the cohort axis is split over the mesh's
        "cohort" axis (8-way forced-host-device CPU and TPU pods alike);
        each cohort slice lax.maps its local rows, and the delta buffer
        comes back sharded over the mesh — it never gathers unless a row
        is materialized.  Buckets round up to a cohort-slice-count
        multiple.
    All are the same math; ``"auto"`` selects vmap/map by backend.

    ``mesh`` picks the layout for the shard_map path: the 1-D
    ``("cohort",)`` mesh (default), or a 2-D ``("cohort", "model")`` mesh
    from :func:`repro.sharding.ctx.cohort_model_mesh`.  The shard_map
    body is Manual over every mesh axis, with the params gathered
    replicated into it, so each row's update runs model-replicated; the
    "model" axis shards storage: with ``param_shardings`` (a params-shaped
    pytree of ``NamedSharding``s, e.g. from
    :func:`repro.sharding.rules.param_shardings`) the delta stack is
    re-sharded on the way out, so the bank's rows come back split along
    BOTH axes.  The masked cohort mean is one
    ``psum("cohort")`` per leaf and never crosses "model" — a
    cross-model reduction would re-reduce within each row.  When no
    ``mesh`` is passed, the ambient :func:`repro.sharding.ctx.use_mesh`
    context (if any) is consulted before the memoized 1-D default.
    """

    def __init__(self, pcfg: PersAFLConfig, loss_fn: Callable, *,
                 vectorized: bool = True, cohort_impl: str = "auto",
                 client_fn=None, strategy=None, mesh=None,
                 param_shardings=None):
        if client_fn is not None:
            raise TypeError(
                "CohortEngine(client_fn=...) was removed in PR 10 (it was "
                "deprecated since PR 4): wrap the update rule in a "
                "repro.fl.api.Strategy and pass strategy=... — e.g. "
                "strategy('personalize', mode='C') for the serving "
                "override it used to spell.")
        self.pcfg = pcfg
        self.loss_fn = loss_fn
        self.vectorized = vectorized
        if cohort_impl == "auto":
            cohort_impl = "vmap" if jax.default_backend() == "tpu" else "map"
        self.cohort_impl = cohort_impl
        self.stats: Dict[str, int] = {"cohort_calls": 0, "clients": 0,
                                      "max_cohort": 0, "padding_waste": 0,
                                      "host_materializations": 0}
        # window-boundary hooks: every bank this engine produces is handed
        # to each registered callback before update_cohort returns — the
        # handoff point the serving ring uses to retain banks (and their
        # device residency) across flush windows without the scheduler
        # knowing the ring exists.
        self._bank_hooks: List[Callable[[DeltaBank], None]] = []

        self.strategy = strategy
        self.stateful = bool(strategy is not None
                             and getattr(strategy, "stateful", False))
        if strategy is not None:
            def _one(params, batches):
                # metrics are dropped so XLA dead-code-eliminates the
                # per-step norm reductions — schedulers only consume the
                # delta
                delta, _, _ = strategy.local_update(params, batches, None)
                return delta

            def _one_s(params, batches, cstate, shared):
                # shared state (SCAFFOLD's c_global) is a separate
                # REPLICATED input — one device copy per cohort call, not
                # one per cohort row — recombined with the client's state
                # row inside the traced body
                delta, new_cstate, _ = strategy.local_update(
                    params, batches,
                    strategy.assemble_state(cstate, shared))
                return delta, new_cstate
        else:
            def _one(params, batches_3q):
                batches = split_batches_for_option(pcfg.option, batches_3q)
                delta, _ = client_update(pcfg, loss_fn, params, batches)
                return delta
            _one_s = None

        self._jit_one = jax.jit(_one)
        self._jit_one_s = jax.jit(_one_s) if self.stateful else None
        self._ndev = 1
        self._jit_cohort_sum = None
        self._jit_cohort_s = None
        if cohort_impl == "vmap":
            cohort_fn = lambda params, stacked: jax.vmap(  # noqa: E731
                lambda b: _one(params, b))(stacked)
            cohort_s_fn = lambda params, stacked, cstates, shared: \
                jax.vmap(lambda b, c: _one_s(params, b, c,
                                             shared))(stacked, cstates)
        elif cohort_impl == "map":
            cohort_fn = lambda params, stacked: jax.lax.map(  # noqa: E731
                lambda b: _one(params, b), stacked)
            cohort_s_fn = lambda params, stacked, cstates, shared: \
                jax.lax.map(lambda bc: _one_s(params, bc[0], bc[1], shared),
                            (stacked, cstates))
        elif cohort_impl == "shard_map":
            from jax.sharding import NamedSharding, PartitionSpec as P
            self._mesh = mesh if mesh is not None \
                else (active_mesh() or cohort_mesh())
            if "cohort" not in self._mesh.axis_names:
                raise ValueError(
                    f"cohort_impl='shard_map' needs a mesh with a 'cohort' "
                    f"axis, got axes {self._mesh.axis_names}; build one "
                    f"with repro.sharding.ctx.cohort_model_mesh()")
            # _ndev is the COHORT-AXIS size, not the device count: it
            # drives bucket rounding and the batcher's user→cohort-slice
            # keying, and on a ("cohort", "model") mesh each cohort slice
            # is a model-parallel device group
            self._ndev = cohort_axis_size(self._mesh)
            self._param_shardings = param_shardings

            # the shard_map below is Manual over EVERY mesh axis.  Cohort
            # rows split over "cohort"; params enter replicated (P() in-
            # spec) so each row's update is full-size local math — no
            # cross-"model" collective ever runs inside a grad, whose
            # reductions would otherwise reassociate with the model-axis
            # size and break bit-parity across mesh layouts.  (A partially-
            # Auto model axis would shard the compute too, but jax 0.4.x
            # crashed XLA on any scan under subgroup-manual spmd, and real
            # archs scan everywhere; not re-tested on jax 0.9.0.)  The
            # model axis shards STORAGE: _bank_constrain re-shards the
            # delta stack on the way out, and the server device_puts
            # params/snapshots.

            def _gather(params):
                # explicit replicate of model-sharded params before the
                # Manual region (device-to-device all-gather, one per
                # cohort call, never a host materialization); also keeps
                # shard_map from seeing an input whose committed sharding
                # disagrees with its P() in-spec
                if param_shardings is None:
                    return params
                repl = NamedSharding(self._mesh, P())
                return jax.tree.map(
                    lambda x: jax.lax.with_sharding_constraint(x, repl),
                    params)

            def _bank_constrain(stack):
                # re-shard the delta stack for storage: each param leaf's
                # model-axis spec, with the cohort axis prepended for the
                # row dim — P(None, "model") params make P("cohort", None,
                # "model") bank rows.  Pure placement (each device keeps
                # its slice of rows it already holds replicated): bits
                # never change, so parity with the 1-D path survives.
                # Subset-pruned delta trees don't match the full-params
                # sharding tree — they stay cohort-sharded only.
                if param_shardings is None:
                    return stack
                try:
                    sh = jax.tree.map(
                        lambda s: NamedSharding(
                            self._mesh, P("cohort", *s.spec)),
                        param_shardings)
                    return jax.tree.map(
                        jax.lax.with_sharding_constraint, stack, sh)
                except ValueError:
                    return stack

            def _shard_body(params, stacked):
                return jax.lax.map(lambda b: _one(params, b), stacked)

            def cohort_fn(params, stacked):
                # out_specs is a pytree PREFIX: a bare P("cohort") covers
                # whatever structure the strategy's delta takes — full
                # params-shaped or a pruned personal_subset tree (which a
                # params-shaped spec tree could not describe).  Axes the
                # specs leave out (the "model" axis) see replicated data.
                out = jax.shard_map(
                    _shard_body, mesh=self._mesh,
                    in_specs=(jax.tree.map(lambda _: P(), params),
                              jax.tree.map(lambda _: P("cohort"), stacked)),
                    out_specs=P("cohort"),
                    check_vma=False)(_gather(params), stacked)
                return _bank_constrain(out)

            def _shard_body_s(params, stacked, cstates, shared):
                return jax.lax.map(
                    lambda bc: _one_s(params, bc[0], bc[1], shared),
                    (stacked, cstates))

            def cohort_s_fn(params, stacked, cstates, shared):
                # pytree-prefix specs: every leaf of the stacked batch /
                # state buffers is split on the cohort axis, params and the
                # shared state replicated; outputs (delta stack, cstate
                # stack) come back cohort-sharded
                delta, cs = jax.shard_map(
                    _shard_body_s, mesh=self._mesh,
                    in_specs=(P(), P("cohort"), P("cohort"), P()),
                    out_specs=(P("cohort"), P("cohort")),
                    check_vma=False)(_gather(params), stacked, cstates,
                                     shared)
                return _bank_constrain(delta), cs

            def _sum_body(params, stacked, mask):
                deltas = jax.lax.map(lambda b: _one(params, b), stacked)
                local = jax.tree.map(
                    lambda d: jnp.tensordot(mask, d.astype(jnp.float32),
                                            axes=(0, 0)), deltas)
                # the whole cohort reduction is this ONE psum per leaf,
                # over "cohort" ONLY — the model axis (Auto) already holds
                # every row replicated, so a psum crossing "model" would
                # multiply the sum by the model-axis size
                return jax.tree.map(lambda x: jax.lax.psum(x, "cohort"),
                                    local)

            def sum_fn(params, stacked, mask):
                return jax.shard_map(
                    _sum_body, mesh=self._mesh,
                    in_specs=(jax.tree.map(lambda _: P(), params),
                              jax.tree.map(lambda _: P("cohort"), stacked),
                              P("cohort")),
                    out_specs=jax.tree.map(lambda _: P(), params),
                    check_vma=False)(_gather(params), stacked, mask)

            self._jit_cohort_sum = jax.jit(sum_fn)
        else:
            raise ValueError(f"unknown cohort_impl {cohort_impl!r}")
        self._jit_cohort = jax.jit(cohort_fn)
        if self.stateful:
            self._jit_cohort_s = jax.jit(cohort_s_fn)

    def add_bank_hook(self, fn: Callable[["DeltaBank"], None]) -> None:
        """Register a bank-handoff callback (serving ring retention, stats
        scrapers).  Called once per ``update_cohort`` with the new bank."""
        self._bank_hooks.append(fn)

    def _emit(self, bank: "DeltaBank") -> "DeltaBank":
        for hook in self._bank_hooks:
            hook(bank)
        return bank

    def _bucket(self, k: int) -> int:
        """Pow2 bucket, rounded up to a cohort-slice-count multiple when
        the cohort axis is sharded (every cohort slice gets equal rows; on
        the 2-D mesh a slice is a whole model-parallel device group, so a
        2×4 mesh rounds to multiples of 2, not 8)."""
        pow2 = 1 << max(k - 1, 0).bit_length()
        if self._ndev > 1:
            per_dev = -(-k // self._ndev)
            return self._ndev * (1 << max(per_dev - 1, 0).bit_length())
        return pow2

    def _pad_stack(self, batch_list: List):
        """Pad to the bucket size, record stats, stack host-side."""
        k = len(batch_list)
        bucket = self._bucket(k)
        self.stats["cohort_calls"] += 1
        self.stats["clients"] += k
        self.stats["max_cohort"] = max(self.stats["max_cohort"], k)
        self.stats["padding_waste"] += bucket - k
        with span("engine.stack", rows=k, bucket=bucket):
            padded = list(batch_list) + [batch_list[-1]] * (bucket - k)
            return _stack(padded), k, bucket

    def update_cohort(self, params, batch_list: List,
                      cstate_list: Optional[List] = None) -> DeltaBank:
        """Run the local update rule for every client in the cohort.

        ``batch_list``: one 3Q-leading-dim batch pytree per client (the raw
        ``sample_batches`` output).  Returns a :class:`DeltaBank` over the
        per-client deltas in the same order — the stacked buffer stays on
        device; iterate / ``row(i)`` for host materialization.  All clients
        are computed against the same ``params`` — the caller guarantees no
        server apply happened inside the cohort's window.

        Stateful strategies pass ``cstate_list`` — one dispatch-ready
        client-state pytree per client, stacked along the cohort axis and
        threaded through the same vmap/map/shard_map call; updated states
        come back on the bank (:meth:`DeltaBank.client_state`).
        """
        if self.stateful != (cstate_list is not None):
            raise ValueError("cstate_list must be given exactly when the "
                             "engine's strategy is stateful")
        k = len(batch_list)
        if k == 0:
            return self._emit(DeltaBank(rows=[], stats=self.stats))
        if not self.vectorized:
            self.stats["cohort_calls"] += 1
            self.stats["clients"] += k
            self.stats["max_cohort"] = max(self.stats["max_cohort"], k)
            if cstate_list is not None:
                shared = self.strategy.shared_state()
                outs = [self._jit_one_s(params, b, c, shared)
                        for b, c in zip(batch_list, cstate_list)]
                return self._emit(DeltaBank(
                    rows=[d for d, _ in outs], stats=self.stats,
                    cstate_rows=[c for _, c in outs]))
            return self._emit(DeltaBank(rows=[self._jit_one(params, b)
                                              for b in batch_list],
                                        stats=self.stats))
        if cstate_list is not None:
            stacked, k, bucket = self._pad_stack(batch_list)
            padded_cs = list(cstate_list) + \
                [cstate_list[-1]] * (bucket - len(cstate_list))
            cstacked = jax.tree.map(lambda *xs: jnp.stack(xs), *padded_cs)
            with span("engine.cohort", bucket=bucket):
                deltas, new_cs = self._jit_cohort_s(
                    params, stacked, cstacked, self.strategy.shared_state())
            return self._emit(DeltaBank(stacked=deltas, k=k,
                                        stats=self.stats, cstates=new_cs))
        stacked, k, bucket = self._pad_stack(batch_list)
        with span("engine.cohort", bucket=bucket):
            deltas = self._jit_cohort(params, stacked)
        return self._emit(DeltaBank(stacked=deltas, k=k, stats=self.stats))

    def update_cohort_mean(self, params, batch_list: List):
        """Cohort deltas reduced to their mean (sync FedAvg-family rounds).

        Padding clients are masked out of the reduction; in shard_map mode
        the mask-weighted sum happens inside the sharded region and the
        cross-device reduction is a single psum per leaf.
        """
        k = len(batch_list)
        if k == 0:
            raise ValueError("cohort mean over an empty batch_list")
        if not self.vectorized:
            deltas = list(self.update_cohort(params, batch_list))
            return jax.tree.map(lambda *xs: sum(xs) / k, *deltas)
        if self._jit_cohort_sum is not None:
            stacked, k, bucket = self._pad_stack(batch_list)
            mask = np.zeros(bucket, np.float32)
            mask[:k] = 1.0 / k
            return self._jit_cohort_sum(params, stacked, jnp.asarray(mask))
        stacked, k, _ = self._pad_stack(batch_list)
        deltas = self._jit_cohort(params, stacked)
        return jax.tree.map(lambda x: jnp.mean(x[:k], axis=0), deltas)
