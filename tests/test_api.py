"""Strategy/Scheduler API (PR 4): registry construction, FedProx /
SCAFFOLD cohort-path vs old sequential-path parity, the typed ServerState
pytree, and the PR-10 removal breadcrumbs for the retired simulator
shims."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (PersAFLConfig, ServerState, init_server_state,
                        apply_update)
from repro.data.federated import ClientData, sample_batches
from repro.fl import (CohortEngine, DelayModel, FLRun, History, Strategy,
                      buffered, immediate, register_strategy, strategy,
                      strategy_names, sync_barrier)
from repro.fl.algorithms import fedprox_update, scaffold_update
from repro.fl.api import resolve_schedule, resolve_strategy
from repro.kernels.fused_update.ops import apply_delta_tree


def _loss(p, b):
    logits = b["images"] @ p["w"]
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.sum(jax.nn.one_hot(b["labels"], 4) * logp, -1))


def _clients(n=6, d=5, seed=0):
    rng = np.random.RandomState(seed)
    out = []
    for _ in range(n):
        x = rng.randn(64, d).astype(np.float32)
        y = rng.randint(0, 4, 64).astype(np.int32)
        out.append(ClientData(train_x=x, train_y=y, test_x=x[:8],
                              test_y=y[:8], classes=(0, 1, 2, 3)))
    return out


def _params(d=5):
    return {"w": jnp.zeros((d, 4))}


def _pcfg(**kw):
    base = dict(option="A", q_local=2, eta=0.05, alpha=0.05, lam=20.0,
                inner_steps=3, inner_eta=0.02)
    base.update(kw)
    return PersAFLConfig(**base)


def _leaves_equal(a, b, **kw):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), **kw)


def _run(strategy_spec, schedule, *, rounds=6, seed=0, pcfg=None,
         clients=None, **kw):
    clients = clients if clients is not None else _clients()
    run = FLRun(clients=clients, loss_fn=_loss, init_params=_params(),
                pcfg=pcfg or _pcfg(), delays=DelayModel(len(clients), seed=1),
                strategy=strategy_spec, schedule=schedule, batch_size=8,
                seed=seed, **kw)
    hist = run.run(max_rounds=rounds)
    return run, hist


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_registry_has_paper_strategies():
    names = strategy_names()
    for nm in ("persafl", "fedavg", "fedasync", "perfedavg", "pfedme",
               "fedprox", "scaffold", "personalize"):
        assert nm in names


def test_registry_unknown_name_raises():
    with pytest.raises(ValueError, match="unknown strategy"):
        strategy("fedsgd-of-theseus")
    with pytest.raises(ValueError, match="unknown schedule"):
        resolve_schedule("eventually")
    with pytest.raises(TypeError):
        resolve_strategy(42)


def test_registry_kwargs_and_option_presets():
    pcfg = _pcfg(option="C")
    s = strategy("fedprox", mu=0.3).bind(pcfg, _loss)
    assert s.mu == 0.3 and s.pcfg.option == "A"
    s = strategy("perfedavg").bind(pcfg, _loss)
    assert s.option == "B"
    s = strategy("persafl").bind(pcfg, _loss)
    assert s.option == "C"       # defaults to the bound pcfg's option
    s = strategy("persafl", option="B").bind(pcfg, _loss)
    assert s.option == "B"


def test_register_strategy_decorator_roundtrip():
    @register_strategy("_test_null")
    class NullStrategy(Strategy):
        name = "_test_null"

        def local_update(self, params, batches, cstate):
            return jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params), None, {}

    run, hist = _run("_test_null", immediate(), rounds=3)
    _leaves_equal(run.state.params, _params())  # zero deltas move nothing
    assert len(hist.staleness) == 3


# ---------------------------------------------------------------------------
# FLRun schedule surfaces (the retired simulators' behavior contracts)
# ---------------------------------------------------------------------------

def test_flrun_immediate_runs_and_stays_on_device():
    run, h = _run("persafl", immediate(), rounds=8)
    assert int(run.final_stats["server_rounds"]) == 8
    assert len(h.staleness) == 8
    for leaf in jax.tree.leaves(run.state.params):
        assert bool(jnp.all(jnp.isfinite(leaf)))


def test_flrun_buffered_runs_and_stays_on_device():
    run, h = _run("persafl", buffered(3), rounds=9)
    assert run.engine.stats["host_materializations"] == 0
    assert int(run.final_stats["server_rounds"]) >= 9
    for leaf in jax.tree.leaves(run.state.params):
        assert bool(jnp.all(jnp.isfinite(leaf)))


def test_flrun_buffered_m_defaults_to_pcfg_buffer_size():
    run, h = _run("persafl", "buffered", rounds=8,
                  pcfg=_pcfg(buffer_size=4))
    assert run.schedule.m is None and run.schedule.m_effective == 4
    assert int(run.final_stats["server_rounds"]) % 4 == 0
    # the policy re-resolves per run instead of freezing the first pcfg
    run2, _ = _run("persafl", run.schedule, rounds=6,
                   pcfg=_pcfg(buffer_size=2))
    assert run2.schedule.m_effective == 2


@pytest.mark.parametrize("algo", ["fedavg", "perfedavg", "pfedme"])
def test_flrun_sync_barrier_runs_every_registry_algo(algo):
    run, h = _run(algo, sync_barrier(3), rounds=3)
    assert int(run.final_stats["server_rounds"]) == 3
    for leaf in jax.tree.leaves(run.state.params):
        assert bool(jnp.all(jnp.isfinite(leaf)))


# ---------------------------------------------------------------------------
# FedProx / SCAFFOLD: cohort path == the old sequential path
# ---------------------------------------------------------------------------

def _legacy_sequential_sync(algo, clients, *, rounds, m, mu=0.1, seed=0):
    """The pre-PR-4 SyncSimulator fedprox/scaffold path: one jitted
    sequential dispatch per client, host-side mean, apply_delta_tree."""
    pcfg = _pcfg()
    rng = np.random.RandomState(seed)
    delays = DelayModel(len(clients), seed=1)
    params = jax.tree.map(jnp.array, _params())
    n = len(clients)
    if algo == "scaffold":
        zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                             params)
        c_global, c_clients = zeros, [zeros for _ in clients]
        jit = jax.jit(lambda p, b, cg, ci: scaffold_update(
            pcfg, _loss, p,
            jax.tree.map(lambda x: x[:pcfg.q_local], b), cg, ci))
    else:
        jit = jax.jit(lambda p, b: fedprox_update(
            pcfg, _loss, p,
            jax.tree.map(lambda x: x[:pcfg.q_local], b), mu=mu))
    for _ in range(rounds):
        sel = rng.choice(n, m, replace=False)
        batches = [sample_batches(clients[i], rng, 3 * pcfg.q_local, 8)
                   for i in sel]
        if algo == "scaffold":
            deltas, c_updates = [], []
            for i, b in zip(sel, batches):
                delta, c_new, _ = jit(params, b, c_global, c_clients[i])
                c_updates.append((i, c_new))
                deltas.append(delta)
        else:
            deltas = [jit(params, b)[0] for b in batches]
        mean = jax.tree.map(lambda *xs: sum(xs) / len(xs), *deltas)
        [delays.sample_download(int(i)) + delays.sample_upload(int(i))
         for i in sel]
        params = apply_delta_tree(params, mean, jnp.float32(pcfg.beta))
        if algo == "scaffold":
            for i, c_new in c_updates:
                old = c_clients[i]
                c_clients[i] = c_new
                c_global = jax.tree.map(
                    lambda cg, cn, co: cg + (cn - co) / n,
                    c_global, c_new, old)
    return params, (c_global if algo == "scaffold" else None)


@pytest.mark.parametrize("algo", ["fedprox", "scaffold"])
def test_cohort_path_matches_legacy_sequential(algo):
    """Acceptance pin: strategy('fedprox'/'scaffold') through the
    CohortEngine (stacked client state, deltas in the DeltaBank) matches
    the retired sequential per-client jit loop on a fixed seed."""
    clients = _clients()
    spec = strategy("fedprox", mu=0.1) if algo == "fedprox" \
        else strategy("scaffold")
    run, _ = _run(spec, sync_barrier(3), rounds=4, clients=clients)
    ref_params, ref_cg = _legacy_sequential_sync(algo, clients, rounds=4,
                                                 m=3)
    _leaves_equal(run.state.params, ref_params, rtol=1e-6, atol=1e-7)
    # deltas landed in the bank, never crossed to the host
    assert run.engine.stats["cohort_calls"] == 4
    assert run.engine.stats["host_materializations"] == 0
    if algo == "scaffold":
        _leaves_equal(run.strategy.c_global, ref_cg, rtol=1e-6, atol=1e-7)


def test_scaffold_client_state_rides_cohort_stack():
    """Stateful dispatch: client states stack over the cohort axis and the
    bank hands updated per-client states back (device gathers)."""
    clients = _clients(4)
    run, _ = _run("scaffold", sync_barrier(4), rounds=2, clients=clients)
    assert run.engine.stateful
    for cs in run._cstates:
        assert cs is not None
        assert jax.tree.structure(cs) == jax.tree.structure(_params())
    # control variates actually moved off zero
    norm = sum(float(jnp.sum(jnp.abs(leaf)))
               for cs in run._cstates for leaf in jax.tree.leaves(cs))
    assert norm > 0


def test_scaffold_runs_under_async_schedules():
    """Beyond the legacy matrix: a stateful strategy under the buffered
    async schedule (impossible pre-PR-4) — deltas stay on device."""
    run, hist = _run("scaffold", buffered(3), rounds=6)
    assert int(run.final_stats["server_rounds"]) >= 6
    assert len(hist.staleness) >= 6
    assert run.engine.stats["host_materializations"] == 0
    for leaf in jax.tree.leaves(run.state.params):
        assert bool(jnp.all(jnp.isfinite(leaf)))


def test_fedprox_mu_zero_matches_fedavg_cohort():
    """μ=0 FedProx is plain local SGD — must coincide with the fedavg
    strategy through the same engine path."""
    r1, _ = _run(strategy("fedprox", mu=0.0), sync_barrier(3), rounds=2)
    r2, _ = _run("fedavg", sync_barrier(3), rounds=2)
    _leaves_equal(r1.state.params, r2.state.params, rtol=1e-6, atol=1e-7)


# ---------------------------------------------------------------------------
# run surface
# ---------------------------------------------------------------------------

def test_max_time_bounds_simulated_time():
    run_full, h_full = _run("persafl", immediate(), rounds=40)
    budget = max(h_full.active_times) / 2
    run_cut = FLRun(clients=_clients(), loss_fn=_loss,
                    init_params=_params(), pcfg=_pcfg(),
                    delays=DelayModel(6, seed=1), strategy="persafl",
                    schedule=immediate(), batch_size=8, seed=0)
    h_cut = run_cut.run(max_rounds=40, max_time=budget)
    assert int(run_cut.final_stats["server_rounds"]) \
        < int(run_full.final_stats["server_rounds"])
    assert all(t <= budget for t in h_cut.active_times)


def test_max_time_clamps_end_time_and_closes_grid():
    """Regression: the event loop used to break only after popping an
    event PAST the budget, so hist.end_time overshot max_time (handing
    equal-simulated-time comparisons extra seconds) and the active-ratio
    grid stopped short of the boundary."""
    run = FLRun(clients=_clients(), loss_fn=_loss, init_params=_params(),
                pcfg=_pcfg(), delays=DelayModel(6, seed=1),
                strategy="persafl", schedule=immediate(), batch_size=8,
                seed=0)
    budget = 23.0
    h = run.run(max_rounds=10_000, max_time=budget,
                record_active_every=1.0)
    # a dense stream guarantees events beyond the budget: the budget binds
    assert h.end_time == budget
    assert h.active_times and max(h.active_times) <= budget
    # the grid is closed out to the boundary, not left at the last event
    assert budget - max(h.active_times) < 1.0
    assert len(h.active_times) == len(h.active_ratio)


def test_run_requires_max_rounds():
    run = FLRun(clients=_clients(2), loss_fn=_loss, init_params=_params(),
                pcfg=_pcfg(), delays=DelayModel(2, seed=1))
    with pytest.raises(TypeError, match="max_rounds"):
        run.run()


def test_history_is_shared_shape_across_schedules():
    for schedule in (immediate(), buffered(2), sync_barrier(2)):
        _, hist = _run("persafl", schedule, rounds=4)
        assert isinstance(hist, History)
        assert hist.active_times and hist.active_ratio


# ---------------------------------------------------------------------------
# ServerState
# ---------------------------------------------------------------------------

def test_server_state_is_pytree_and_dict_compatible():
    state = init_server_state({"w": jnp.zeros(3)})
    assert isinstance(state, ServerState)
    # pytree: leaves in field order, tree.map preserves the type
    leaves = jax.tree.leaves(state)
    assert len(leaves) == 4
    mapped = jax.tree.map(lambda x: x + 1, state)
    assert isinstance(mapped, ServerState)
    assert int(mapped.t) == 1
    # legacy dict-style reads still work
    np.testing.assert_array_equal(np.asarray(state["params"]["w"]),
                                  np.zeros(3))
    assert set(state.keys()) == {"params", "t", "staleness_sum",
                                 "staleness_max"}
    # round-trips through as_dict/from_dict and replace
    assert ServerState.from_dict(state.as_dict()) == state
    assert int(state.replace(t=jnp.int32(7)).t) == 7


def test_server_state_threads_through_jitted_apply():
    state = init_server_state({"w": jnp.zeros(4)})
    delta = {"w": jnp.ones(4)}
    state = apply_update(state, delta, 1.0, 2)
    assert isinstance(state, ServerState)
    assert int(state.t) == 1 and int(state.staleness_max) == 2
    np.testing.assert_allclose(np.asarray(state.params["w"]), -1.0)


def test_old_format_checkpoint_loads_as_server_state(tmp_path):
    """Pre-PR-4 checkpoints were raw dicts — same npz layout, so they load
    straight into the typed state."""
    from repro.checkpoint import load_server_state, save_pytree
    legacy = {"params": {"w": np.arange(3.0, dtype=np.float32)},
              "t": np.int32(5), "staleness_sum": np.float32(2.0),
              "staleness_max": np.int32(1)}
    path = str(tmp_path / "old_state")
    save_pytree(path, legacy)
    back = load_server_state(path)
    assert isinstance(back, ServerState)
    assert int(back.t) == 5
    np.testing.assert_array_equal(back.params["w"], legacy["params"]["w"])


# ---------------------------------------------------------------------------
# PR-10 removals: the PR-4 shims now raise ImportError breadcrumbs
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["AsyncSimulator", "BufferedAsyncSimulator",
                                  "SyncSimulator"])
def test_removed_simulator_names_raise_with_migration_spelling(name):
    import repro.fl
    import repro.fl.simulator
    # both the package re-export and the module attribute name the FLRun
    # spelling to migrate to
    with pytest.raises(ImportError, match="FLRun"):
        getattr(repro.fl, name)
    with pytest.raises(ImportError, match="removed in PR 10"):
        getattr(repro.fl.simulator, name)
    # unknown names still fail the normal way
    with pytest.raises(AttributeError):
        repro.fl.simulator.NotAThing


def test_removed_personalize_delta_fn_raises():
    import repro.serving
    import repro.serving.batcher
    with pytest.raises(ImportError, match="personalize"):
        repro.serving.personalize_delta_fn
    with pytest.raises(ImportError, match="removed in PR 10"):
        repro.serving.batcher.personalize_delta_fn


def test_engine_client_fn_override_removed():
    with pytest.raises(TypeError, match="client_fn.*removed in PR 10"):
        CohortEngine(_pcfg(), _loss,
                     client_fn=lambda p, b: jax.tree.map(
                         lambda x: jnp.zeros_like(x, jnp.float32), p))
    # with a strategy alongside it fails the same way — the kwarg is gone
    with pytest.raises(TypeError, match="client_fn"):
        CohortEngine(_pcfg(), _loss,
                     strategy=strategy("fedavg").bind(_pcfg(), _loss),
                     client_fn=lambda p, b: p)


def test_quickstart_example_runs_on_the_current_api(run_example):
    """``examples/quickstart.py`` at its smoke size, with deprecations
    raised from the example or from ``repro`` fatal."""
    run_example("quickstart.py", timeout=100)
