"""PersonalizationServer subsystem: head correctness against the direct
personalization functions, bounded-staleness straggler admission against a
hand-rolled oracle, micro-batcher bucketing/shard layout, ring retention,
and the steady-state zero-host-materialization contract."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import PersAFLConfig
from repro.core.maml import personalize_maml
from repro.core.moreau import personalize_me
from repro.serving import MicroBatcher, PersonalizationServer, Ticket


def loss(p, b):
    logits = b["x"] @ p["w"] + p["b"]
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.sum(jax.nn.one_hot(b["y"], 4) * logp, -1))


def user_batch(seed, n=8, d=5):
    rng = np.random.RandomState(seed)
    return {"x": rng.randn(n, d).astype(np.float32),
            "y": rng.randint(0, 4, n).astype(np.int32)}


def _params(seed=0, d=5):
    rng = np.random.RandomState(seed)
    return {"w": jnp.asarray(0.1 * rng.randn(d, 4).astype(np.float32)),
            "b": jnp.zeros((4,))}


def _pcfg(**kw):
    base = dict(option="C", lam=20.0, inner_steps=5, inner_eta=0.05,
                alpha=0.1, beta=0.5)
    base.update(kw)
    return PersAFLConfig(**base)


def _close(a, b, **kw):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=kw.get("rtol", 1e-5),
                                   atol=kw.get("atol", 1e-6))


# -- head correctness ------------------------------------------------------

@pytest.mark.parametrize("cohort_impl", ["auto", "shard_map"])
def test_mode_c_head_equals_prox_solve(cohort_impl):
    params = _params()
    pcfg = _pcfg()
    srv = PersonalizationServer(params, loss, pcfg,
                                cohort_impl=cohort_impl)
    tickets = [srv.submit(f"u{i}", user_batch(i)) for i in range(5)]
    assert all(srv.poll(t) is None for t in tickets)
    srv.flush()
    for i, t in enumerate(tickets):
        ref = personalize_me(loss, params, user_batch(i), pcfg.lam,
                             pcfg.inner_eta, pcfg.inner_steps)
        _close(srv.poll(t), ref)


def test_mode_b_head_equals_one_step_finetune():
    params = _params()
    pcfg = _pcfg()
    srv = PersonalizationServer(params, loss, pcfg, modes=("B",))
    t = srv.submit("u0", user_batch(3), mode="B")
    srv.flush()
    _close(srv.poll(t), personalize_maml(loss, params, user_batch(3),
                                         pcfg.alpha))


def test_stacked_heads_match_rows():
    srv = PersonalizationServer(_params(), loss, _pcfg())
    tickets = [srv.submit(f"u{i}", user_batch(i)) for i in range(4)]
    srv.flush()
    stacked = srv.stacked_heads([t.user for t in tickets])
    for i, t in enumerate(tickets):
        _close(jax.tree.map(lambda x: x[i], stacked), srv.head(t.user))


# -- straggler admission ---------------------------------------------------

def test_straggler_admission_matches_oracle():
    """A request stamped in window t but drained in window t+1 must be
    computed against w_t and re-weighted into window t+1's apply with the
    staleness discount — pinned against a hand-rolled oracle."""
    damping = 0.7
    pcfg = _pcfg(staleness_damping=damping)
    params0 = _params()
    srv = PersonalizationServer(params0, loss, pcfg, windows=3)

    # window 0: two fresh users, applied at the boundary
    srv.submit("a", user_batch(1))
    srv.submit("b", user_batch(2))
    srv.flush()
    # late request queued BEFORE the boundary fires; drained after it
    srv.submit("late", user_batch(3))
    srv.advance_window(flush=False)
    # window 1: one fresh user joins the straggler
    t_late_check = srv.submit("c", user_batch(4))
    srv.advance_window()   # flushes: c fresh (τ=0), late straggler (τ=1)
    assert srv.stats["ring_stragglers"] == 1
    assert srv.stats["ring_dropped"] == 0

    def prox_delta(w, seed):
        theta = personalize_me(loss, w, user_batch(seed), pcfg.lam,
                               pcfg.inner_eta, pcfg.inner_steps)
        return jax.tree.map(lambda a, b: np.asarray(a) - np.asarray(b),
                            w, theta)

    # oracle: window 0 applies a,b at β/2 each
    d_a, d_b = prox_delta(params0, 1), prox_delta(params0, 2)
    params1 = jax.tree.map(
        lambda w, da, db: np.asarray(w) - pcfg.beta / 2 * (da + db),
        params0, d_a, d_b)
    # window 1: fresh c at β/2, late row (computed at w_0!) at
    # β/2·(1+1)^{-damping}
    d_c = prox_delta(params1, 4)
    d_late = prox_delta(params0, 3)
    w_late = pcfg.beta / 2 * (1.0 + 1.0) ** (-damping)
    params2 = jax.tree.map(
        lambda w, dc, dl: w - pcfg.beta / 2 * dc - w_late * dl,
        params1, d_c, d_late)
    _close(srv.params, params2, rtol=1e-5, atol=1e-5)
    # the straggler was still served a head — computed at its stamped w_0
    _close(srv.poll(t_late_check),
           personalize_me(loss, params1, user_batch(4), pcfg.lam,
                          pcfg.inner_eta, pcfg.inner_steps))
    _close(srv.head("late"),
           personalize_me(loss, params0, user_batch(3), pcfg.lam,
                          pcfg.inner_eta, pcfg.inner_steps))


def test_past_tau_max_is_dropped_not_applied():
    srv = PersonalizationServer(_params(), loss, _pcfg(), windows=2)
    assert srv.ring.tau_max == 1
    t = srv.submit("slow", user_batch(0))
    srv.advance_window(flush=False)
    srv.advance_window(flush=False)      # τ = 2 > τ_max = 1
    before = jax.tree.map(np.asarray, srv.params)
    srv.flush()
    assert t.status == "dropped" and t.tau == 2
    assert srv.stats["batcher_dropped"] == 1   # refused pre-cohort: the
    assert srv.stats["ring_dropped"] == 0      # drop never cost a slot
    with pytest.raises(RuntimeError, match="tau_max"):
        srv.poll(t)
    srv.advance_window()
    _close(srv.params, before)           # dropped row never applied


def test_ring_retention_prunes_old_windows():
    srv = PersonalizationServer(_params(), loss, _pcfg(), windows=2)
    srv.submit("u0", user_batch(0))
    srv.flush()
    assert srv.ring.lookup("u0") is not None
    live0 = srv.ring.live_banks
    assert live0 > 0
    for _ in range(3):
        srv.advance_window()
    assert srv.ring.lookup("u0") is None
    assert srv.ring.live_banks == 0      # old windows' banks released


# -- batching --------------------------------------------------------------

def test_micro_batcher_groups_by_mode_and_buckets_pow2():
    srv = PersonalizationServer(_params(), loss, _pcfg(), modes=("B", "C"))
    for i in range(5):
        srv.submit(f"c{i}", user_batch(i), mode="C")
    for i in range(3):
        srv.submit(f"b{i}", user_batch(10 + i), mode="B")
    srv.flush()
    s = srv.stats
    assert s["batcher_drains"] == 1
    assert s["cohort_calls"] == 2        # one per mode group
    # pow2 buckets: 5 -> 8 (waste 3), 3 -> 4 (waste 1)
    assert s["padding_waste"] == 4
    assert s["max_cohort"] == 5


def test_auto_flush_at_max_pending():
    srv = PersonalizationServer(_params(), loss, _pcfg(), max_pending=4)
    tickets = [srv.submit(f"u{i}", user_batch(i)) for i in range(4)]
    assert all(t.status == "done" for t in tickets)   # flushed on the 4th


def test_shard_major_layout_preserves_row_identity():
    """With a sharded batcher layout every user's head must still be the
    user's own solve — placement moves rows, never mixes them."""
    params = _params()
    pcfg = _pcfg()
    srv = PersonalizationServer(params, loss, pcfg)
    srv.batcher.n_shards = 4             # force the shard-major path
    tickets = [srv.submit(f"u{i}", user_batch(i)) for i in range(5)]
    srv.flush()
    # every row of the cohort's stack that holds no request is filler
    rows = jax.tree.leaves(tickets[0].head[0].stacked)[0].shape[0]
    assert srv.stats["padding_waste"] == rows - 5 > 0
    for i, t in enumerate(tickets):
        _close(srv.poll(t), personalize_me(loss, params, user_batch(i),
                                           pcfg.lam, pcfg.inner_eta,
                                           pcfg.inner_steps))
    # stable keying: the same users land in the same shard slots again
    b = MicroBatcher(srv.engines, n_shards=4)
    assert all(b._shard(f"u{i}") == srv.batcher._shard(f"u{i}")
               for i in range(5))


def test_ticket_unknown_mode_rejected():
    srv = PersonalizationServer(_params(), loss, _pcfg(), modes=("C",))
    with pytest.raises(ValueError, match="not enabled"):
        srv.submit("u", user_batch(0), mode="B")
    with pytest.raises(ValueError, match="unknown personalization mode"):
        PersonalizationServer(_params(), loss, _pcfg(), modes=("Z",))


# -- steady-state contract -------------------------------------------------

def test_steady_state_zero_host_materializations():
    """submit → flush → poll/stacked_heads → advance over many windows
    never moves a delta or head to the host."""
    srv = PersonalizationServer(_params(), loss, _pcfg(), windows=3)
    users = [f"u{i}" for i in range(6)]
    for _ in range(5):
        tickets = [srv.submit(u, user_batch(i))
                   for i, u in enumerate(users)]
        srv.flush()
        for t in tickets:
            jax.block_until_ready(jax.tree.leaves(srv.poll(t))[0])
        jax.block_until_ready(
            jax.tree.leaves(srv.stacked_heads(users))[0])
        srv.advance_window()
    assert srv.stats["host_materializations"] == 0
    assert srv.stats["ring_windows"] == 5
    assert int(srv.staleness()["server_rounds"]) == 30
    for leaf in jax.tree.leaves(srv.params):
        assert bool(jnp.all(jnp.isfinite(leaf)))


def test_head_cache_lru_eviction():
    srv = PersonalizationServer(_params(), loss, _pcfg(), head_cache=3)
    tickets = [srv.submit(f"u{i}", user_batch(i)) for i in range(5)]
    srv.flush()
    assert srv.stats["cached_heads"] == 3
    with pytest.raises(KeyError):
        srv.head("u0")                    # evicted from the LRU cache
    # but the TICKET still owns its (bank, row) handle: eviction only
    # affects user-keyed lookups, never an open ticket's own result
    _close(srv.poll(tickets[0]),
           personalize_me(loss, srv.ring.snapshot(0), user_batch(0),
                          _pcfg().lam, _pcfg().inner_eta,
                          _pcfg().inner_steps))
    # a handle-less done ticket (pre-restart construction) falls back to
    # the cache and surfaces the eviction explicitly
    orphan = Ticket(user="u0", mode="C", stamp=0, status="done")
    with pytest.raises(RuntimeError, match="evicted"):
        srv.poll(orphan)
    jax.block_until_ready(jax.tree.leaves(srv.head("u4"))[0])


def test_fairness_cap_bounds_per_user_rows_per_window():
    """A heavy user cannot monopolize the window's apply weight vector:
    rows beyond user_cap are refused pre-cohort and the drop is typed."""
    srv = PersonalizationServer(_params(), loss, _pcfg(), user_cap=2)
    tickets = [srv.submit("heavy", user_batch(i)) for i in range(4)]
    t_light = srv.submit("light", user_batch(9))
    srv.flush()
    assert [t.status for t in tickets] == ["done", "done", "capped",
                                           "capped"]
    assert t_light.status == "done"
    assert srv.stats["batcher_fairness_capped"] == 2
    assert srv.stats["ring_admitted"] == 3        # 2 heavy + 1 light
    with pytest.raises(RuntimeError, match="fairness cap"):
        srv.poll(tickets[2])
    # the cap resets at the window boundary: same user serves again
    srv.advance_window()
    t_next = srv.submit("heavy", user_batch(5))
    srv.flush()
    assert t_next.status == "done"


def test_ring_refusal_surfaces_capped_not_dropped():
    """Regression: a ring-level fairness refusal used to mark the ticket
    "dropped", so poll reported a bogus tau_max violation.  The ring (the
    admission authority) must report WHY it refused, and flush must type
    the ticket accordingly."""
    srv = PersonalizationServer(_params(), loss, _pcfg(), user_cap=1)
    # simulate pre-filter drift (multiple front-ends, restarted batcher):
    # the batcher lets everything through, the ring stays the authority
    srv.batcher.user_cap = None
    t1 = srv.submit("u", user_batch(0))
    t2 = srv.submit("u", user_batch(1))
    srv.flush()
    assert t1.status == "done"
    assert t2.status == "capped"        # pre-PR: "dropped"
    assert srv.stats["ring_fairness_capped"] == 1
    assert srv.stats["ring_dropped"] == 0
    with pytest.raises(RuntimeError, match="fairness cap"):
        srv.poll(t2)                    # pre-PR: raised "tau_max"


def test_ring_admit_row_reports_cause():
    from repro.serving import DeltaRing
    srv = PersonalizationServer(_params(), loss, _pcfg())
    srv.submit("u", user_batch(0))
    srv.flush()
    bank = srv.ring._banks[0][0]
    ring = DeltaRing(_params(), windows=3, user_cap=1)
    assert ring.admit_row("a", bank, 0, 0) == "admitted"
    assert ring.admit_row("a", bank, 0, 0) == "capped"
    assert ring.admit_row("b", bank, 0, 3) == "dropped"  # tau_max = 2
    # the boolean wrapper keeps its contract
    assert ring.admit("c", bank, 0, 1) is True
    assert ring.admit("c", bank, 0, 1) is False
    assert ring.stats == {"windows": 0, "admitted": 2, "stragglers": 1,
                          "dropped": 1, "fairness_capped": 2,
                          "robust_clipped": 0, "robust_trimmed": 0,
                          "robust_nonfinite": 0}


def test_fairness_cap_ring_is_admission_authority():
    """The ring enforces the cap cumulatively across drains within one
    window (the batcher's pre-filter is per-drain bookkeeping)."""
    from repro.serving import DeltaRing
    ring = DeltaRing(_params(), windows=2, user_cap=1)
    srv = PersonalizationServer(_params(), loss, _pcfg())
    srv.submit("u", user_batch(0))
    srv.flush()
    bank = srv.ring._banks[0][0]
    assert ring.admit("u", bank, 0, 0) is True
    assert ring.admit("u", bank, 0, 0) is False   # over cap, same window
    assert ring.stats["fairness_capped"] == 1
    state = ring.advance(srv.state, beta=0.5)
    assert ring.admit("u", bank, 0, 1) is True    # new window, cap reset
    assert state is not None


def test_restart_warm_start_roundtrip(tmp_path):
    """save/restore through repro.checkpoint.store: a restarted server
    keeps its global params, ring snapshots + window counter, and the
    head cache — no empty-ring cold start."""
    pcfg = _pcfg()
    srv = PersonalizationServer(_params(), loss, pcfg, windows=3,
                                user_cap=4)
    users = [f"u{i}" for i in range(4)]
    for w in range(2):
        for i, u in enumerate(users):
            srv.submit(u, user_batch(10 * w + i))
        srv.advance_window()
    heads_before = {u: jax.tree.map(np.asarray, srv.head(u))
                    for u in users}
    path = str(tmp_path / "serve_state")
    srv.save(path)

    srv2 = PersonalizationServer.restore(path, loss, pcfg)
    # global model, window counter, staleness accounting all survive
    _close(srv2.params, srv.params)
    assert srv2.window == srv.window == 2
    assert int(srv2.staleness()["server_rounds"]) \
        == int(srv.staleness()["server_rounds"])
    assert srv2.ring.user_cap == 4
    # ring snapshots survive (straggler requests can still drain)
    assert set(srv2.ring._snapshots) == set(srv.ring._snapshots)
    for w in srv.ring._snapshots:
        _close(srv2.ring.snapshot(w), srv.ring.snapshot(w))
    # the head cache is warm: no re-personalization needed after restart
    assert srv2.stats["cached_heads"] == len(users)
    for u in users:
        _close(srv2.head(u), heads_before[u])
    # and the restored server keeps serving + advancing
    t = srv2.submit("fresh", user_batch(99))
    srv2.advance_window()
    assert t.status == "done"
    assert srv2.window == 3


def test_restart_preserves_ring_stats(tmp_path):
    """Regression: DeltaRing.load restored the window counter but left
    stats["windows"] (and every other ring counter) at zero, skewing any
    per-window serve metric computed after a restart."""
    srv = PersonalizationServer(_params(), loss, _pcfg(), windows=3)
    for w in range(2):
        srv.submit(f"u{w}", user_batch(w))
        srv.advance_window()
    before = dict(srv.ring.stats)
    assert before["windows"] == 2 and before["admitted"] == 2
    path = str(tmp_path / "ring_stats")
    srv.save(path)
    srv2 = PersonalizationServer.restore(path, loss, _pcfg())
    assert srv2.ring.stats == before          # pre-PR: all zeros
    # counters keep accumulating from the restored values
    srv2.submit("fresh", user_batch(9))
    srv2.advance_window()
    assert srv2.ring.stats["windows"] == 3
    assert srv2.ring.stats["admitted"] == 3


def test_ring_load_without_stats_falls_back_to_counter():
    """Pre-stats checkpoints: windows falls back to the window counter
    (the one value the counter implies), the rest stay zero."""
    from repro.serving import DeltaRing
    ring = DeltaRing(_params(), windows=3)
    ring.load({4: _params(), 5: _params()}, 5)
    assert ring.stats["windows"] == 5
    assert ring.stats["admitted"] == 0


def test_restart_with_empty_head_cache(tmp_path):
    srv = PersonalizationServer(_params(), loss, _pcfg())
    srv.advance_window()
    path = str(tmp_path / "empty_state")
    srv.save(path)
    srv2 = PersonalizationServer.restore(path, loss, _pcfg())
    assert srv2.stats["cached_heads"] == 0
    assert srv2.window == 1
    _close(srv2.params, srv.params)


# -- admission-weight duplicate accumulation (bugfix regression) -----------

def test_admission_weights_duplicate_rows_accumulate():
    """Regression: a row admitted twice in one window (user_cap >= 2,
    transport re-submits landing in the same bank slot) used to be
    OVERWRITTEN (`w[idx] = wt`), silently under-applying the duplicate
    while the version counter still advanced per admission."""
    from repro.core import admission_weights
    w = admission_weights(4, [(0, 0), (0, 0)], beta=1.0, count=2)
    np.testing.assert_allclose(w, [1.0, 0.0, 0.0, 0.0])   # pre-fix: 0.5
    # damping composes per admission, not per slot
    w = admission_weights(4, [(1, 0), (1, 2)], beta=1.0, count=2,
                          damping=1.0)
    np.testing.assert_allclose(w[1], 0.5 + 0.5 / 3.0, rtol=1e-6)


def test_duplicate_admission_applies_both_rows():
    """End-to-end through the ring: the SAME (bank, row) admitted twice
    into one window contributes 2·β/count to the apply — pre-fix the
    second admission overwrote the first's weight."""
    from repro.core import init_server_state
    from repro.serving import DeltaRing
    pcfg = _pcfg()
    params0 = _params()
    srv = PersonalizationServer(params0, loss, pcfg)
    srv.submit("u", user_batch(1))
    srv.flush()
    bank = srv.ring._banks[0][0]
    ring = DeltaRing(params0, windows=2, user_cap=2)
    assert ring.admit("u", bank, 0, 0)
    assert ring.admit("u", bank, 0, 0)    # transport re-submit, same slot
    state = ring.advance(init_server_state(params0), beta=pcfg.beta)
    delta = jax.tree.map(lambda x: np.asarray(x[0]), bank.stacked)
    expect = jax.tree.map(lambda w, d: np.asarray(w) - pcfg.beta * d,
                          params0, delta)   # 2 · β/2 · d; pre-fix: β/2 · d
    _close(state.params, expect, rtol=1e-5, atol=1e-6)


# -- per-ticket result handles (stale-ticket aliasing bugfix) ---------------

def test_stale_ticket_keeps_its_own_head():
    """Regression: poll resolved "done" tickets BY USER, so polling an
    older ticket after a newer flush for the same user silently returned
    the newest head.  Each ticket owns its (bank, row) handle."""
    pcfg = _pcfg()
    params = _params()
    srv = PersonalizationServer(params, loss, pcfg)
    t1 = srv.submit("u", user_batch(1))
    srv.flush()
    t2 = srv.submit("u", user_batch(2))
    srv.flush()
    ref1 = personalize_me(loss, params, user_batch(1), pcfg.lam,
                          pcfg.inner_eta, pcfg.inner_steps)
    ref2 = personalize_me(loss, params, user_batch(2), pcfg.lam,
                          pcfg.inner_eta, pcfg.inner_steps)
    _close(srv.poll(t1), ref1)            # pre-fix: aliased to ref2
    _close(srv.poll(t2), ref2)
    _close(srv.head("u"), ref2)           # user-keyed lookup IS the newest


def test_superseded_ticket_fails_explicitly_after_retirement():
    """Once a served ticket's ring window rotates out, its head bank is
    gone — poll must raise a typed superseded-and-retired error, never
    return another flush's head."""
    srv = PersonalizationServer(_params(), loss, _pcfg(), windows=2)
    t_old = srv.submit("u", user_batch(1))
    srv.flush()
    assert t_old.status == "done" and t_old.window == 0
    for _ in range(2):                    # horizon moves past window 0
        srv.submit("u", user_batch(2))
        srv.advance_window()
    with pytest.raises(RuntimeError, match="superseded and retired"):
        srv.poll(t_old)
    assert t_old.head is None             # the bank pin is dropped too


# -- tau_max requested-vs-effective round trip (bugfix regression) ----------

def test_tau_max_clamp_warns_and_preserves_requested():
    from repro.serving import DeltaRing
    with pytest.warns(UserWarning, match="clamped"):
        ring = DeltaRing(_params(), windows=2, tau_max=5)
    assert ring.tau_max == 1              # effective: ring depth bound
    assert ring.tau_max_requested == 5    # requested: preserved


def test_tau_max_roundtrips_requested_not_clamped(tmp_path):
    """Regression: the checkpoint used to persist the CLAMPED tau_max, so
    restoring a shallow-ring checkpoint into a deeper ring silently kept
    the accidentally-tightened bound."""
    path = str(tmp_path / "tau_state")
    with pytest.warns(UserWarning, match="clamped"):
        srv = PersonalizationServer(_params(), loss, _pcfg(), windows=2,
                                    tau_max=5)
    assert srv.ring.tau_max == 1
    srv.save(path)
    # restore into a deeper ring: the REQUESTED bound re-clamps against
    # the new depth (min(5, 8-1) = 5), not the old ring's accident
    srv2 = PersonalizationServer.restore(path, loss, _pcfg(), windows=8)
    assert srv2.ring.tau_max_requested == 5
    assert srv2.ring.tau_max == 5         # pre-fix: stayed 1
    # same-depth restore still warns and re-clamps identically
    with pytest.warns(UserWarning, match="clamped"):
        srv3 = PersonalizationServer.restore(path, loss, _pcfg())
    assert srv3.ring.tau_max == 1 and srv3.ring.tau_max_requested == 5


def test_window_apply_advances_global_model():
    srv = PersonalizationServer(_params(), loss, _pcfg())
    before = jax.tree.map(np.asarray, srv.params)
    srv.submit("u", user_batch(0))
    srv.advance_window()
    moved = sum(float(np.sum(np.abs(np.asarray(a) - b)))
                for a, b in zip(jax.tree.leaves(srv.params),
                                jax.tree.leaves(before)))
    assert moved > 0
    assert int(srv.staleness()["server_rounds"]) == 1
