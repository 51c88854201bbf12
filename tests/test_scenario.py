"""Scenario engine (PR 8): counter-based delay streams, ChurnModel,
heap-vs-vectorized event parity, the DeviceScheduler, and robust
admission against adversarial rows."""
import heapq
import json
import pathlib
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (PersAFLConfig, bank_row_norms, mask_rows,
                        robust_admission_weights, robust_flush_weights,
                        scale_rows)
from repro.data.federated import ClientData
from repro.fl import (Adversarial, ChurnModel, DelayModel, DeviceScheduler,
                      Diurnal, EventStream, FLRun, ScenarioSpec, Tier,
                      buffered, immediate, sync_barrier)
from repro.fl.delays import hash_u01, hash_u32
from repro.fl.scenario import KIND_DOWN, KIND_UP


def _loss(p, b):
    logits = b["images"] @ p["w"]
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.sum(jax.nn.one_hot(b["labels"], 4) * logp, -1))


def _clients(n, d=5, seed=0):
    rng = np.random.RandomState(seed)
    out = []
    for _ in range(n):
        x = rng.randn(64, d).astype(np.float32)
        y = rng.randint(0, 4, 64).astype(np.int32)
        out.append(ClientData(train_x=x, train_y=y, test_x=x[:8],
                              test_y=y[:8], classes=(0, 1, 2, 3)))
    return out


def _pcfg():
    return PersAFLConfig(option="A", q_local=2, eta=0.05, alpha=0.05,
                         lam=20.0, inner_steps=3, inner_eta=0.02)


def _churn_spec(n, seed=3, **kw):
    base = dict(tiers=(Tier("fast", 0.5, 0.7), Tier("slow", 0.5, 1.6)),
                diurnal=Diurnal(period=40.0, floor=0.3), dropout=0.15)
    base.update(kw)
    return ScenarioSpec(n_clients=n, seed=seed, **base)


# ---------------------------------------------------------------------------
# counter-based hash streams
# ---------------------------------------------------------------------------

def test_hash_np_jnp_bit_parity():
    """The numpy (host schedulers) and jax (device scheduler) backends of
    the counter hash must agree bit-for-bit — this is what lets the
    DeviceScheduler draw the same jitter as the heap."""
    ids = np.arange(257)
    for tag in (1, 2, 5):
        for k in (0, 1, 1000):
            h_np = hash_u32(7, ids, k, tag, np)
            h_j = np.asarray(hash_u32(7, jnp.asarray(ids), k, tag, jnp))
            np.testing.assert_array_equal(h_np, h_j)
            u_np = hash_u01(7, ids, k, tag, np)
            u_j = np.asarray(hash_u01(7, jnp.asarray(ids), k, tag, jnp))
            # u01 uses 24 bits so f32 and f64 represent it exactly
            np.testing.assert_array_equal(u_np.astype(np.float32), u_j)


def test_hash_decorrelates_tags_and_counters():
    u1 = hash_u01(0, np.arange(64), 0, 1)
    u2 = hash_u01(0, np.arange(64), 0, 2)
    u3 = hash_u01(0, np.arange(64), 1, 1)
    assert not np.array_equal(u1, u2)
    assert not np.array_equal(u1, u3)
    assert 0.0 <= u1.min() and u1.max() < 1.0


def test_delay_stream_invariant_to_n_clients():
    """Regression for the shared-RNG bug: client i's realized delay
    sequence must depend only on (seed, i) — never on how many other
    clients exist or how their events interleave."""
    a = DelayModel(8, seed=5)
    b = DelayModel(100, seed=5)
    for i in range(8):
        seq_a = [a.sample_download(i, 0.0) for _ in range(6)] \
            + [a.sample_upload(i, 1.0) for _ in range(6)]
        seq_b = [b.sample_download(i, 0.0) for _ in range(6)] \
            + [b.sample_upload(i, 1.0) for _ in range(6)]
        assert seq_a == seq_b


def test_delay_stream_independent_of_other_clients_draws():
    a = DelayModel(8, seed=5)
    b = DelayModel(8, seed=5)
    for i in range(1, 8):
        for _ in range(4):
            b.sample_download(i, 0.0)
            b.sample_upload(i, 0.0)
    assert a.sample_download(0, 0.0) == b.sample_download(0, 0.0)
    assert a.sample_upload(0, 0.0) == b.sample_upload(0, 0.0)


def test_upload_download_ratio_preserved():
    m = DelayModel(200, seed=0)
    downs = np.array([m.sample_download(i, 0.0) for i in range(200)])
    ups = np.array([m.sample_upload(i, 0.0) for i in range(200)])
    ratio = ups.mean() / downs.mean()
    assert 3.5 < ratio < 6.5


# ---------------------------------------------------------------------------
# ScenarioSpec
# ---------------------------------------------------------------------------

def test_spec_json_roundtrip():
    spec = _churn_spec(32, adversarial=Adversarial(
        frac=0.1, kinds=("scale", "nan"), magnitude=25.0))
    j = spec.to_json()
    json.loads(j)                      # well-formed JSON
    back = ScenarioSpec.from_json(j)
    assert back == spec
    m = back.build()
    assert isinstance(m, ChurnModel)
    assert m.n_clients == 32 and m.dropout == spec.dropout


def test_spec_validation():
    with pytest.raises(ValueError):
        ScenarioSpec(n_clients=0)
    with pytest.raises(ValueError):
        ScenarioSpec(n_clients=4, dropout=1.5)
    with pytest.raises(ValueError):
        ScenarioSpec(n_clients=4, tiers=())
    with pytest.raises(ValueError):
        ScenarioSpec(n_clients=4,
                     adversarial=Adversarial(frac=0.1, kinds=("bogus",)))


def test_churn_model_tiers_and_adversaries_are_hash_assigned():
    m = _churn_spec(4000, adversarial=Adversarial(frac=0.05)).build()
    fast = float(np.mean(m.tier_mult == 0.7))
    assert 0.4 < fast < 0.6            # ~half the population per tier
    adv = len(m.adversary_ids) / 4000
    assert 0.02 < adv < 0.08           # ~5% adversaries
    fac = m.corruption_factors(np.arange(4000))
    assert np.all(fac[np.setdiff1d(np.arange(4000), m.adversary_ids)]
                  == 1.0)
    assert np.all(fac[m.adversary_ids] != 1.0)
    # availability stays in [floor, 1]
    av = m.availability(np.arange(4000), 13.7)
    assert np.all(av >= m.diurnal.floor - 1e-12) and np.all(av <= 1.0)


# ---------------------------------------------------------------------------
# event ordering: heap oracle vs vectorized EventStream
# ---------------------------------------------------------------------------

def _heap_oracle(model, n_events):
    """The per-event heap under the documented (time, client, kind) total
    order — the exact loop FLRun._heap_events runs."""
    heap = []
    for i in range(model.n_clients):
        heapq.heappush(heap, (model.sample_download(i, 0.0), i, KIND_DOWN))
    out = []
    while len(out) < n_events:
        now, i, kind = heapq.heappop(heap)
        if kind == KIND_DOWN:
            dropped = model.drops(i)
            t_up = now + model.sample_upload(i, now)
            if dropped:
                heapq.heappush(
                    heap, (t_up + model.sample_download(i, t_up), i,
                           KIND_DOWN))
            else:
                heapq.heappush(heap, (t_up, i, KIND_UP))
            out.append((now, i, KIND_DOWN, dropped, t_up))
        else:
            heapq.heappush(
                heap, (now + model.sample_download(i, now), i, KIND_DOWN))
            out.append((now, i, KIND_UP, False, now))
    return out


@pytest.mark.parametrize("make", [
    lambda: DelayModel(24, seed=1),
    lambda: _churn_spec(24, adversarial=Adversarial(frac=0.2)).build(),
])
def test_eventstream_bit_equal_to_heap(make):
    """EventStream must emit the heap's exact event tuples — times
    bit-equal (same float64 ops in the same order), same total order."""
    oracle = _heap_oracle(make(), 400)
    stream = EventStream(make(), chunk=3).events()
    got = [next(stream) for _ in range(400)]
    assert got == oracle


def test_event_order_is_time_client_kind():
    """The documented deterministic total order: sorted by (t, i, kind),
    KIND_DOWN before KIND_UP on ties."""
    stream = EventStream(_churn_spec(16).build()).events()
    evs = [next(stream) for _ in range(300)]
    keys = [(t, i, k) for t, i, k, _, _ in evs]
    assert keys == sorted(keys)


def test_eventstream_dropout_suppresses_uploads():
    def frac_up(dropout):
        spec = ScenarioSpec(n_clients=64, seed=2, dropout=dropout)
        stream = EventStream(spec.build()).events()
        evs = [next(stream) for _ in range(800)]
        downs = sum(1 for e in evs if e[2] == KIND_DOWN)
        ups = sum(1 for e in evs if e[2] == KIND_UP)
        return ups / downs
    # warm-up transient (first downloads outnumber landed uploads) keeps
    # the no-dropout ratio a bit under 1; dropout must cut well below it
    f0, f4 = frac_up(0.0), frac_up(0.4)
    assert f0 > 0.8
    assert f4 < f0 - 0.15


# ---------------------------------------------------------------------------
# FLRun: heap scheduler vs device scheduler, bit-equal
# ---------------------------------------------------------------------------

def _flrun(n, schedule, scheduler, delays, rounds=18):
    run = FLRun(clients=_clients(n), loss_fn=_loss,
                init_params={"w": jnp.zeros((5, 4))}, pcfg=_pcfg(),
                delays=delays, strategy="persafl", schedule=schedule,
                batch_size=8, seed=0, scheduler=scheduler)
    hist = run.run(max_rounds=rounds)
    return run, hist


@pytest.mark.parametrize("n", [16, 100])
@pytest.mark.parametrize("make_schedule,make_delays", [
    (immediate, lambda n: DelayModel(n, seed=1)),
    (lambda: buffered(4), lambda n: DelayModel(n, seed=1)),
    (immediate, lambda n: _churn_spec(n).build()),
    (lambda: buffered(4), lambda n: _churn_spec(n).build()),
])
def test_flrun_heap_vs_device_bit_equal(n, make_schedule, make_delays):
    """scheduler="device" replays the heap's exact simulation: identical
    History (times, staleness, active-ratio grid) and identical final
    params, at small and at heap-comfortable n, with and without churn."""
    rh, hh = _flrun(n, make_schedule(), "heap", make_delays(n))
    rd, hd = _flrun(n, make_schedule(), "device", make_delays(n))
    assert hh.as_dict() == hd.as_dict()
    for a, b in zip(jax.tree.leaves(rh.state.params),
                    jax.tree.leaves(rd.state.params)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert rh.stats["dropouts"] == rd.stats["dropouts"]
    assert rh.stats["windows"] == rd.stats["windows"]


def test_flrun_sync_ignores_scheduler_flag():
    rh, hh = _flrun(16, sync_barrier(4), "heap", DelayModel(16, seed=1),
                    rounds=3)
    rd, hd = _flrun(16, sync_barrier(4), "device", DelayModel(16, seed=1),
                    rounds=3)
    assert hh.as_dict() == hd.as_dict()
    for a, b in zip(jax.tree.leaves(rh.state.params),
                    jax.tree.leaves(rd.state.params)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_flrun_scheduler_arg_validated():
    with pytest.raises(ValueError):
        FLRun(clients=_clients(2), loss_fn=_loss,
              init_params={"w": jnp.zeros((5, 4))}, pcfg=_pcfg(),
              delays=DelayModel(2), scheduler="gpu")


def test_flrun_stats_surface():
    run, _ = _flrun(16, buffered(4), "auto", _churn_spec(16).build())
    s = run.stats
    for key in ("scheduler", "windows", "cohort_fill_sum",
                "cohort_fill_max", "mean_cohort_fill", "dropouts",
                "corrupted_rows", "robust_clipped", "robust_trimmed",
                "robust_nonfinite", "cohort_calls",
                "host_materializations"):
        assert key in s, key
    assert s["scheduler"] == "heap"         # auto resolves at 16 clients
    assert s["windows"] > 0
    assert s["mean_cohort_fill"] == pytest.approx(4.0)
    assert run.window_log and run.window_log[0]["window"] == 1


# ---------------------------------------------------------------------------
# robust admission
# ---------------------------------------------------------------------------

def _stack(norm_per_row, d=6):
    """A one-leaf [M, d] stack whose rows have the given L2 norms."""
    m = len(norm_per_row)
    rows = np.zeros((m, d), np.float32)
    for j, nrm in enumerate(norm_per_row):
        rows[j, 0] = nrm
    return {"w": jnp.asarray(rows)}


def test_bank_row_norms_matches_numpy():
    rng = np.random.RandomState(0)
    stack = {"a": jnp.asarray(rng.randn(8, 3, 2).astype(np.float32)),
             "b": jnp.asarray(rng.randn(8, 5).astype(np.float32))}
    got = bank_row_norms(stack)
    want = np.sqrt((np.asarray(stack["a"]).reshape(8, -1) ** 2).sum(1)
                   + (np.asarray(stack["b"]) ** 2).sum(1))
    np.testing.assert_allclose(got, want, rtol=1e-5)


def test_robust_weights_clip_oracle():
    norms = np.array([1.0, 10.0, np.nan, 1.0])
    w, keep, info = robust_admission_weights(
        4, [(0, 0), (1, 0), (2, 0)], norms, beta=1.0, count=2,
        method="clip", clip_norm=2.0)
    assert keep.tolist() == [True, True, False, True]
    assert info == {"clipped": 1, "nonfinite": 1, "trimmed": 0,
                    "clip_norm": 2.0}
    np.testing.assert_allclose(w, [0.5, 0.5 * 2.0 / 10.0, 0.0, 0.0],
                               rtol=1e-6)


def test_robust_weights_clip_self_calibrates_on_median():
    norms = np.array([1.0, 1.0, 1.0, 50.0])
    w, _, info = robust_admission_weights(
        4, [(j, 0) for j in range(4)], norms, beta=1.0, count=4,
        method="clip")
    assert info["clip_norm"] == pytest.approx(2.0)  # 2 x median
    assert info["clipped"] == 1
    np.testing.assert_allclose(w[3], 0.25 * 2.0 / 50.0, rtol=1e-6)
    np.testing.assert_allclose(w[:3], 0.25, rtol=1e-6)


def test_robust_weights_trim_oracle():
    norms = np.array([0.1, 1.0, 1.1, 1.2, 100.0])
    w, keep, info = robust_admission_weights(
        5, [(j, 0) for j in range(5)], norms, beta=1.0, count=5,
        method="trim", trim_frac=0.2)
    assert keep.all()
    assert info["trimmed"] == 2                 # one from each tail
    np.testing.assert_allclose(w, [0.0, 1 / 3, 1 / 3, 1 / 3, 0.0],
                               rtol=1e-6)


def test_robust_weights_trim_always_keeps_one():
    w, _, info = robust_admission_weights(
        2, [(0, 0), (1, 0)], np.array([1.0, 2.0]), beta=1.0, count=2,
        method="trim", trim_frac=0.9)
    assert (w > 0).sum() == 1 or (w > 0).sum() == 2
    assert info["trimmed"] < 2


def test_robust_weights_respect_tau_max_and_damping():
    norms = np.ones(3)
    w, _, _ = robust_admission_weights(
        3, [(0, 0), (1, 5)], norms, beta=1.0, count=2, damping=1.0,
        tau_max=2, method="clip", clip_norm=10.0)
    assert w[1] == 0.0                           # past tau_max
    np.testing.assert_allclose(w[0], 0.5, rtol=1e-6)


def test_robust_flush_calibrates_across_banks():
    """A corrupted row alone in its own bank group must still be caught.

    A buffered flush's rows split across banks (in-flight clients were
    computed in an earlier window's bank).  Calibrating per group, the
    lone corrupted row sets its OWN clip median (never clipped) and a
    1-row group cannot be trimmed at all — robust_flush_weights ranks
    and calibrates over the whole flush instead."""
    honest = types.SimpleNamespace(stacked=_stack([1.0, 1.0, 1.2]),
                                   capacity=3)
    lone = types.SimpleNamespace(stacked=_stack([50.0]), capacity=1)
    groups = {"honest": (honest, [(0, 0), (1, 0), (2, 0)]),
              "lone": (lone, [(0, 1)])}

    per_bank, info = robust_flush_weights(groups, beta=1.0, count=4,
                                          method="clip")
    assert info["clip_norm"] == pytest.approx(2.2)  # 2 x median of ALL 4
    assert info["clipped"] == 1
    w_lone, keep_lone = per_bank["lone"]
    assert keep_lone.all()
    np.testing.assert_allclose(w_lone, [0.25 * 2.2 / 50.0], rtol=1e-6)
    w_honest, _ = per_bank["honest"]
    np.testing.assert_allclose(w_honest, 0.25, rtol=1e-6)
    # the per-group function, for contrast, cannot see the outlier
    _, _, solo = robust_admission_weights(
        1, [(0, 1)], bank_row_norms(lone.stacked), beta=1.0, count=4,
        method="clip")
    assert solo["clipped"] == 0

    # trim: global rank over k=4 norms [1, 1, 1.2, 50], cut=1 per tail
    per_bank, info = robust_flush_weights(groups, beta=1.0, count=4,
                                          method="trim", trim_frac=0.25)
    assert info["trimmed"] == 2
    w_lone, _ = per_bank["lone"]
    assert w_lone[0] == 0.0
    w_honest, _ = per_bank["honest"]
    np.testing.assert_allclose(sorted(w_honest), [0.0, 0.5, 0.5],
                               rtol=1e-6)


def test_mask_rows_neutralizes_nan_rows():
    stack = _stack([1.0, np.nan, 3.0])
    keep = np.array([True, False, True])
    masked = mask_rows(stack, keep)
    arr = np.asarray(masked["w"])
    assert np.isfinite(arr).all()
    np.testing.assert_array_equal(arr[0], np.asarray(stack["w"])[0])
    np.testing.assert_array_equal(arr[2], np.asarray(stack["w"])[2])
    assert (arr[1] == 0).all()


def test_scale_rows_applies_per_row_factors():
    stack = _stack([1.0, 2.0, 3.0])
    out = scale_rows(stack, np.array([1.0, -50.0, np.nan], np.float32))
    arr = np.asarray(out["w"])
    assert arr[0, 0] == 1.0
    assert arr[1, 0] == -100.0
    assert np.isnan(arr[2, 0])


def test_nan_adversaries_poison_plain_but_not_robust():
    """End-to-end: 25% NaN-bombing clients destroy the plain buffered
    flush; clip and trim admissions keep the params finite."""
    spec = ScenarioSpec(n_clients=16, seed=5,
                        adversarial=Adversarial(frac=0.25, kinds=("nan",)))

    def go(schedule):
        run, _ = _flrun(16, schedule, "heap", spec.build(), rounds=24)
        finite = all(np.isfinite(np.asarray(x)).all()
                     for x in jax.tree.leaves(run.state.params))
        return run, finite

    r0, f0 = go(buffered(4))
    r1, f1 = go(buffered(4, robust="clip"))
    r2, f2 = go(buffered(4, robust="trim", trim_frac=0.3))
    assert r0.stats["corrupted_rows"] > 0
    assert not f0
    assert f1 and f2
    assert r1.stats["robust_nonfinite"] > 0
    assert r2.stats["robust_nonfinite"] > 0


def test_buffered_robust_arg_validated():
    with pytest.raises(ValueError):
        buffered(4, robust="median")


# ---------------------------------------------------------------------------
# DeviceScheduler
# ---------------------------------------------------------------------------

def test_device_scheduler_deterministic():
    spec = _churn_spec(512, seed=9)
    a = DeviceScheduler.from_spec(spec, window_len=30.0, cohort_cap=64)
    b = DeviceScheduler.from_spec(spec, window_len=30.0, cohort_cap=64)
    for _ in range(4):
        ia, ta = a.next_window()
        ib, tb = b.next_window()
        np.testing.assert_array_equal(ia, ib)
        np.testing.assert_array_equal(ta, tb)
    assert a.stats == b.stats


def test_device_scheduler_cohort_matches_eventstream_oracle():
    """First-window cohort = the clients whose first non-dropped upload
    lands inside the window, times f32-close to the float64 EventStream."""
    spec = ScenarioSpec(n_clients=64, seed=4, dropout=0.1)
    window = 60.0
    sched = DeviceScheduler.from_spec(spec, window_len=window,
                                      cohort_cap=64, cycles_per_window=8)
    ids, times = sched.next_window()
    # float64 oracle: replay events, keep first completion per client
    stream = EventStream(spec.build()).events()
    first = {}
    for t, i, kind, dropped, t_up in stream:
        if t >= window:
            break
        if kind == KIND_UP and i not in first:
            first[i] = t
    want = sorted(first.items(), key=lambda kv: kv[1])
    # exclude boundary-ambiguous completions (f32 vs f64 window edge)
    certain = [(i, t) for i, t in want if abs(t - window) > 1e-3]
    got = dict(zip(ids.tolist(), times.tolist()))
    for i, t in certain:
        assert i in got, (i, t)
        assert got[i] == pytest.approx(t, rel=1e-4)


def test_device_scheduler_counts_dropouts_and_overflow():
    spec = ScenarioSpec(n_clients=256, seed=7, dropout=0.3)
    sched = DeviceScheduler.from_spec(spec, window_len=100.0,
                                      cohort_cap=16)
    ids, _ = sched.next_window()
    st = sched.stats
    assert st["dropouts"] > 0
    assert st["arrivals"] > 16
    assert st["overflow_arrivals"] == st["arrivals"] - len(ids)
    assert len(ids) <= 16
    assert sched.window_log[0]["window"] == 1


def test_device_scheduler_1e4_smoke():
    """10^4 clients advance in a handful of jitted window calls; the host
    only ever sees [cohort_cap]-sized vectors."""
    spec = _churn_spec(10_000, seed=11, dropout=0.05)
    sched = DeviceScheduler.from_spec(spec, window_len=25.0,
                                      cohort_cap=512)
    total = 0
    for _ in range(3):
        ids, times = sched.next_window()
        assert len(ids) == len(times) <= 512
        assert np.all(np.diff(times) >= 0)
        total += len(ids)
    assert total > 0
    assert sched.stats["windows"] == 3


def test_delta_ring_robust_survives_nan_rows():
    """The serving ring's window apply under robust admission: a NaN row
    poisons the plain apply, clip/trim drop it and stay finite."""
    import types
    from repro.core import init_server_state
    from repro.serving import DeltaRing
    stack = _stack([1.0, np.nan, 2.0], d=6)
    bank = types.SimpleNamespace(stacked=stack, capacity=3)
    for robust, want_finite in ((None, False), ("clip", True),
                                ("trim", True)):
        ring = DeltaRing({"w": jnp.zeros(6)}, windows=2, robust=robust)
        state = init_server_state({"w": jnp.zeros(6)})
        for user, row in (("a", 0), ("b", 1), ("c", 2)):
            assert ring.admit(user, bank, row, 0)
        state = ring.advance(state, beta=0.5)
        finite = bool(np.isfinite(np.asarray(state.params["w"])).all())
        assert finite == want_finite, robust
        if robust is not None:
            assert ring.stats["robust_nonfinite"] == 1


def test_delta_ring_robust_arg_validated():
    from repro.serving import DeltaRing
    with pytest.raises(ValueError):
        DeltaRing({"w": jnp.zeros(2)}, robust="median")


@pytest.mark.parametrize("n", [1_000, 100_000])
def test_scheduled_windows_apply_without_host_materializations(n):
    """The fleet-scale window path: the DeviceScheduler forms each cohort
    on device, adversaries corrupt rows of a synthetic delta bank, clip
    admission weighs them, and one fused pass applies them. Every window
    sees arrivals, no bank reaches the host, and the params stay finite."""
    from repro.core import (apply_admitted_rows, init_server_state,
                            robust_admission_weights)
    from repro.fl.engine import DeltaBank
    spec = ScenarioSpec(
        n_clients=n, seed=0,
        tiers=(Tier("fast", 0.5, 0.7), Tier("slow", 0.5, 1.6)),
        diurnal=Diurnal(period=400.0, floor=0.25), dropout=0.02,
        adversarial=Adversarial(frac=0.05, kinds=("scale", "sign_flip")))
    model = spec.build()
    sched = DeviceScheduler(model, window_len=30.0, cohort_cap=256,
                            cycles_per_window=8)
    cap, d = sched.cohort_cap, 1 << 12
    state = init_server_state({"w": jnp.zeros(d, jnp.float32)})
    base = {"w": 0.01 * jax.random.normal(jax.random.PRNGKey(0), (cap, d))}
    bank_stats = {}
    corrupted = 0
    for _ in range(3):
        ids, _ = sched.next_window()
        fill = len(ids)
        assert fill > 0
        bank = DeltaBank(stacked=base, k=fill, stats=bank_stats)
        fac = model.corruption_factors(ids)
        vec = np.ones(cap, np.float32)
        vec[:fill] = fac
        corrupted += int(np.sum(fac != 1.0))
        stacked = scale_rows(bank.stacked, vec)
        weights, keep, _ = robust_admission_weights(
            cap, [(j, 0) for j in range(fill)], bank_row_norms(stacked),
            beta=0.1, count=fill, method="clip")
        if not bool(keep.all()):
            stacked = mask_rows(stacked, keep)
        state = apply_admitted_rows(state, stacked, weights, fill,
                                    staleness_max=0, staleness_sum=0.0)
    assert sched.stats["arrivals"] > 0
    assert corrupted > 0
    assert bank_stats.get("host_materializations", 0) == 0
    assert np.isfinite(np.asarray(state.params["w"])).all()


def test_scenario_churn_example_runs_on_the_current_api(run_example):
    """``examples/scenario_churn.py`` at its smoke size, with deprecations
    raised from the example or from ``repro`` fatal."""
    run_example("scenario_churn.py", timeout=90)


def test_churn_robustness_sweep_smoke(run_cli):
    """``experiments/sweeps/churn_robustness.py`` at ``SWEEP_FAST=1``: its
    own gates (adversaries active, robust arms finite) raise on failure."""
    sweep = (pathlib.Path(__file__).resolve().parents[1] / "experiments"
             / "sweeps" / "churn_robustness.py")
    out = run_cli([str(sweep)],
                  timeout=150, env={"SWEEP_FAST": "1"})
    assert "gate,adversaries_active,True" in out
