"""`repro.tune` (PR 9): SweepSpec JSON round-trip + fingerprint stability,
StopRules vs hand-built traces, journal resume, the `on_eval` stop hook
halting FLRun with a well-formed History, `final_eval` correctness (the
pre-fix stale-`hist.acc[-1]` read), paired client/delay streams across
strategies, and the hillclimb promotion ladder."""
import dataclasses
import json
import math
import os
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import PersAFLConfig
from repro.data.federated import ClientData
from repro.fl import DelayModel, FLRun, buffered, immediate
from repro.fl.api import _normalize_eval
from repro.fl.scenario import ScenarioSpec, Tier
from repro.tune import (AccPlateau, AnyOf, Arm, LossSpike, MedianLoss,
                        SweepSpec, Trial, TuneRunner, default_rules,
                        make_report, parse_schedule, promote,
                        promote_winners, rule_from_dict, rule_to_dict,
                        rung_arms, to_markdown, trial_key)


# ---------------------------------------------------------------------------
# tiny problem (mirrors tests/test_api.py)
# ---------------------------------------------------------------------------

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


def _eval_fn(clients):
    """Mean test accuracy + loss over clients' test sets (dict return —
    the History records both series)."""
    test = [{"images": c.test_x, "labels": c.test_y} for c in clients]

    def ev(params):
        accs, losses = [], []
        for b in test:
            logits = np.asarray(b["images"] @ np.asarray(params["w"]))
            accs.append(float(np.mean(np.argmax(logits, -1) == b["labels"])))
            losses.append(float(_loss(params, b)))
        return {"acc": float(np.mean(accs)), "loss": float(np.mean(losses))}
    return ev


def _problem_factory(clients=None, **over):
    clients = clients or _clients()
    prob = {"clients": clients, "loss_fn": _loss, "init_params": _params(),
            "eval_fn": _eval_fn(clients), "pcfg": _pcfg(),
            "batch_size": 8, "eval_every": 2}
    prob.update(over)
    return lambda arm: prob


def _arm(**kw):
    base = dict(strategy="persafl", strategy_kwargs={"option": "A"},
                schedule="immediate", seed=0, max_rounds=6, group="g")
    base.update(kw)
    return Arm(**base)


# ---------------------------------------------------------------------------
# SweepSpec / Arm: JSON round-trip + fingerprint stability
# ---------------------------------------------------------------------------

def test_sweepspec_json_roundtrip():
    spec = SweepSpec(
        strategies=({"name": "persafl", "option": "B"},
                    {"name": "fedprox", "mu": 0.3}),
        schedules=("immediate", "buffered(8)"),
        pcfg={"eta": 0.01}, pcfg_grid={"q_local": (2, 4)},
        scenario=ScenarioSpec(n_clients=6, seed=3,
                              tiers=(Tier("fast", 0.5, 0.7),
                                     Tier("slow", 0.5, 1.6))),
        seeds=(0, 1), group="mnist")
    back = SweepSpec.from_json(spec.to_json())
    assert back == spec
    # expansion is the full product, deterministic order
    arms = spec.arms(max_rounds=10, budget=50.0)
    assert len(arms) == 2 * 2 * 2 * 2
    assert arms == spec.arms(max_rounds=10, budget=50.0)


def test_arm_fingerprint_stability_and_sensitivity():
    a = _arm(budget=100.0)
    assert a.fingerprint() == _arm(budget=100.0).fingerprint()
    assert a.fingerprint() == Arm.from_dict(a.to_dict()).fingerprint()
    # every config field moves the fingerprint
    for variant in (_arm(budget=200.0), _arm(seed=1),
                    _arm(schedule="buffered(4)"),
                    _arm(strategy_kwargs={"option": "B"}),
                    _arm(pcfg={"eta": 0.01})):
        assert variant.fingerprint() != a.fingerprint()
    # stop-rule hash extends the key: exhaustive != self-stopped trial
    assert trial_key(a, None) != trial_key(a, default_rules())
    assert trial_key(a, default_rules()) == trial_key(a, default_rules())


def test_sweepspec_validation():
    with pytest.raises(ValueError):
        SweepSpec(strategies=())
    with pytest.raises(ValueError):
        SweepSpec(strategies=({"option": "B"},))        # no name
    with pytest.raises(ValueError, match="unknown strategy"):
        _arm(strategy="fedsgd-of-theseus")
    with pytest.raises(ValueError):
        _arm(schedule="eventually(8)")


def test_parse_schedule_spellings():
    assert type(parse_schedule("immediate")).__name__ == "Immediate"
    b = parse_schedule("buffered(8)")
    assert b.m == 8 and b.robust is None
    b = parse_schedule("buffered(4, robust=clip)")
    assert b.m == 4 and b.robust == "clip"
    b = parse_schedule("buffered(8, robust=trim, trim_frac=0.2)")
    assert b.robust == "trim" and b.trim_frac == 0.2
    assert parse_schedule("sync(10)").m == 10
    # fresh instance per call: policies hold per-run state
    assert parse_schedule("buffered(8)") is not parse_schedule("buffered(8)")


# ---------------------------------------------------------------------------
# stop rules vs hand-built traces
# ---------------------------------------------------------------------------

def _trace(loss=(), acc=()):
    return SimpleNamespace(loss=list(loss), acc=list(acc))


def test_loss_spike_stops_divergence():
    rule = LossSpike(factor=3.0)
    assert rule.check(_trace(loss=[1.0, 0.9, 0.8])) is None
    assert "loss_spike" in rule.check(_trace(loss=[1.0, 0.9, 3.1]))
    assert "non-finite" in rule.check(_trace(loss=[1.0, float("nan")]))
    assert "non-finite" in rule.check(_trace(loss=[1.0, float("inf")]))


def test_median_loss_stops_creep_not_noise():
    rule = MedianLoss(window=4, factor=1.3, warmup=3)
    # steady decline never fires
    assert rule.check(_trace(loss=[1.0, 0.8, 0.7, 0.65, 0.6])) is None
    # creeping back above the running median fires
    assert rule.check(_trace(loss=[1.0, 0.5, 0.5, 0.5, 0.9])) is not None
    # within warmup: silent even on bad losses
    assert rule.check(_trace(loss=[0.5, 2.0])) is None


def test_acc_plateau_patience():
    rule = AccPlateau(patience=3, min_delta=0.01)
    # monotone improver with real slope never stops, at any prefix
    ramp = [0.1 + 0.05 * i for i in range(12)]
    for k in range(1, len(ramp) + 1):
        assert rule.check(_trace(acc=ramp[:k])) is None
    # flat tail fires once patience is exhausted
    flat = [0.1, 0.3, 0.5, 0.501, 0.502, 0.5]
    assert rule.check(_trace(acc=flat)) is not None


def test_monotone_improver_survives_default_bundle():
    rules = default_rules()
    loss = [2.0 / (1 + 0.3 * i) for i in range(20)]
    acc = [0.1 + 0.04 * i for i in range(20)]
    for k in range(1, 21):
        assert rules.check(_trace(loss=loss[:k], acc=acc[:k])) is None


def test_stop_rule_serialization_roundtrip():
    bundle = AnyOf((LossSpike(factor=2.5), MedianLoss(window=5),
                    AccPlateau(patience=4, min_delta=0.01)))
    back = rule_from_dict(json.loads(json.dumps(rule_to_dict(bundle))))
    assert back == bundle
    assert rule_from_dict(None) is None and rule_to_dict(None) is None
    with pytest.raises(ValueError, match="unknown stop rule"):
        rule_from_dict({"kind": "vibes"})


def test_normalize_eval_spellings():
    assert _normalize_eval(0.5) == (0.5, None)
    assert _normalize_eval((0.5, 1.25)) == (0.5, 1.25)
    assert _normalize_eval({"acc": 0.5}) == (0.5, None)
    assert _normalize_eval({"acc": 0.5, "loss": 1.25}) == (0.5, 1.25)
    with pytest.raises(ValueError):
        _normalize_eval((1.0, 2.0, 3.0))


# ---------------------------------------------------------------------------
# on_eval stop hook + final_eval (FLRun integration)
# ---------------------------------------------------------------------------

def test_on_eval_stop_halts_flrun_with_wellformed_history():
    clients = _clients()
    run = FLRun(clients=clients, loss_fn=_loss, init_params=_params(),
                pcfg=_pcfg(), delays=DelayModel(len(clients), seed=1),
                schedule=immediate(), batch_size=8)
    seen = []

    def on_eval(hist):
        seen.append(len(hist.acc))
        return "stop" if len(hist.acc) >= 2 else None

    hist = run.run(max_rounds=500, eval_every=2,
                   eval_fn=_eval_fn(clients), on_eval=on_eval)
    # halted at the second eval, far short of max_rounds
    assert seen == [1, 2]
    assert hist.rounds == [2, 4]
    assert int(np.asarray(run.state.t)) == 4
    # History is well-formed: loss parallel to acc, end_time is the stop
    # time, the active grid is closed out to it and stays monotone
    assert len(hist.loss) == len(hist.acc) == 2
    assert hist.end_time == hist.times[-1] > 0
    assert hist.active_times == sorted(hist.active_times)
    assert hist.active_times[-1] <= hist.end_time


def test_on_eval_stop_halts_sync_rounds():
    clients = _clients()
    run = FLRun(clients=clients, loss_fn=_loss, init_params=_params(),
                pcfg=_pcfg(), delays=DelayModel(len(clients), seed=1),
                schedule=parse_schedule("sync(4)"), batch_size=8)
    hist = run.run(max_rounds=50, eval_every=1, eval_fn=_eval_fn(clients),
                   on_eval=lambda h: "stop")
    assert hist.rounds == [1]
    assert int(np.asarray(run.state.t)) == 1


def test_final_eval_fixes_stale_accuracy_read():
    """Regression (pre-fix failing): eval_every larger than the round
    count used to leave `hist.acc` empty — `hist.acc[-1]` reads crashed
    or, with a mid-grid max_time stop, silently reported a STALE grid
    point.  final_eval=True forces the end-time eval."""
    clients = _clients()

    def mk():
        return FLRun(clients=clients, loss_fn=_loss, init_params=_params(),
                     pcfg=_pcfg(), delays=DelayModel(len(clients), seed=1),
                     schedule=immediate(), batch_size=8)

    # eval_every > rounds: no grid eval ever fires
    hist = mk().run(max_rounds=6, eval_every=100, eval_fn=_eval_fn(clients))
    assert hist.acc == []                      # the pre-fix failure mode
    hist = mk().run(max_rounds=6, eval_every=100, eval_fn=_eval_fn(clients),
                    final_eval=True)
    assert len(hist.acc) == 1 and len(hist.loss) == 1
    assert hist.rounds == [6] and hist.times == [hist.end_time]
    # already-fresh last eval is NOT duplicated (params unchanged since)
    hist = mk().run(max_rounds=6, eval_every=2, eval_fn=_eval_fn(clients),
                    final_eval=True)
    assert hist.rounds == [2, 4, 6]


def test_history_loss_roundtrip_and_backcompat():
    clients = _clients()

    def scalar_ev(params):
        return 0.25                      # legacy scalar contract

    run = FLRun(clients=clients, loss_fn=_loss, init_params=_params(),
                pcfg=_pcfg(), delays=DelayModel(len(clients), seed=1),
                schedule=immediate(), batch_size=8)
    hist = run.run(max_rounds=4, eval_every=2, eval_fn=scalar_ev)
    assert hist.acc == [0.25, 0.25] and hist.loss == []
    d = hist.as_dict()
    assert d["loss"] == [] and d["acc"] == [0.25, 0.25]
    # dict round-trips through History(**d)
    from repro.fl import History
    assert History(**d) == hist


# ---------------------------------------------------------------------------
# paired streams: the contract the tuner's comparisons rely on
# ---------------------------------------------------------------------------

class _RecordingDelays(DelayModel):
    """DelayModel that logs every realized (kind, client, value) draw —
    the run's event timeline is a pure function of this log."""

    def __post_init__(self):
        super().__post_init__()
        self.log = []

    def sample_download(self, i, t=0.0):
        v = super().sample_download(i, t)
        self.log.append(("down", int(i), float(v)))
        return v

    def sample_upload(self, i, t=0.0):
        v = super().sample_upload(i, t)
        self.log.append(("up", int(i), float(v)))
        return v


@pytest.mark.parametrize("schedule_mk", [immediate, lambda: buffered(3)])
def test_paired_streams_bit_identical_across_strategies(schedule_mk):
    """Two FLRuns with different strategies but the same delay seed see
    bit-identical event timelines: delay draws, apply times, and staleness
    sequences all match.  This is the counter-based-stream contract that
    makes the tuner's paired grid cells comparable — a strategy must never
    perturb the event schedule."""
    clients = _clients()
    logs, timelines, staleness = [], [], []
    for strat in ("persafl", "scaffold"):       # stateless vs stateful
        delays = _RecordingDelays(len(clients), seed=7)
        run = FLRun(clients=clients, loss_fn=_loss, init_params=_params(),
                    pcfg=_pcfg(), delays=delays, strategy=strat,
                    schedule=schedule_mk(), batch_size=8, seed=0)
        hist = run.run(max_rounds=9)
        logs.append(delays.log)
        timelines.append([(w["window"], w["time"]) for w in run.window_log])
        staleness.append(hist.staleness)
    assert logs[0] == logs[1]                   # bit-identical draws
    assert timelines[0] == timelines[1]         # identical apply times
    assert staleness[0] == staleness[1]


# ---------------------------------------------------------------------------
# runner: journal resume, self-stopping, hillclimb
# ---------------------------------------------------------------------------

def test_runner_executes_and_journals(tmp_path):
    journal = str(tmp_path / "journal.jsonl")
    runner = TuneRunner(_problem_factory(), journal=journal)
    arms = [_arm(), _arm(schedule="buffered(3)", max_rounds=6)]
    trials = runner.run_sweep(arms)
    assert [t.status for t in trials] == ["completed", "completed"]
    assert all(not t.resumed for t in trials)
    assert all(len(t.acc) == len(t.loss) > 0 for t in trials)
    assert all(t.rounds >= 6 for t in trials)
    # one JSONL row per trial, loadable
    rows = [json.loads(l) for l in open(journal)]
    assert len(rows) == 2
    assert {Trial.from_dict(r).key for r in rows} == {t.key for t in trials}


def test_runner_resumes_by_fingerprint_skip(tmp_path):
    journal = str(tmp_path / "journal.jsonl")
    arms = [_arm(), _arm(seed=1)]
    first = TuneRunner(_problem_factory(), journal=journal).run_sweep(arms)

    # a fresh runner over the same journal executes NOTHING: the problem
    # factory raising proves resume never rebuilds a run
    def exploding_problem(arm):
        raise AssertionError("resumed trial must not re-execute")

    again = TuneRunner(exploding_problem, journal=journal).run_sweep(arms)
    assert all(t.resumed for t in again)
    assert [t.key for t in again] == [t.key for t in first]
    assert [t.final_acc for t in again] == [t.final_acc for t in first]
    # journal grew no new rows
    assert len(open(journal).read().splitlines()) == 2
    # a NEW arm still executes
    t3 = TuneRunner(_problem_factory(), journal=journal).run_arm(
        _arm(schedule="buffered(3)"))
    assert not t3.resumed
    assert len(open(journal).read().splitlines()) == 3


def test_runner_selfstop_kills_diverging_arm(tmp_path):
    """An arm whose pcfg diverges (huge eta) is stopped by the bundle;
    the journal row records the reason and the spent budget is less than
    the exhaustive twin's."""
    journal = str(tmp_path / "journal.jsonl")
    problem = _problem_factory(eval_every=1)
    bad = _arm(pcfg={"eta": 50.0}, max_rounds=40)
    ex = TuneRunner(problem, journal=journal).run_arm(bad)
    ss = TuneRunner(problem, journal=journal,
                    stop_rule=default_rules(warmup=1)).run_arm(bad)
    assert ex.status == "completed"
    assert ss.status == "stopped" and ss.stop_reason
    assert ss.rounds < ex.rounds
    assert ss.sim_time < ex.sim_time
    assert ss.stop_rule is not None          # serialized into the record
    # the stopped trial's trace is a prefix of the exhaustive twin's
    # (paired streams: same arm, same seed, same timeline)
    k = len(ss.acc) - 1                      # last entry is the final eval
    assert ss.times[:k] == ex.times[:k]
    np.testing.assert_allclose(ss.acc[:k], ex.acc[:k])


def test_runner_scenario_arm(tmp_path):
    spec = ScenarioSpec(n_clients=6, seed=2, dropout=0.2)
    t = TuneRunner(_problem_factory(),
                   journal=str(tmp_path / "j.jsonl")).run_arm(
        _arm(scenario=spec, max_rounds=4))
    assert t.status == "completed"
    assert t.stats["dropouts"] >= 0 and "windows" in t.stats


def test_hillclimb_promote_and_ladder(tmp_path):
    # pure promotion: top ceil(n/eta), NaN sorts last, deterministic
    arms = [_arm(seed=s) for s in range(4)]
    kept = promote(list(zip(arms, [0.1, float("nan"), 0.9, 0.5])), eta=2.0)
    assert len(kept) == 2
    assert kept[0] == arms[2] and kept[1] == arms[3]
    assert promote([(arms[0], float("nan"))]) == [arms[0]]  # never empty
    # re-budgeting re-fingerprints
    rb = rung_arms(arms[:1], 123.0)
    assert rb[0].budget == 123.0
    assert rb[0].fingerprint() != arms[0].fingerprint()

    # a 2-rung ladder over a real problem: rung sizes halve, every trial
    # journaled, and resuming the ladder re-executes nothing
    runner = TuneRunner(_problem_factory(), journal=str(tmp_path / "j.jsonl"))
    pop = [_arm(seed=s, max_rounds=200) for s in range(4)]
    rungs = runner.run_hillclimb(pop, budgets=[30.0, 60.0], eta=2.0)
    assert [len(r) for r in rungs] == [4, 2]
    assert all(t.sim_time <= b + 1e-9 for r, b in zip(rungs, [30.0, 60.0])
               for t in r)
    rungs2 = TuneRunner(_problem_factory(),
                        journal=str(tmp_path / "j.jsonl")).run_hillclimb(
        pop, budgets=[30.0, 60.0], eta=2.0)
    assert all(t.resumed for r in rungs2 for t in r)


# ---------------------------------------------------------------------------
# report
# ---------------------------------------------------------------------------

def test_report_and_winner_promotion(tmp_path):
    runner = TuneRunner(_problem_factory(),
                        journal=str(tmp_path / "j.jsonl"))
    trials = runner.run_sweep([
        _arm(group="d/grid"), _arm(group="d/grid", schedule="buffered(3)")])
    rep = make_report(trials)
    g = rep["groups"]["d/grid"]
    assert g["n_arms"] == 2
    accs = [r["final_acc"] for r in g["rows"]]
    assert g["winner"]["final_acc"] == max(accs)
    md = to_markdown(rep)
    assert "d/grid" in md and "winner" in md
    path = str(tmp_path / "winners.json")
    blob = promote_winners(rep, path, extra={"note": "t"})
    assert os.path.exists(path)
    assert blob["winners"]["d/grid"]["strategy"] == \
        g["winner"]["strategy"]
    assert blob["note"] == "t"


def test_run_tuned_example_replays_the_promoted_winners(run_example):
    """``examples/run_tuned.py`` replays ``examples/tuned/fig2_winners.json``
    at its smoke size, with deprecations raised from the example or from
    ``repro`` fatal."""
    run_example("run_tuned.py", timeout=240)
