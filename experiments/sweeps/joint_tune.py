"""Joint self-stopping tune (the `repro.tune` subsystem's flagship driver,
closing the ROADMAP "convergence hillclimb + self-stopping sweep harness"
item): ``buffered(M in {1,4,8})`` × {persafl-B, persafl-C, scaffold,
fedprox} on the fig2 MNIST/CIFAR configurations, at equal simulated time.

Per dataset, the sweep runs the SAME fingerprinted grid twice through
:class:`repro.tune.TuneRunner`:

  * **exhaustive** — every arm to the full simulated-time budget T (set by
    a reference run of persafl-B/buffered(1) to ``ROUNDS`` server rounds);
  * **selfstop**  — identical arms under the default stop-rule bundle
    (loss-spike abort, running-median loss watch, accuracy-plateau
    patience) checked live through ``FLRun.run(on_eval=...)``.

Because arms share seed-paired client/delay streams, a self-stopped trial
is a bit-exact prefix of its exhaustive twin — the comparison isolates
exactly what early stopping gives up.  Gates (recorded in the JSON and
enforced):

  * the selfstop grid selects the same (strategy, schedule) winner per
    dataset as the exhaustive grid;
  * zero host materializations across every arm (all-buffered grids never
    move per-client deltas to the host);
  * full run only: the selfstop grid's total simulated time is ≤ 60% of
    the exhaustive grid's budget.

Artifacts: ``experiments/sweeps/joint_tune.json`` + ``joint_tune.md``
(fig2-style table), ``experiments/sweeps/joint_tune_journal.jsonl`` (the
resumable trial journal — re-running skips completed arms),
and ``examples/tuned/fig2_winners.json`` (the promoted winning configs,
which ``examples/run_tuned.py`` replays).

    PYTHONPATH=src python experiments/sweeps/joint_tune.py

Env: SWEEP_FAST=1 shrinks the grid/rounds for the CI smoke pass;
SWEEP_FRESH=1 deletes the journal first (forces a from-scratch run).
"""
from __future__ import annotations

import json
import os
import time

import jax

from repro.configs.paper_models import CIFAR_CNN, MNIST_CNN
from repro.core import PersAFLConfig
from repro.data import make_federated_dataset
from repro.fl import make_personalized_eval
from repro.models.cnn import cnn_accuracy, cnn_loss, init_cnn
from repro.tune import (AnyOf, Arm, LossSpike, SweepSpec, TuneRunner,
                        default_rules, make_report, promote_winners,
                        to_markdown)

FAST = bool(int(os.environ.get("SWEEP_FAST", "0")))
OUT = os.path.join("experiments", "sweeps")
JOURNAL = os.path.join(OUT, "joint_tune_journal.jsonl")
WINNERS = os.path.join("examples", "tuned", "fig2_winners.json")

DATASETS = ("mnist", "cifar")
ROUNDS = 24 if FAST else 48            # reference-run budget, server rounds
EVALS = 6 if FAST else 12              # eval grid points per budget
STRATEGIES = ({"name": "persafl", "option": "B"},
              {"name": "fedprox"}) if FAST else \
             ({"name": "persafl", "option": "B"},
              {"name": "persafl", "option": "C"},
              {"name": "scaffold"},
              {"name": "fedprox"})
SCHEDULES = ("buffered(1)", "buffered(8)") if FAST else \
            ("buffered(1)", "buffered(4)", "buffered(8)")
# Stop rules.  FAST (the CI smoke) aborts on divergence only: 24-round
# traces are pure noise for plateau/median watches — any constant safe
# for the late-blooming winners stops nothing, and any constant that
# stops something kills a winner (the plateau/median rules are pinned
# deterministically in tests/test_tune.py instead).  The full run's
# constants were calibrated by replaying candidates over the journaled
# exhaustive traces (the selfstop trial is a bit-exact prefix of its
# exhaustive twin, so the replay predicts the live run exactly): a
# demanding plateau watch (+0.05 acc per 2 evals) stops every arm at
# ~45-51% of the budget, where the ranking already agrees with the
# full grid — selection is cheap, and the promoted winner is replayed
# at full budget by examples/run_tuned.py.
RULES = AnyOf((LossSpike(factor=3.0, warmup=1),)) if FAST else \
    default_rules(window=4, median_factor=1.2, spike_factor=3.0,
                  patience=2, min_delta=0.05, warmup=2)


def _problem(kind: str):
    """One problem closure per dataset: data/params/eval built lazily on
    the first live arm (a fully-resumed re-run never builds anything)
    and shared by every arm (the jitted eval amortizes across the
    grid)."""
    cache = {}

    def build(arm):
        if not cache:
            cpc = 5 if kind == "mnist" else 3  # §5: c=5 MNIST, c=3 CIFAR
            ccfg = MNIST_CNN if kind == "mnist" else CIFAR_CNN
            clients = make_federated_dataset(kind, n_clients=10,
                                             classes_per_client=cpc,
                                             seed=0)
            params = init_cnn(ccfg, jax.random.PRNGKey(0))
            loss = lambda p, b: cnn_loss(ccfg, p, b, train=False)  # noqa
            acc = lambda p, b: cnn_accuracy(ccfg, p, b)            # noqa
            cache.update(
                clients=clients, loss_fn=loss, init_params=params,
                eval_fn=make_personalized_eval(loss, acc, clients,
                                               ft_steps=1, ft_lr=0.01,
                                               with_loss=True),
                pcfg=PersAFLConfig(option="A", q_local=5, eta=0.002,
                                   alpha=0.01, lam=25.0, inner_steps=5,
                                   inner_eta=0.02),
                batch_size=16, eval_every=max(ROUNDS // EVALS, 1))
        return cache

    return build


def main():
    if bool(int(os.environ.get("SWEEP_FRESH", "0"))) \
            and os.path.exists(JOURNAL):
        os.remove(JOURNAL)
    all_trials, gates, per_ds = [], {}, {}
    wall0 = time.time()
    for ds in DATASETS:
        problem = _problem(ds)
        # reference run pins the dataset's simulated-time budget T
        ref = TuneRunner(problem, journal=JOURNAL).run_arm(Arm(
            strategy="persafl", strategy_kwargs={"option": "B"},
            schedule="buffered(1)", seed=0, budget=None,
            max_rounds=ROUNDS, group=f"{ds}/ref"))
        budget = ref.sim_time
        grid = dict(strategies=STRATEGIES, schedules=SCHEDULES, seeds=(0,))
        arms_ex = SweepSpec(group=f"{ds}/exhaustive", **grid).arms(
            max_rounds=8 * ROUNDS, budget=budget)
        arms_ss = SweepSpec(group=f"{ds}/selfstop", **grid).arms(
            max_rounds=8 * ROUNDS, budget=budget)

        t0 = time.time()
        ex = TuneRunner(problem, journal=JOURNAL,
                        verbose=True).run_sweep(arms_ex)
        wall_ex = time.time() - t0
        t0 = time.time()
        ss = TuneRunner(problem, journal=JOURNAL, stop_rule=RULES,
                        verbose=True).run_sweep(arms_ss)
        wall_ss = time.time() - t0

        spent_ex = sum(t.sim_time for t in ex)
        spent_ss = sum(t.sim_time for t in ss)
        frac = spent_ss / max(spent_ex, 1e-9)
        win_ex = min(ex, key=lambda t: (-t.final_acc, t.sim_time))
        win_ss = min(ss, key=lambda t: (-t.final_acc, t.sim_time))
        match = (win_ex.arm.strategy, dict(win_ex.arm.strategy_kwargs),
                 win_ex.arm.schedule) == \
                (win_ss.arm.strategy, dict(win_ss.arm.strategy_kwargs),
                 win_ss.arm.schedule)
        per_ds[ds] = {
            "budget": budget, "cost_fraction": frac,
            "sim_spent_exhaustive": spent_ex, "sim_spent_selfstop": spent_ss,
            "wall_exhaustive_s": wall_ex, "wall_selfstop_s": wall_ss,
            "n_stopped": sum(1 for t in ss if t.status == "stopped"),
            "n_arms": len(ss),
            "winner_exhaustive": win_ex.arm.name,
            "winner_selfstop": win_ss.arm.name,
            "winner_acc_exhaustive": win_ex.final_acc,
            "winner_acc_selfstop": win_ss.final_acc,
        }
        gates[f"winner_match_{ds}"] = bool(match)
        if not FAST:
            gates[f"cost_fraction_{ds}"] = frac <= 0.6
        all_trials += [ref] + ex + ss
        print(f"dataset,{ds},budget,{budget:.0f},frac,{frac:.2f},"
              f"winner_ex,{win_ex.arm.name},winner_ss,{win_ss.arm.name}",
              flush=True)

    gates["host_materializations_zero"] = all(
        t.host_materializations == 0 for t in all_trials)
    gates["params_finite"] = all(t.params_finite for t in all_trials)

    report = make_report(all_trials)
    result = {"fast": FAST, "rounds": ROUNDS, "per_dataset": per_ds,
              "gates": gates, "stop_rules": RULES.to_dict(),
              "n_trials": report["n_trials"],
              "n_stopped": report["n_stopped"],
              "n_resumed": report["n_resumed"],
              "wall_s": time.time() - wall0,
              "report": report}
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, "joint_tune.json"), "w") as f:
        json.dump(result, f, indent=2, sort_keys=True)
    with open(os.path.join(OUT, "joint_tune.md"), "w") as f:
        f.write(to_markdown(
            report, title="Joint self-stopping tune "
            "(buffered(M) x strategy, fig2 configs, equal simulated time)"))
    promote_winners(
        {"groups": {g: v for g, v in report["groups"].items()
                    if g.endswith("/selfstop")}},
        WINNERS, extra={"source": "experiments/sweeps/joint_tune.py",
                        "fast": FAST, "rounds": ROUNDS})
    for gate, ok in gates.items():
        print(f"gate,{gate},{ok}")
    bad = [g for g, ok in gates.items() if not ok]
    if bad:
        raise RuntimeError(f"joint_tune gates failed: {bad} "
                           f"({json.dumps(per_ds, default=float)})")


if __name__ == "__main__":
    main()
