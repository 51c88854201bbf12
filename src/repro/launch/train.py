"""PersA-FL training driver.

Two modes:
  * ``--preset paper-mnist|paper-cifar`` — the paper's §5 experiment:
    asynchronous personalized FL over n heterogeneous clients with the
    paper's CNNs, driven by the ``repro.fl.api.FLRun`` event loop.  The
    apply schedule is ``--schedule`` (default: the paper-faithful
    ``immediate``; ``buffered(M)`` closes FedBuff-style windows of M
    deltas through the cohort engine and the fused ``apply_rows`` pass).
  * ``--arch <id> [--smoke]`` — PersA-FL over an assigned LLM architecture
    (reduced config on CPU with --smoke; the full config runs on one TPU
    v5e chip, see ``chip_smoke.py``).

Examples:
  PYTHONPATH=src python -m repro.launch.train --preset paper-mnist \
      --option C --rounds 200
  PYTHONPATH=src python -m repro.launch.train --preset paper-mnist \
      --schedule 'buffered(4)' --rounds 40
  PYTHONPATH=src python -m repro.launch.train --arch mamba2-130m --smoke \
      --rounds 4
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import save_server_state
from repro.configs import get_config, reduce_for_smoke
from repro.configs.paper_models import CIFAR_CNN, MNIST_CNN
from repro.core import PersAFLConfig
from repro.data import make_federated_dataset, synthetic_token_batch
from repro.fl import DelayModel, FLRun, make_personalized_eval
from repro.launch.compile_cache import enable_compile_cache
from repro.models import api
from repro.models.cnn import cnn_accuracy, cnn_loss, init_cnn
from repro.tune.space import parse_schedule


def all_finite(tree) -> bool:
    """True when every leaf of ``tree`` is finite (a device reduction)."""
    return all(bool(jnp.all(jnp.isfinite(x))) for x in jax.tree.leaves(tree))


def run_paper_preset(args) -> dict:
    kind = "mnist" if args.preset == "paper-mnist" else "cifar"
    ccfg = MNIST_CNN if kind == "mnist" else CIFAR_CNN
    cpc = 5 if kind == "mnist" else 3  # c classes per client (paper §5)
    clients = make_federated_dataset(kind, n_clients=args.clients,
                                     classes_per_client=cpc, seed=args.seed)
    params = init_cnn(ccfg, jax.random.PRNGKey(args.seed))
    loss = lambda p, b: cnn_loss(ccfg, p, b, train=False)
    acc = lambda p, b: cnn_accuracy(ccfg, p, b)
    ev = make_personalized_eval(loss, acc, clients, ft_steps=1,
                                ft_lr=args.eta, with_loss=True)
    pcfg = PersAFLConfig(option=args.option, q_local=args.q, eta=args.eta,
                         beta=args.beta, alpha=args.alpha, lam=args.lam,
                         inner_steps=args.inner_steps,
                         maml_mode=args.maml_mode)
    sim = FLRun(clients=clients, loss_fn=loss, init_params=params,
                pcfg=pcfg, delays=DelayModel(args.clients, seed=args.seed,
                                             scale=args.delay_scale),
                strategy="persafl",
                schedule=parse_schedule(args.schedule),
                batch_size=args.batch, seed=args.seed)
    t0 = time.time()
    hist = sim.run(max_rounds=args.rounds,
                   eval_every=args.eval_every, eval_fn=ev, final_eval=True)
    wall = time.time() - t0
    out = {
        "preset": args.preset, "option": args.option, "rounds": args.rounds,
        "schedule": args.schedule, "acc": hist.acc, "loss": hist.loss,
        "times": hist.times, "rounds_series": hist.rounds,
        "mean_active_ratio": float(np.mean(hist.active_ratio)),
        "staleness_max": int(max(hist.staleness)) if hist.staleness else 0,
        "staleness_mean": float(np.mean(hist.staleness)) if hist.staleness else 0,
        "wall_s": wall, "params_finite": all_finite(sim.params),
    }
    os.makedirs(args.out, exist_ok=True)
    ckpt = os.path.join(args.out, f"{args.preset}_opt{args.option}")
    save_server_state(ckpt, sim.state, meta=out)
    with open(ckpt + ".history.json", "w") as f:
        json.dump(out, f, indent=2)
    print(json.dumps({k: v for k, v in out.items()
                      if k not in ("times", "rounds_series")}, indent=2))
    return out


def run_arch(args) -> dict:
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = reduce_for_smoke(cfg)
    pcfg = PersAFLConfig(option=args.option, q_local=args.q, eta=args.eta,
                         lam=args.lam, inner_steps=args.inner_steps,
                         maml_mode=cfg.maml_mode)
    from repro.launch.steps import make_train_step
    params = api.init_params(cfg, jax.random.PRNGKey(args.seed))
    step = jax.jit(make_train_step(cfg, pcfg, n_microbatches=1))
    B, S = (args.batch, args.seq) if args.smoke else (8, 512)
    losses = []
    t0 = time.time()
    loss_of = jax.jit(lambda p, b: api.loss_fn(cfg, p, b))
    for r in range(args.rounds):
        batch = synthetic_token_batch(args.seed + r, B, S, cfg.vocab)
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        if cfg.n_visual_tokens:
            batch["visual"] = jnp.zeros((B, cfg.n_visual_tokens, cfg.d_model),
                                        cfg.activation_dtype)
        if cfg.is_encdec:
            batch["frames"] = jnp.zeros((B, cfg.enc_len, cfg.d_model),
                                        cfg.activation_dtype)
        # paper-faithful: the delta is computed at the (here: current)
        # downloaded params; staleness comes from the event schedule
        params, metrics = step(params, params, batch)
        losses.append(float(loss_of(params, batch)))
        print(f"round {r}: loss={losses[-1]:.4f} "
              f"delta_norm={float(metrics['delta_norm']):.4f}", flush=True)
    out = {"arch": cfg.arch_id, "losses": losses,
           "wall_s": time.time() - t0, "params_finite": all_finite(params)}
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, f"train_{cfg.arch_id}.json"), "w") as f:
        json.dump(out, f, indent=2)
    return out


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default=None,
                    choices=[None, "paper-mnist", "paper-cifar"])
    ap.add_argument("--arch", default=None)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--option", default="C", choices=["A", "B", "C"])
    ap.add_argument("--rounds", type=int, default=200)
    ap.add_argument("--clients", type=int, default=30)
    ap.add_argument("--q", type=int, default=10)
    ap.add_argument("--eta", type=float, default=0.01)
    ap.add_argument("--beta", type=float, default=1.0)
    ap.add_argument("--alpha", type=float, default=0.01)
    ap.add_argument("--lam", type=float, default=30.0)
    ap.add_argument("--inner-steps", type=int, default=10)
    ap.add_argument("--maml-mode", default="full")
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--delay-scale", type=float, default=1.0)
    ap.add_argument("--eval-every", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--schedule", default="immediate",
                    help="--preset apply schedule: 'immediate', "
                         "'buffered(M)' or 'sync(m)'")
    ap.add_argument("--out", default="experiments/train")
    return ap


def main():
    ap = build_parser()
    args = ap.parse_args()
    enable_compile_cache()
    if args.preset:
        run_paper_preset(args)
    elif args.arch:
        run_arch(args)
    else:
        ap.error("need --preset or --arch")


if __name__ == "__main__":
    main()
