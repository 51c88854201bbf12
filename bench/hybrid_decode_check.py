#!/usr/bin/env python3
"""Personalized decode of the Zamba2 cut with adapter heads, against the
plain reference, on the chip:

    python3 bench/hybrid_decode_check.py --seed <n> [--users 8] \
        [--prompt 256] [--gen 128]

The configuration of the ``zamba2-7b.personalize`` cell; ``--users``
users are personalized through the server in one cohort (their adapters
are the personal subset), and one ``launch/serve.py``
``_decode_personalized`` call decodes a fresh prompt of each with its
adapter head over the one shared backbone, as the decode cell does for
full-model heads.  The reference personalizes the same users from the
seed weights and runs a full forward over each prompt with its served
tokens; ``logit_gap`` is the widest gap by which a served (greedy) token's
logit lies below the reference's best at its position, as
``bench/drivers/decode.py`` reads it.  One JSON line.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from bench import common, run  # noqa: E402
from bench.drivers import hybrid_personalize as hybrid  # noqa: E402
from bench.reference import personalize as ref_personalize  # noqa: E402
from bench.reference import zamba2  # noqa: E402
from bench.weights_zamba2 import make_weights  # noqa: E402

CELL = "zamba2-7b.personalize"


def served(conf, cfg, seed, requests, users, P, T):
    """Heads personalized through the server, and the tokens one decode
    call serves with them (users x T)."""
    from repro.launch.serve import _decode_personalized
    server = common.make_server(conf, cfg, hybrid.host_weights(conf, seed),
                                max_pending=len(users) + 1,
                                head_cache=len(users))
    mode = conf["personalization"]["mode"]
    tickets = [server.submit(u, requests.batch(u, j), mode=mode)
               for j, u in enumerate(users)]
    server.flush()
    if any(t.status != "done" for t in tickets):
        raise RuntimeError("personalization was refused")
    heads = server.stacked_heads(users)
    prompt = np.stack([requests.stream(u, 10 ** 6 + j, P - 1)
                       for j, u in enumerate(users)])
    toks = _decode_personalized(cfg, heads, jnp.asarray(prompt), P + T, P,
                                params=server.params,
                                spec=server.personal_subset)
    return prompt, np.asarray(jax.device_get(toks))


def logit_gap(conf, seed, requests, users, prompt, toks, P) -> float:
    d = zamba2.Dims.from_config(conf)
    p = conf["personalization"]
    full = make_weights(conf, common.jax_key(seed))
    sub0 = hybrid.subset(full, conf["serving"]["personal_subset"])
    delta = jax.jit(lambda s, b: ref_personalize.prox_delta(
        zamba2.subset_loss(d), s, b, p["lam"], p["inner_eta"],
        p["inner_steps"]))
    logits = jax.jit(lambda w, t: zamba2.logits(w, t, d))
    worst = 0.0
    for j, u in enumerate(users):
        b = requests.batch(u, j)
        head = jax.tree.map(lambda w, x: w - x, sub0,
                            delta(sub0, (full, b["tokens"][0],
                                         b["labels"][0])))
        seq = jnp.asarray(np.concatenate([prompt[j], toks[j]])[:-1])
        lg = logits(zamba2.merge(full, head), seq)[P - 1:]
        gap = jnp.max(lg, axis=-1) - jnp.take_along_axis(
            lg, jnp.asarray(toks[j])[:, None], axis=-1)[:, 0]
        worst = common.worst([worst, common.worst(gap)])
    return worst


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--users", type=int, default=8)
    ap.add_argument("--prompt", type=int, default=256)
    ap.add_argument("--gen", type=int, default=128)
    args = ap.parse_args()
    run.configure_jax()
    spec = run.cell_spec(CELL)
    conf, mix = spec["config"], spec["traffic"]
    cfg = hybrid.program_config(conf)
    requests = common.Requests(args.seed, conf["traffic_vocab"],
                               mix["stream_len"], mix)
    users = [f"user-{u}" for u in range(args.users)]
    prompt, toks = served(conf, cfg, args.seed, requests, users,
                          args.prompt, args.gen)
    gap = logit_gap(conf, args.seed, requests, users, prompt, toks,
                    args.prompt)
    print(json.dumps({"seed": args.seed, "users": args.users,
                      "prompt": args.prompt, "gen": args.gen,
                      "device": jax.devices()[0].device_kind,
                      "tokens": int(toks.size), "logit_gap": gap}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
