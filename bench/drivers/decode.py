"""Driver of the ``decode`` traffic kind: personalized decode for returning
users through ``repro.launch.serve._decode_personalized``.

Set-up personalizes ``users`` users once through the server (one cohort)
and stacks their heads, as the serving CLI does; the window then decodes
back-to-back batches for those users, each a fresh prompt of
``prompt_len`` tokens and ``gen_len`` greedy tokens.  The window's call is
the whole batch: the entry point returns only when every token is done.

``correct``: the reference personalizes the same users from the seed
weights and runs a full forward pass over each sampled prompt with its
served tokens; the compared number is the widest gap by which a served
(greedy) token's logit lies below the reference's best at that position.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import common
from bench.reference import personalize as ref_personalize
from bench.reference import ssm_lm


class Driver:
    def __init__(self, spec: dict, seed: int):
        self.conf, self.mix, self.seed = spec["config"], spec["traffic"], seed
        self.U, self.P, self.T = (self.mix["users"], self.mix["prompt_len"],
                                  self.mix["gen_len"])
        self.requests = common.Requests(seed, self.conf["traffic_vocab"],
                                        self.mix["stream_len"], self.mix)
        self.user_ids = [f"user-{u}" for u in range(self.U)]

    def _prompts(self, i: int):
        return np.stack([self.requests.stream(u, 10 ** 6 + i * self.U + j,
                                              self.P - 1)
                         for j, u in enumerate(self.user_ids)])

    def _decode(self, prompt):
        from repro.launch.serve import _decode_personalized
        with jax.profiler.TraceAnnotation("bench.decode"):
            toks = _decode_personalized(self.cfg, self.heads,
                                        jnp.asarray(prompt),
                                        self.P + self.T, self.P)
            return np.asarray(jax.device_get(toks))

    def setup(self):
        self.cfg = common.program_config(self.conf)
        w0 = common.make_weights(self.conf, self.seed)
        # the head cache holds every user of the batch, whose heads are
        # stacked from it (the serving CLI's cache holds 4096)
        server = common.make_server(self.conf, self.cfg, w0,
                                    max_pending=self.U + 1,
                                    head_cache=max(self.U, self.conf[
                                        "serving"]["head_cache"]))
        del w0
        mode = self.conf["personalization"]["mode"]
        tickets = [server.submit(u, self.requests.batch(u, j), mode=mode)
                   for j, u in enumerate(self.user_ids)]
        server.flush()
        if any(t.status != "done" for t in tickets):
            raise RuntimeError("set-up personalization was refused")
        self.heads = jax.block_until_ready(
            server.stacked_heads(self.user_ids))
        del server, tickets
        self.pool = [self._prompts(i) for i in range(self.mix["prompt_pool"])]
        self._decode(self.pool[0])          # compiles every program

    def window(self, seconds: float) -> dict:
        self.served, n = [], 0
        with jax.profiler.TraceAnnotation("bench.window"):
            t0 = time.perf_counter()
            while True:
                i = n % len(self.pool)
                self.served.append((i, self._decode(self.pool[i])))
                n += 1
                end = time.perf_counter()
                if end - t0 >= seconds:
                    break
        return {"attempted": n * self.U, "failed": 0, "batches": n,
                "completed": n * self.U, "elapsed_s": end - t0,
                "tokens": n * self.U * self.T}

    def end_to_end(self, w: dict) -> dict:
        return {"decode_tok_per_s": w["tokens"] / w["elapsed_s"]}

    def counts(self) -> dict:
        from bench.counts import decode_step
        return {"flops_per_sequence": decode_step.flops_per_sequence(
                    self.conf, self.mix)}

    def release(self):
        self.heads = None

    # -- correct ----------------------------------------------------------------

    def sample(self):
        """Served batches compared: a seeded draw, the last one included."""
        g = np.random.default_rng([self.seed, 5])
        n = len(self.served)
        k = min(self.mix["check_batches"], n)
        pick = set(g.choice(n, k, replace=False).tolist()) | {n - 1}
        return [self.served[i] for i in sorted(pick)]

    def _head_fn(self, rnd):
        """The reference's personalized head of one fresh user."""
        conf, d = self.conf, ssm_lm.Dims.from_config(self.conf)
        p = conf["personalization"]

        def head(w, tokens, labels):
            delta = ref_personalize.prox_delta(
                lambda ww, bb: ssm_lm.loss(ww, bb[0], bb[1], d, rnd), w,
                (tokens, labels), p["lam"], p["inner_eta"], p["inner_steps"])
            return ref_personalize.served_head(
                w, delta, conf["serving"]["delta_dtype"])
        return jax.jit(head)

    def readings(self, control: bool = False) -> dict:
        """Widest gap over the sample between the reference's best logit
        and the logit of the token served; with ``control``, also of the
        token the float8 reference (its own heads) puts first.  One user
        at a time, so a user's reference head is the only one held."""
        d = ssm_lm.Dims.from_config(self.conf)
        w0 = common.make_weights(self.conf, self.seed)
        heads = {"program": self._head_fn(ssm_lm.exact)}
        fwd = {"program": jax.jit(lambda w, t: ssm_lm.logits(w, t, d))}
        if control:
            heads["control"] = self._head_fn(ssm_lm.fp8)
            fwd["control"] = jax.jit(
                lambda w, t: ssm_lm.logits(w, t, d, ssm_lm.fp8))
        worst = {k: 0.0 for k in heads}
        sample = self.sample()
        for j, u in enumerate(self.user_ids):
            b = self.requests.batch(u, j)
            h = {k: f(w0, b["tokens"][0], b["labels"][0])
                 for k, f in heads.items()}
            for i, toks in sample:
                full = jnp.asarray(np.concatenate([self.pool[i][j],
                                                   toks[j]])[:-1])
                lg = fwd["program"](h["program"], full)[self.P - 1:]
                best = jnp.max(lg, axis=-1)
                for k in worst:
                    pick = jnp.asarray(toks[j]) if k == "program" else \
                        jnp.argmax(fwd[k](h[k], full)[self.P - 1:], axis=-1)
                    gap = best - jnp.take_along_axis(lg, pick[:, None],
                                                     axis=-1)[:, 0]
                    worst[k] = common.worst([worst[k], common.worst(gap)])
            del h
        return worst

    def check(self) -> dict:
        return {"logit_gap": {"value": self.readings()["program"],
                              "limit": self.conf["limits"]["logit_gap"]}}
