"""Driver of the ``personalize`` traffic kind: served personalization
through ``repro.serving.PersonalizationServer``.

A closed loop of ``concurrency`` users with no think time: each request is
due when its user's previous reply arrived.  One cohort per aggregation
window, so every iteration is the window's own call,

    submit x C -> flush (micro-batcher, cohort engine, int8 EF banking)
    -> poll x C (device-side head gather, blocked until ready)
    -> advance_window (the ring's fused window apply),

and the apply that closes a window delays the next requests, as it does
in the deployment.  Users are drawn Zipf over the population.

``correct`` replays the first W + 1 windows (W the ring's depth, so the
ring wraps once), which set-up drives through the same call on distinct
streams (returning users included, so error feedback is exercised), with
the plain reference from the seed weights.  It compares the served deltas
(snapshot - head) and the change of the global weights after each window:
per leaf by norm, and as a whole by direction (the cosine of sketches,
``common.sketch``), so a head served to the wrong user or with the wrong
sign fails as well as one of the wrong size.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from bench import common
from bench.reference import personalize as ref_personalize
from bench.reference import ssm_lm


def check_users(concurrency: int, windows: int):
    """Users of the replayed windows: fresh ones, then half returning."""
    out, fresh = [], 0
    prev = []
    for _ in range(windows):
        keep = prev[:concurrency // 2]
        new = [f"check-{fresh + j}" for j in range(concurrency - len(keep))]
        fresh += len(new)
        prev = keep + new
        out.append(list(prev))
    return out


def warm_users(concurrency: int):
    """Windows whose returning users gather error-feedback residuals of
    every count 1..C from one earlier bank, the shapes traffic can hit."""
    users = [f"warm-{j}" for j in range(concurrency)]
    out, fresh = [list(users)], concurrency
    for n in range(concurrency, 0, -1):
        users = users[:n] + [f"warm-{fresh + j}"
                             for j in range(concurrency - n)]
        fresh += concurrency - n
        out.append(list(users))
    return out


class Driver:
    def __init__(self, spec: dict, seed: int):
        self.conf, self.mix, self.seed = spec["config"], spec["traffic"], seed
        self.C = self.mix["concurrency"]
        self.requests = common.Requests(seed, self.conf["traffic_vocab"],
                                        self.mix["stream_len"], self.mix)
        self._next = 0

    # -- the window's own call ----------------------------------------------

    def _serve(self, users):
        """Submit, flush and poll one cohort; returns heads and the time
        they were all ready."""
        s = self.server
        batches = []
        for u in users:
            batches.append(self.requests.batch(u, self._next))
            self._next += 1
        with jax.profiler.TraceAnnotation("bench.submit"):
            tickets = [s.submit(u, b, mode=self.mode)
                       for u, b in zip(users, batches)]
        with jax.profiler.TraceAnnotation("bench.flush"):
            s.flush()
        with jax.profiler.TraceAnnotation("bench.poll"):
            heads = [s.poll(t) for t in tickets]
            jax.block_until_ready(heads)
        return tickets, heads, time.perf_counter()

    def _advance(self):
        with jax.profiler.TraceAnnotation("bench.advance"):
            self.server.advance_window()
            jax.block_until_ready(self.server.params)

    # -- set-up -------------------------------------------------------------

    def setup(self):
        self.cfg = common.program_config(self.conf)
        self.mode = self.conf["personalization"]["mode"]
        w0 = common.make_weights(self.conf, self.seed)
        self.server = common.make_server(self.conf, self.cfg, w0,
                                         max_pending=self.C + 1)
        self.check_windows = check_users(
            self.C, self.conf["serving"]["windows"] + 1)
        self.captured = []
        first = self._next
        for users in self.check_windows:
            snap = self.server.params
            _, heads, _ = self._serve(users)
            deltas = [(common.leaf_norms(snap, minus=h),
                       common.sketch(snap, minus=h)) for h in heads]
            del heads, snap
            self._advance()
            self.captured.append(
                (deltas, (common.leaf_norms(self.server.params, minus=w0),
                          common.sketch(self.server.params, minus=w0))))
        self.check_first = first
        del w0
        for users in warm_users(self.C):
            self._serve(users)
            self._advance()
        pool = self.mix["request_pool"]
        self.users = common.zipf_users(self.seed, pool,
                                       self.mix["population"],
                                       self.mix["user_zipf_s"])

    # -- the measured window --------------------------------------------------

    def window(self, seconds: float) -> dict:
        lat, attempted, failed, it = [], 0, 0, 0
        pool = len(self.users)
        with jax.profiler.TraceAnnotation("bench.window"):
            t0 = due = time.perf_counter()
            while True:
                users = [self.users[(it * self.C + j) % pool]
                         for j in range(self.C)]
                it += 1
                tickets, heads, ready = self._serve(users)
                attempted += len(users)
                failed += sum(t.status != "done" for t in tickets)
                lat.extend([ready - due] * len(users))
                due = ready
                del heads, tickets
                self._advance()
                end = time.perf_counter()
                if end - t0 >= seconds:
                    break
        return {"attempted": attempted, "failed": failed,
                "completed": attempted - failed, "elapsed_s": end - t0,
                "latency_s": lat, "windows": it,
                "observed": self._pinned_banks()}

    def _pinned_banks(self) -> dict:
        """Banks the server's head cache and residual LRU keep alive: head
        banks no longer in the ring (past its horizon), and residual banks
        (which only the LRU holds)."""
        s = self.server
        ring = {id(b) for banks in s.ring._banks.values() for b in banks}
        heads = {id(getattr(h, "qbank", h)) for h, _ in s._heads.values()}
        return {"head_banks_past_horizon": len(heads - ring),
                "residual_banks": len({id(b) for b, _ in
                                       s._residuals.values()})}

    def end_to_end(self, w: dict) -> dict:
        return {"personalize_req_per_s": w["completed"] / w["elapsed_s"],
                "personalize_p95_ms":
                    1e3 * float(np.percentile(w["latency_s"], 95))}

    def counts(self) -> dict:
        from bench.counts import personalize_step, ring_apply
        return {"flops_per_request": personalize_step.flops_per_request(
                    self.conf, self.mix),
                "apply_bytes_per_window": ring_apply.bytes_per_window(
                    self.conf, self.C)}

    def release(self):
        del self.server
        self.server = None

    # -- correct ----------------------------------------------------------------

    def reference(self, rnd=ssm_lm.exact):
        """The plain reference serving the replayed windows; yields, per
        window, the banked deltas' and the weight change's leaf norms and
        sketches."""
        conf, d = self.conf, ssm_lm.Dims.from_config(self.conf)
        p = conf["personalization"]
        w0 = common.make_weights(conf, self.seed)

        def loss(w, batch):
            return ssm_lm.loss(w, batch[0], batch[1], d, rnd)

        ref = ref_personalize.ServedReference(
            w0, lambda w, b: ref_personalize.prox_delta(
                loss, w, b, p["lam"], p["inner_eta"], p["inner_steps"]),
            beta=p["beta"], head_cache=conf["serving"]["head_cache"],
            codec=conf["serving"]["delta_dtype"])
        i = self.check_first
        for users in self.check_windows:
            reqs = []
            for u in users:
                b = self.requests.batch(u, i)
                i += 1
                reqs.append((u, (b["tokens"][0], b["labels"][0])))
            banked = ref.window(reqs)
            deltas = [(common.leaf_norms(x), common.sketch(x))
                      for x in banked]
            del banked
            yield deltas, (common.leaf_norms(ref.w, minus=w0),
                           common.sketch(ref.w, minus=w0))

    def readings(self, got_windows, want_windows) -> dict:
        """Worst over the replayed requests and windows.  The direction of
        a served delta is compared on a user's first request only: a
        returning user's delta carries its error-feedback residual, an
        int8 rounding error that any two computations differing in the
        last bit round differently, so its direction is noise."""
        out = {k: [] for k in ("delta_norm_gap", "delta_cos_dist",
                               "change_norm_gap", "change_cos_dist")}
        seen = set()
        for users, (g_d, g_c), (w_d, w_c) in zip(
                self.check_windows, got_windows, want_windows):
            for u, (g_n, g_s), (w_n, w_s) in zip(users, g_d, w_d):
                out["delta_norm_gap"].append(common.norm_gap(g_n, w_n))
                if u not in seen:
                    out["delta_cos_dist"].append(common.cos_dist(g_s, w_s))
            seen.update(users)
            out["change_norm_gap"].append(common.norm_gap(g_c[0], w_c[0]))
            out["change_cos_dist"].append(common.cos_dist(g_c[1], w_c[1]))
        return {k: common.worst(v) for k, v in out.items()}

    def check(self) -> dict:
        limits = self.conf["limits"]
        got = self.readings(self.captured, list(self.reference()))
        return {k: {"value": v, "limit": limits[k]} for k, v in got.items()}
