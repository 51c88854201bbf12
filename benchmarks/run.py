"""Benchmark harness — one function per paper table/figure.

  fig2a_concurrency   — active-client ratio, async vs sync (Figure 2a)
  fig2b_mnist         — 8-algorithm personalized accuracy, hetero MNIST-like
                        within a fixed communication-time budget (Figure 2b)
  fig2c_cifar         — same on CIFAR-like data (Figure 2c)
  table1_staleness    — FedAsync convergence vs maximum delay τ (Table 1's
                        O(1/√T)+O(τ²/T) staleness term, empirically)
  engine              — vectorized cohort engine vs per-event dispatch,
                        32-client buffered-async run (wall-clock speedup,
                        plus padding_waste / host_materializations stats)
  engine_sharded      — shard_map cohort split over 8 forced host devices
                        (or the accelerators present) vs single-device
                        vmap, equality at cohort ≥ 32
  serve               — batched personalization through
                        PersonalizationServer vs per-request loop at 32
                        concurrent users (req/s, zero host materializations)
  serve_transport     — N concurrent socket connections driving submit/poll
                        through TransportServer vs the in-process server
                        path (req/s, p50/p99 latency, ≤1.5x gate, zero
                        host materializations)
  partial             — head-only (personal_subset) serving vs full-model:
                        ring_bytes_per_user ≥ 20x smaller (gated), backbone
                        bit-parity across windows, users/GiB residency row,
                        and a fig2-config convergence pin (|Δacc| ≤ 0.1)
  quant               — int8 delta banking + compressed wire: apply_rows_q
                        kernel parity vs the jnp oracle (gated), ring
                        residency ≥ 3.5x smaller than fp32 banking (gated),
                        SUBMIT/HEAD wire bodies ≥ 3.5x smaller (gated),
                        fig2-config convergence pin fp32 vs int8+EF
                        (|Δacc| ≤ 0.1, gated), host_materializations == 0
  scale               — scenario engine at 10^3→10^6 simulated clients:
                        DeviceScheduler window cost (sub-linear in n,
                        gated), robust admission of adversary-corrupted
                        cohort banks, host_materializations == 0 (gated)
  kernels             — Pallas kernels (interpret) vs jnp oracle, µs/call

Prints ``name,us_per_call,derived`` CSV lines (plus per-figure CSV blocks)
AND appends one machine-readable JSON line per bench run to
``experiments/bench/BENCH_<name>.json`` (JSONL: wall_s, gate results,
measured bytes — CI and sweep scripts parse these instead of scraping
stdout).  Env: BENCH_FAST=1 shrinks rounds for smoke runs.

  PYTHONPATH=src python -m benchmarks.run [--only fig2a,kernels]
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import (ALGOS, FAST, acc_at_time_budget, run_algo,
                               setup)
from repro.launch.compile_cache import enable_compile_cache

OUT_DIR = "experiments/bench"


def _save(name, obj):
    os.makedirs(OUT_DIR, exist_ok=True)
    with open(os.path.join(OUT_DIR, f"{name}.json"), "w") as f:
        json.dump(obj, f, indent=2)


def _bench_log(name, row):
    """Append one machine-readable JSON line for this bench run.

    ``<name>.json`` (:func:`_save`) holds the latest run's full result;
    ``BENCH_<name>.json`` accumulates one JSONL row per run so CI gate
    checks and regression sweeps parse records instead of scraping the
    stdout CSV."""
    os.makedirs(OUT_DIR, exist_ok=True)
    with open(os.path.join(OUT_DIR, f"BENCH_{name}.json"), "a") as f:
        f.write(json.dumps(row, sort_keys=True) + "\n")


def fig2a_concurrency():
    """Figure 2a: proportion of active users, async vs sync."""
    clients, params, loss, acc, ev = setup("mnist", n_clients=30)
    r_async = run_algo("fedasync", clients, params, loss, None,
                       async_rounds=60 if FAST else 150)
    r_sync = run_algo("fedavg", clients, params, loss, None,
                      sync_rounds=6 if FAST else 15)
    print("fig2a,algo,mean_active_ratio")
    print(f"fig2a,async,{r_async['mean_active_ratio']:.3f}")
    print(f"fig2a,sync,{r_sync['mean_active_ratio']:.3f}")
    derived = r_async["mean_active_ratio"] - r_sync["mean_active_ratio"]
    print(f"fig2a_concurrency,{(r_async['wall_s']+r_sync['wall_s'])*1e6:.0f},"
          f"{derived:.3f}")
    _save("fig2a", {"async": r_async, "sync": r_sync})
    return derived


def _figure2(kind: str):
    clients, params, loss, acc, ev = setup(kind, n_clients=30)
    async_rounds = 60 if FAST else 160
    sync_rounds = 8 if FAST else 24
    results = {}
    for algo in ALGOS:
        r = run_algo(algo, clients, params, loss, ev,
                     async_rounds=async_rounds, sync_rounds=sync_rounds)
        results[algo] = r
        print(f"fig2_{kind},{algo},final_acc={r['acc'][-1]:.3f},"
              f"wall={r['wall_s']:.0f}s", flush=True)
    # equal simulated-communication-time budget (paper: fixed time window)
    budget = min(max(r["times"]) for r in results.values() if r["times"])
    print(f"fig2_{kind},time_budget,{budget:.0f}")
    print(f"fig2_{kind},algo,acc_at_budget")
    for algo, r in results.items():
        print(f"fig2_{kind},{algo},{acc_at_time_budget(r, budget):.3f}")
    _save(f"fig2_{kind}", {k: v for k, v in results.items()})
    return results, budget


def fig2b_mnist():
    results, budget = _figure2("mnist")
    ours = max(acc_at_time_budget(results[a], budget)
               for a in ("persafl-maml", "persafl-me"))
    base = max(acc_at_time_budget(results[a], budget)
               for a in ("fedavg", "fedasync", "fedprox", "scaffold"))
    print(f"fig2b_mnist,0,{ours - base:.3f}")
    return results


def fig2c_cifar():
    results, budget = _figure2("cifar")
    ours = max(acc_at_time_budget(results[a], budget)
               for a in ("persafl-maml", "persafl-me"))
    base = max(acc_at_time_budget(results[a], budget)
               for a in ("fedavg", "fedasync", "fedprox", "scaffold"))
    print(f"fig2c_cifar,0,{ours - base:.3f}")
    return results


def table1_staleness():
    """Empirical staleness tolerance: FedAsync accuracy vs delay scale."""
    from repro.core import PersAFLConfig
    from repro.fl import DelayModel, FLRun, immediate
    clients, params, loss, acc, ev = setup("mnist", n_clients=20)
    rounds = 60 if FAST else 120
    rows = []
    for scale in (1.0, 4.0, 16.0):
        pcfg = PersAFLConfig(option="A", q_local=5, eta=0.01)
        sim = FLRun(clients=clients, loss_fn=loss,
                    init_params=params, pcfg=pcfg,
                    delays=DelayModel(len(clients), seed=1, scale=scale,
                                      jitter=(0.2, 3.0)),
                    strategy="fedasync", schedule=immediate(),
                    batch_size=16, seed=0)
        h = sim.run(max_rounds=rounds, eval_every=rounds, eval_fn=ev)
        tau = max(h.staleness) if h.staleness else 0
        rows.append({"delay_scale": scale, "tau_max": tau,
                     "acc": h.acc[-1] if h.acc else 0.0})
        print(f"table1,scale={scale},tau_max={tau},acc={rows[-1]['acc']:.3f}",
              flush=True)
    _save("table1_staleness", rows)
    # derived: accuracy degradation from smallest to largest tau
    print(f"table1_staleness,0,{rows[0]['acc'] - rows[-1]['acc']:.3f}")
    return rows


def engine():
    """Cohort engine speedup: one vmapped call per inter-apply window vs one
    jitted dispatch per client event, same ``FLRun(schedule=buffered(M))``
    schedule.

    Uses the dispatch-bound regime the engine targets — a per-user
    personalized head (logistic model on feature vectors, the serving-side
    workload): at 32+ concurrent clients the per-event path pays O(cohort)
    device round-trips per window, the engine pays one."""
    from repro.core import PersAFLConfig, init_server_state
    from repro.data.federated import ClientData
    from repro.fl import DelayModel, FLRun, buffered

    d, n_clients = 32, 32
    rng = np.random.RandomState(0)
    clients = []
    for _ in range(n_clients):
        x = rng.randn(256, d).astype(np.float32)
        y = rng.randint(0, 10, 256).astype(np.int32)
        clients.append(ClientData(train_x=x, train_y=y, test_x=x[:32],
                                  test_y=y[:32], classes=tuple(range(10))))

    def loss(p, b):
        logits = b["images"] @ p["w"] + p["b"]
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.sum(jax.nn.one_hot(b["labels"], 10) * logp, -1))

    params = {"w": jnp.zeros((d, 10)), "b": jnp.zeros((10,))}
    rounds = 1536 if FAST else 4096
    walls, calls = {}, {}
    for vectorized in (True, False):
        sim = FLRun(
            clients=clients, loss_fn=loss, init_params=params,
            pcfg=PersAFLConfig(option="A", q_local=1, eta=0.05),
            delays=DelayModel(len(clients), seed=1), batch_size=8, seed=0,
            strategy="persafl", schedule=buffered(32),
            vectorized=vectorized)
        def reset():
            # replay the identical schedule every repetition: fresh batch
            # rng + delay streams + server state, so warm-up compiles every
            # cohort bucket the timed runs will see
            sim.rng = np.random.RandomState(0)
            sim.delays = DelayModel(len(clients), seed=1)
            sim.state = init_server_state(jax.tree.map(jnp.array, params))
            sim.engine.stats.update(cohort_calls=0, clients=0, max_cohort=0,
                                    padding_waste=0, host_materializations=0)

        reset()
        sim.run(max_rounds=rounds)                 # warm-up: compiles
        best = float("inf")
        for _ in range(3):                         # best-of-3: 2-vCPU noise
            reset()
            t0 = time.time()
            sim.run(max_rounds=rounds)
            best = min(best, time.time() - t0)
        walls[vectorized] = best
        stats = dict(sim.engine.stats)             # identical per repetition
        calls[vectorized] = max(stats["cohort_calls"], 1)
        path = "vectorized" if vectorized else "per_event"
        print(f"engine,{path},wall_s={walls[vectorized]:.3f},"
              f"cohort_calls={stats['cohort_calls']},"
              f"max_cohort={stats['max_cohort']},"
              f"padding_waste={stats['padding_waste']},"
              f"host_materializations={stats['host_materializations']}",
              flush=True)
    speedup = walls[False] / walls[True]
    print(f"engine,{walls[True] / calls[True] * 1e6:.0f},"
          f"speedup={speedup:.2f}")
    _save("engine", {"wall_vectorized_s": walls[True],
                     "wall_per_event_s": walls[False], "speedup": speedup})
    return speedup


def _delegate_to_cpu_child(name: str, *, cpu_devices: int,
                           min_devices: int) -> bool:
    """Device-count guard for the multi-device rows; True when the row ran
    in a child process and its CSV rows were passed through.

    On a CPU parent with fewer than ``cpu_devices`` devices the row
    re-execs itself under ``--xla_force_host_platform_device_count`` (the
    split must exist before JAX initializes).  On any other backend it
    never falls back to the CPU: it runs on the devices present, or raises
    when there are fewer than ``min_devices``.
    """
    n = jax.device_count()
    backend = jax.default_backend()
    if backend != "cpu":
        if n < min_devices:
            raise RuntimeError(f"{name} needs {min_devices} {backend} "
                               f"devices; this host has {n}")
        return False
    if n >= cpu_devices:
        return False
    if os.environ.get("_BENCH_CPU_CHILD"):
        raise RuntimeError(f"forced {cpu_devices}-device split did not "
                           f"take effect (device_count={n})")
    env = dict(os.environ, JAX_PLATFORMS="cpu", _BENCH_CPU_CHILD="1")
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + f" --xla_force_host_platform_device_count="
                          f"{cpu_devices}").strip()
    res = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--only", name],
        env=env, capture_output=True, text=True)
    rows = [line for line in res.stdout.splitlines()
            if line.startswith(f"{name},")]
    for line in rows:
        print(line, flush=True)
    if res.returncode != 0 or not rows:
        sys.stderr.write(res.stderr[-4000:])
        raise RuntimeError(f"{name} {cpu_devices}-device child failed")
    return True


def engine_sharded():
    """shard_map cohort over every device vs single-device vmap.

    The acceptance row: the sharded path must complete a cohort ≥ 32 run
    with deltas equal to the vmap path (atol ≤ 1e-5).  On a CPU parent it
    runs on 8 forced host devices (see :func:`_delegate_to_cpu_child`); on
    an accelerator it runs on the devices present (at least 2).
    """
    if _delegate_to_cpu_child("engine_sharded", cpu_devices=8,
                              min_devices=2):
        return

    from repro.core import PersAFLConfig
    from repro.fl import CohortEngine

    t_bench0 = time.time()
    d, cohort = 32, 32
    rng = np.random.RandomState(0)

    def loss(p, b):
        logits = b["images"] @ p["w"] + p["b"]
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.sum(jax.nn.one_hot(b["labels"], 10) * logp, -1))

    pcfg = PersAFLConfig(option="A", q_local=4, eta=0.05)
    params = {"w": jnp.zeros((d, 10)), "b": jnp.zeros((10,))}
    batch_list = [{"images": rng.randn(3 * pcfg.q_local, 16, d
                                       ).astype(np.float32),
                   "labels": rng.randint(0, 10, (3 * pcfg.q_local, 16)
                                         ).astype(np.int32)}
                  for _ in range(cohort)]

    engines = {"vmap": CohortEngine(pcfg, loss, cohort_impl="vmap"),
               "shard_map": CohortEngine(pcfg, loss,
                                         cohort_impl="shard_map")}
    walls, stacks = {}, {}
    for name, eng in engines.items():
        bank = eng.update_cohort(params, batch_list)        # warm-up
        jax.block_until_ready(bank.stacked)
        best = float("inf")
        for _ in range(3):
            t0 = time.time()
            bank = eng.update_cohort(params, batch_list)
            jax.block_until_ready(bank.stacked)
            best = min(best, time.time() - t0)
        walls[name] = best
        stacks[name] = jax.device_get(bank.stacked)
        print(f"engine_sharded,{name},wall_s={best:.3f},"
              f"devices={jax.device_count() if name == 'shard_map' else 1},"
              f"cohort={cohort},"
              f"padding_waste={eng.stats['padding_waste']}", flush=True)
    diff = max(float(np.max(np.abs(a - b))) for a, b in
               zip(jax.tree.leaves(stacks["vmap"]),
                   jax.tree.leaves(stacks["shard_map"])))
    equal = diff <= 1e-5
    print(f"engine_sharded,{walls['shard_map'] * 1e6:.0f},"
          f"max_abs_diff={diff:.2e},equal={equal}", flush=True)
    gates = {"equal_atol_1e-5": equal}
    result = {"wall_vmap_s": walls["vmap"],
              "wall_shard_map_s": walls["shard_map"],
              "devices": jax.device_count(),
              "cohort": cohort, "max_abs_diff": diff,
              "equal_atol_1e-5": equal,
              "wall_s": time.time() - t_bench0, "gates": gates}
    _save("engine_sharded", result)
    _bench_log("engine_sharded", result)
    if not equal:   # this row is a gate, not a report — fail the run
        raise RuntimeError(f"shard_map deltas diverge from vmap: {diff:.2e}")
    return diff


def serve():
    """Batched personalization throughput: PersonalizationServer (one
    cohort call per micro-batch) vs the pre-subsystem per-request loop
    (one jitted prox solve dispatch per user), 32 concurrent users.

    This is the serving-side twin of the ``engine`` row: per-user heads
    are tiny, so the work is dispatch-bound and the per-request loop pays
    O(users) device round-trips where the server pays one.  Steady state
    must keep ``host_materializations`` at 0 — heads are served as
    device-side gathers from the stacked head bank."""
    from repro.core import PersAFLConfig
    from repro.core.moreau import personalize_me
    from repro.serving import PersonalizationServer

    t_bench0 = time.time()
    d, users, rounds = 32, 32, 4 if FAST else 8
    rng = np.random.RandomState(0)

    def loss(p, b):
        logits = b["images"] @ p["w"] + p["b"]
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.sum(jax.nn.one_hot(b["labels"], 10) * logp, -1))

    params = {"w": jnp.zeros((d, 10)), "b": jnp.zeros((10,))}
    pcfg = PersAFLConfig(option="C", lam=20.0, inner_steps=5,
                         inner_eta=0.05, beta=0.5)
    # payloads stay host-side numpy, as a network-facing server holds them:
    # the micro-batcher stacks them in one memcpy per leaf, while the
    # per-request loop pays a host→device transfer per dispatch
    batches = [{"images": rng.randn(16, d).astype(np.float32),
                "labels": rng.randint(0, 10, 16).astype(np.int32)}
               for _ in range(users)]

    # baseline: the old launch/serve.py shape — one dispatch per request
    per_req = jax.jit(lambda p, b: personalize_me(
        loss, p, b, pcfg.lam, pcfg.inner_eta, pcfg.inner_steps))
    jax.block_until_ready(per_req(params, batches[0]))      # warm-up
    t_loop = float("inf")
    for _ in range(3):
        t0 = time.time()
        for _ in range(rounds):
            for b in batches:
                jax.block_until_ready(per_req(params, b))
        t_loop = min(t_loop, time.time() - t0)

    server = PersonalizationServer(params, loss, pcfg, modes=("C",),
                                   max_pending=2 * users)
    uids = [f"user{u}" for u in range(users)]

    def window():
        for uid, b in zip(uids, batches):
            server.submit(uid, b, mode="C")
        server.flush()
        jax.block_until_ready(server.stacked_heads(uids))
        server.advance_window()

    window()                                                # warm-up
    warm_windows = server.stats["ring_windows"]
    t_server = float("inf")
    for _ in range(3):
        t0 = time.time()
        for _ in range(rounds):
            window()
        t_server = min(t_server, time.time() - t0)
    stats = server.stats
    host_mat = stats["host_materializations"]
    n_req = users * rounds
    speedup = t_loop / t_server
    print(f"serve,per_request,wall_s={t_loop:.3f},"
          f"req_per_s={n_req / t_loop:.0f}", flush=True)
    print(f"serve,server,wall_s={t_server:.3f},"
          f"req_per_s={n_req / t_server:.0f},"
          f"windows={stats['ring_windows'] - warm_windows},"
          f"cohort_calls={stats['cohort_calls']},"
          f"ring_bytes_per_user={stats['ring_bytes_per_user']},"
          f"host_materializations={host_mat}", flush=True)
    print(f"serve,{t_server / n_req * 1e6:.0f},speedup={speedup:.2f}")
    gates = {"host_materializations_zero": host_mat == 0}
    result = {"users": users, "rounds": rounds,
              "wall_per_request_s": t_loop,
              "wall_server_s": t_server, "speedup": speedup,
              "req_per_s_server": n_req / t_server,
              "req_per_s_per_request": n_req / t_loop,
              "ring_bytes_per_user": int(stats["ring_bytes_per_user"]),
              "host_materializations": int(host_mat),
              "wall_s": time.time() - t_bench0, "gates": gates}
    _save("serve", result)
    _bench_log("serve", result)
    if host_mat != 0:    # steady-state contract, not a report
        raise RuntimeError(f"serving path materialized {host_mat} banks")
    return speedup


def serve_transport():
    """Transport front-end throughput: N concurrent client connections in
    a SECOND OS PROCESS (``benchmarks.transport_loadgen``) driving
    submit/poll over the loopback socket vs the same windowed workload
    through the in-process PersonalizationServer surface.

    The contract under test: the transport must NOT forfeit the
    micro-batching win — all N connections' submits coalesce into the same
    pow2-bucketed cohort calls (the queue fills to ``max_pending`` and
    flushes synchronously; the ``flush_ms`` deadline timer only catches
    stragglers) and served heads are encoded from one stacked gather per
    flush, so batched throughput over the socket stays within 1.5x of the
    in-process path (gated) and steady-state ``host_materializations``
    stays 0 (gated).  Reports req/s plus p50/p99 per-request latency
    (submit → personalized head on the client).  The head is
    personalization-sized (d=256 features, K=200 prox steps) — at toy
    sizes the wire codec, not the serving stack, dominates both paths."""
    import asyncio

    from repro.core import PersAFLConfig
    from repro.serving import PersonalizationServer
    from repro.serving.transport import TransportServer

    d, rows, conns = 256, 32, 32
    rounds, reps = 4 if FAST else 8, 3
    rng = np.random.RandomState(0)

    def loss(p, b):
        logits = b["images"] @ p["w"] + p["b"]
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.sum(jax.nn.one_hot(b["labels"], 10) * logp, -1))

    params = {"w": jnp.zeros((d, 10)), "b": jnp.zeros((10,))}
    pcfg = PersAFLConfig(option="C", lam=20.0, inner_steps=200,
                         inner_eta=0.01, beta=0.5)
    # the loadgen process generates bit-identical batches (same seed)
    batches = [{"images": rng.randn(rows, d).astype(np.float32),
                "labels": rng.randint(0, 10, rows).astype(np.int32)}
               for _ in range(conns)]
    uids = [f"user{u}" for u in range(conns)]

    def make_server():
        return PersonalizationServer(params, loss, pcfg, modes=("C",),
                                     max_pending=conns)

    # in-process baseline: the `serve` row's server path at the same
    # (users, rounds) — submit all, flush, fetch every head, advance
    srv = make_server()

    def window():
        tickets = [srv.submit(u, b) for u, b in zip(uids, batches)]
        srv.flush()
        for t in tickets:
            jax.block_until_ready(jax.tree.leaves(srv.poll(t))[0])
        srv.advance_window()

    window()                                                 # warm-up
    t_inproc = float("inf")
    for _ in range(reps):
        t0 = time.time()
        for _ in range(rounds):
            window()
        t_inproc = min(t_inproc, time.time() - t0)

    # transport: boot the front-end here, drive it from the loadgen
    # process — one connection per user, all submits racing the same
    # queue; the Nth submit triggers the synchronous micro-batch flush
    async def drive():
        psrv = make_server()
        ts = await TransportServer(psrv, flush_ms=100.0,
                                   max_inflight=4 * conns).start()
        # the loadgen imports JAX (through the wire codec); pinned to the
        # CPU so it never contends for this process's accelerator
        proc = await asyncio.create_subprocess_exec(
            sys.executable, "-m", "benchmarks.transport_loadgen",
            "--port", str(ts.port), "--conns", str(conns),
            "--rounds", str(rounds), "--reps", str(reps),
            "--d", str(d), "--rows", str(rows),
            stdout=asyncio.subprocess.PIPE,
            env=dict(os.environ, JAX_PLATFORMS="cpu"))
        out, _ = await proc.communicate()
        if proc.returncode != 0:
            raise RuntimeError(
                f"transport loadgen exited {proc.returncode}")
        res = json.loads(out.decode().splitlines()[-1])
        stats = dict(psrv.stats)
        await ts.stop()
        return res["wall_s"], res["latencies_s"], stats

    t_transport, lat, stats = asyncio.run(drive())
    n_req = conns * rounds
    p50 = float(np.percentile(lat, 50) * 1e3)
    p99 = float(np.percentile(lat, 99) * 1e3)
    host_mat = int(stats["host_materializations"])
    ratio = t_transport / t_inproc
    print(f"serve_transport,in_process,wall_s={t_inproc:.3f},"
          f"req_per_s={n_req / t_inproc:.0f}", flush=True)
    print(f"serve_transport,transport,wall_s={t_transport:.3f},"
          f"req_per_s={n_req / t_transport:.0f},conns={conns},"
          f"p50_ms={p50:.2f},p99_ms={p99:.2f},"
          f"cohort_calls={stats['cohort_calls']},"
          f"host_materializations={host_mat}", flush=True)
    print(f"serve_transport,{t_transport / n_req * 1e6:.0f},"
          f"ratio_vs_in_process={ratio:.2f}")
    _save("serve_transport", {
        "conns": conns, "rounds": rounds,
        "wall_in_process_s": t_inproc, "wall_transport_s": t_transport,
        "req_per_s_in_process": n_req / t_inproc,
        "req_per_s_transport": n_req / t_transport,
        "p50_ms": p50, "p99_ms": p99,
        "ratio_vs_in_process": ratio,
        "host_materializations": host_mat})
    if host_mat != 0:       # steady-state contract, not a report
        raise RuntimeError(f"transport path materialized {host_mat} banks")
    if ratio > 1.5:         # the micro-batching win must survive the wire
        raise RuntimeError(
            f"transport throughput {ratio:.2f}x slower than in-process "
            f"(bound: 1.5x) — the socket front-end forfeited batching")
    return ratio


def serve_mesh():
    """2-D ("cohort", "model") mesh serving (PR 10 acceptance row).

    Drives the same windowed personalization workload on the 1-D 8-way
    ``("cohort",)`` mesh and the 2-D ``(2, 4)`` mesh with model-axis
    param shardings, and gates the tentpole's contract:

      * bit-parity — final global params AND every served head are
        ``np.array_equal`` between the two layouts (the mesh is a layout
        choice, never a semantics choice);
      * steady state — ``host_materializations`` stays 0 on BOTH layouts
        (gather-not-transfer on both mesh axes);
      * residency — per-device peak delta/snapshot/params residency on
        the 2x4 mesh is ≤ 0.6x the 1-D peak at equal users: the model
        axis splits every stored row 4 ways and the 2-slice cohort axis
        buckets 4 users into 4 rows where the 8-slice 1-D mesh pads to 8.

    Needs 8 devices: on a CPU parent, 8 forced host devices (see
    :func:`_delegate_to_cpu_child`); on an accelerator, 8 chips.
    """
    if _delegate_to_cpu_child("serve_mesh", cpu_devices=8, min_devices=8):
        return

    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import PersAFLConfig
    from repro.serving import PersonalizationServer
    from repro.sharding.ctx import cohort_mesh, cohort_model_mesh

    t_bench0 = time.time()
    rng = np.random.RandomState(0)
    d, classes, windows = 64, 64, 4

    def loss(p, b):
        logits = b["images"] @ p["w"] + p["b"]
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.sum(
            jax.nn.one_hot(b["labels"], classes) * logp, -1))

    params = {"w": jnp.asarray(rng.randn(d, classes) * 0.1, jnp.float32),
              "b": jnp.zeros((classes,), jnp.float32)}
    pcfg = PersAFLConfig(option="C", lam=20.0, inner_steps=2,
                         inner_eta=0.02, beta=0.5, alpha=0.05)
    # crc32-balanced user ids (distinct mod 8, 2/2 mod 2): both layouts
    # bucket them without cross-slice collisions, so the residency
    # comparison measures the mesh, not hash luck
    users = ["user000", "user004", "user003", "user007"]
    batches = {u: {"images": rng.randn(8, d).astype(np.float32),
                   "labels": rng.randint(0, classes, 8).astype(np.int32)}
               for u in users}

    def per_device_bytes(srv):
        dev = {}

        def add(x):
            if not hasattr(x, "addressable_shards"):
                return
            for s in x.addressable_shards:
                dev[s.device.id] = dev.get(s.device.id, 0) + s.data.nbytes
        for banks in srv.ring._banks.values():
            for bank in banks:
                jax.tree.map(add, bank.stacked)
        for snap in srv.ring._snapshots.values():
            jax.tree.map(add, snap)
        jax.tree.map(add, srv.params)
        return dev

    def drive(mesh, shardings):
        srv = PersonalizationServer(params, loss, pcfg, modes=("C",),
                                    cohort_impl="shard_map", mesh=mesh,
                                    windows=windows,
                                    param_shardings=shardings)
        heads = {}
        t0 = time.time()
        for _ in range(windows):            # fill the ring to steady state
            tickets = {u: srv.submit(u, batches[u], mode="C")
                       for u in users}
            srv.flush()
            heads = {u: jax.tree.map(np.asarray, srv.poll(t))
                     for u, t in tickets.items()}
            srv.advance_window()
        return srv, heads, time.time() - t0

    srv1, heads1, wall1 = drive(cohort_mesh(), None)
    m24 = cohort_model_mesh(4)
    shardings = {"w": NamedSharding(m24, P(None, "model")),
                 "b": NamedSharding(m24, P("model"))}
    srv2, heads2, wall2 = drive(m24, shardings)

    p1 = jax.tree.map(np.asarray, srv1.params)
    p2 = jax.tree.map(np.asarray, srv2.params)
    params_equal = all(np.array_equal(p1[k], p2[k]) for k in p1)
    heads_equal = all(
        np.array_equal(heads1[u][k], heads2[u][k])
        for u in users for k in heads1[u])
    host_mat = (int(srv1.stats["host_materializations"]),
                int(srv2.stats["host_materializations"]))
    peak1 = max(per_device_bytes(srv1).values())
    peak2 = max(per_device_bytes(srv2).values())
    ratio = peak2 / peak1
    print(f"serve_mesh,1d,wall_s={wall1:.3f},peak_device_bytes={peak1},"
          f"host_materializations={host_mat[0]}", flush=True)
    print(f"serve_mesh,2x4,wall_s={wall2:.3f},peak_device_bytes={peak2},"
          f"params_bit_equal={params_equal},heads_bit_equal={heads_equal},"
          f"host_materializations={host_mat[1]}", flush=True)
    print(f"serve_mesh,0,residency_ratio={ratio:.3f}")
    gates = {"params_bit_equal": params_equal,
             "heads_bit_equal": heads_equal,
             "host_materializations_zero": host_mat == (0, 0),
             "residency_ratio_le_0p6": ratio <= 0.6}
    result = {"users": len(users), "windows": windows,
              "wall_1d_s": wall1, "wall_2x4_s": wall2,
              "peak_device_bytes_1d": int(peak1),
              "peak_device_bytes_2x4": int(peak2),
              "residency_ratio": ratio,
              "params_bit_equal": params_equal,
              "heads_bit_equal": heads_equal,
              "host_materializations_1d": host_mat[0],
              "host_materializations_2x4": host_mat[1],
              "wall_s": time.time() - t_bench0, "gates": gates}
    _save("serve_mesh", result)
    _bench_log("serve_mesh", result)
    for gate, ok in gates.items():
        if not ok:
            raise RuntimeError(f"serve_mesh gate failed: {gate} ({result})")
    return ratio


def partial():
    """Partial-model personalization: head-only rows end-to-end.

    Two gates plus a convergence pin:

      * residency — a ``personal_subset=("b",)`` server on the serve-row
        config banks 40-byte rows where the full server banks 1320-byte
        ones; ``ring_bytes_per_user`` must shrink ≥ 20x (gated).  That
        ratio is the resident-users-at-fixed-memory lever, reported as a
        users-per-GiB row for both servers.
      * backbone bit-parity — across several advanced windows the subset
        server's backbone leaf stays bit-identical to the initial params
        (``np.array_equal``, not allclose): head-only deltas never touch
        it, so ONE shared backbone serves every retained window exactly.
      * convergence pin — on the fig2 MNIST config, personalized accuracy
        with head-only fine-tune (``fc/#1``, the final FC layer) must land
        within 0.1 of full-model personalized fine-tune after a short
        persafl-me run (gated): the head carries the personalization.
    """
    from repro.core import PersAFLConfig
    from repro.serving import PersonalizationServer

    t_bench0 = time.time()
    d, users, windows = 32, 32, 3
    rng = np.random.RandomState(0)

    def loss(p, b):
        logits = b["images"] @ p["w"] + p["b"]
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.sum(jax.nn.one_hot(b["labels"], 10) * logp, -1))

    params = {"w": jnp.zeros((d, 10)), "b": jnp.zeros((10,))}
    pcfg = PersAFLConfig(option="C", lam=20.0, inner_steps=5,
                         inner_eta=0.05, beta=0.5)
    batches = [{"images": rng.randn(16, d).astype(np.float32),
                "labels": rng.randint(0, 10, 16).astype(np.int32)}
               for _ in range(users)]
    uids = [f"user{u}" for u in range(users)]
    w0 = np.asarray(params["w"])

    bytes_per_user = {}
    for name, subset in (("full", None), ("head_only", ("b",))):
        srv = PersonalizationServer(params, loss, pcfg, modes=("C",),
                                    max_pending=2 * users,
                                    personal_subset=subset)
        for _ in range(windows):
            for uid, b in zip(uids, batches):
                srv.submit(uid, b, mode="C")
            srv.flush()
            jax.block_until_ready(srv.stacked_heads(uids))
            srv.advance_window()
            if subset is not None and not np.array_equal(
                    np.asarray(srv.params["w"]), w0):
                raise RuntimeError(
                    "head-only serving perturbed the backbone — subset "
                    "rows must leave non-subset leaves bit-identical")
        st = srv.stats
        bytes_per_user[name] = int(st["ring_bytes_per_user"])
        print(f"partial,{name},ring_row_bytes={st['ring_row_bytes']},"
              f"ring_bytes_per_user={bytes_per_user[name]},"
              f"users_per_gib={2 ** 30 // bytes_per_user[name]},"
              f"host_materializations={st['host_materializations']}",
              flush=True)
    ratio = bytes_per_user["full"] / bytes_per_user["head_only"]

    # convergence pin: fig2 MNIST config, short persafl-me run, then the
    # same personalized eval with full vs head-only fine-tune masks
    from repro.fl import (DelayModel, FLRun, immediate,
                          make_personalized_eval, strategy)
    clients, cparams, closs, cacc, _ = setup("mnist",
                                             n_clients=10 if FAST else 20)
    pcfg2 = PersAFLConfig(option="C", q_local=5, lam=25.0, inner_steps=5,
                          inner_eta=0.02, beta=1.0, eta=0.002)
    sim = FLRun(clients=clients, loss_fn=closs, init_params=cparams,
                pcfg=pcfg2, delays=DelayModel(len(clients), seed=1),
                strategy=strategy("persafl", option="C"),
                schedule=immediate(), batch_size=16, seed=0)
    sim.run(max_rounds=20 if FAST else 60)
    trained = sim.state.params
    ev_full = make_personalized_eval(closs, cacc, clients,
                                     ft_steps=1, ft_lr=0.01)
    ev_head = make_personalized_eval(closs, cacc, clients,
                                     ft_steps=1, ft_lr=0.01,
                                     personal_subset="fc/#1")
    a_full, a_head = float(ev_full(trained)), float(ev_head(trained))
    gap = abs(a_full - a_head)
    print(f"partial,convergence,acc_full={a_full:.3f},"
          f"acc_head_only={a_head:.3f},gap={gap:.3f}", flush=True)
    print(f"partial,0,bytes_ratio={ratio:.1f}")
    gates = {"bytes_ratio_ge_20": ratio >= 20.0,
             "acc_gap_le_0p1": gap <= 0.1,
             "backbone_bit_parity": True}
    result = {
        "ring_bytes_per_user_full": bytes_per_user["full"],
        "ring_bytes_per_user_head_only": bytes_per_user["head_only"],
        "users_per_gib_full": 2 ** 30 // bytes_per_user["full"],
        "users_per_gib_head_only": 2 ** 30 // bytes_per_user["head_only"],
        "bytes_ratio": ratio, "backbone_bit_parity": True,
        "acc_full": a_full, "acc_head_only": a_head, "acc_gap": gap,
        "wall_s": time.time() - t_bench0, "gates": gates}
    _save("partial", result)
    _bench_log("partial", result)
    if ratio < 20.0:    # the residency win is the point — gate it
        raise RuntimeError(
            f"head-only rows only {ratio:.1f}x smaller than full rows "
            f"(bound: 20x) — subset rows are not subset-shaped")
    if gap > 0.1:       # head must carry the personalization
        raise RuntimeError(
            f"head-only personalization diverged from full by {gap:.3f} "
            f"accuracy (bound: 0.1) on the fig2 MNIST config")
    return ratio


def quant():
    """Quantized delta banking + compressed wire, four gates + a pin.

      * kernel parity — ``apply_rows_q`` (interpret) must match the jnp
        oracle within 1e-5 over pow2 and non-pow2 cohorts (gated);
      * residency — an int8-banking server at the serve-transport config
        (d=256 features, 256 classes, 32 users) banks int8 delta rows +
        int8 EF residual rows and stores NO fp32 head bank (heads are lazy
        ``snapshot − scale·q`` views), so ``ring_bytes_per_user`` must be
        ≥ 3.5x smaller than the fp32 twin's (gated) — i.e. ≥ 3.5x ring
        capacity at equal device memory;
      * wire — SUBMIT (32×256 batch) and HEAD (256×256 head) npz bodies
        under ``codec="int8"`` must each be ≥ 3.5x smaller than fp32
        (gated; measured on full bodies, npz container overhead included);
      * convergence pin — fig2 MNIST config driven THROUGH two
        PersonalizationServers (fp32 banking vs int8+EF banking) for the
        same windows; personalized accuracy must land within 0.1 (gated):
        error feedback keeps banking noise a residual, not a bias;
      * steady state — the int8 server's ``host_materializations`` stays 0
        (gated): quantized rows never materialize fp32 on the host.
    """
    from repro.core import PersAFLConfig
    from repro.core.quant import quantize_stack
    from repro.kernels.fused_update.kernel import apply_rows_q
    from repro.kernels.fused_update.ref import apply_rows_q_ref
    from repro.serving import PersonalizationServer
    from repro.serving.transport import encode_pytree

    t_bench0 = time.time()
    rng = np.random.RandomState(0)

    # -- gate 1: kernel parity vs the jnp oracle ---------------------------
    max_diff = 0.0
    for m, shape in ((3, (512,)), (8, (4096,)), (5, (257,))):
        w = jnp.asarray(rng.randn(*shape).astype(np.float32))
        stack = jnp.asarray(
            0.01 * rng.randn(m, *shape).astype(np.float32))
        qs = quantize_stack(stack)
        q, sc = jax.tree.leaves(qs.q)[0], jax.tree.leaves(qs.scales)[0]
        weights = jnp.asarray(rng.rand(m).astype(np.float32))
        got = apply_rows_q(w, q, sc, weights, interpret=True)
        want = apply_rows_q_ref(w, q, sc, weights)
        max_diff = max(max_diff, float(jnp.max(jnp.abs(got - want))))
    kernel_parity = max_diff <= 1e-5
    print(f"quant,kernel_parity,max_abs_diff={max_diff:.2e},"
          f"ok={kernel_parity}", flush=True)

    # -- gate 2: ring residency, int8 vs fp32 twin -------------------------
    d, classes, users, windows = 256, 256, 32, 3

    def loss(p, b):
        logits = b["images"] @ p["w"] + p["b"]
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.sum(
            jax.nn.one_hot(b["labels"], classes) * logp, -1))

    params = {"w": jnp.zeros((d, classes)), "b": jnp.zeros((classes,))}
    pcfg = PersAFLConfig(option="C", lam=20.0, inner_steps=5,
                         inner_eta=0.05, beta=0.5)
    batches = [{"images": rng.randn(32, d).astype(np.float32),
                "labels": rng.randint(0, classes, 32).astype(np.int32)}
               for _ in range(users)]
    uids = [f"user{u}" for u in range(users)]

    bytes_per_user, host_mat = {}, {}
    for dtype in ("fp32", "int8"):
        srv = PersonalizationServer(params, loss, pcfg, modes=("C",),
                                    max_pending=2 * users,
                                    delta_dtype=dtype)
        for _ in range(windows):
            for uid, b in zip(uids, batches):
                srv.submit(uid, b, mode="C")
            srv.flush()
            jax.block_until_ready(srv.stacked_heads(uids))
            srv.advance_window()
        st = srv.stats
        bytes_per_user[dtype] = int(st["ring_bytes_per_user"])
        host_mat[dtype] = int(st["host_materializations"])
        print(f"quant,{dtype},ring_row_bytes={st['ring_row_bytes']},"
              f"ring_bytes_per_user={bytes_per_user[dtype]},"
              f"users_per_gib={2 ** 30 // bytes_per_user[dtype]},"
              f"host_materializations={host_mat[dtype]}", flush=True)
    ring_ratio = bytes_per_user["fp32"] / bytes_per_user["int8"]

    # -- gate 3: wire bytes, SUBMIT and HEAD bodies ------------------------
    submit_bytes = {c: len(encode_pytree(batches[0], codec=c))
                    for c in ("fp32", "int8")}
    head = {"w": rng.randn(d, classes).astype(np.float32),
            "b": rng.randn(classes).astype(np.float32)}
    head_bytes = {c: len(encode_pytree(head, codec=c))
                  for c in ("fp32", "int8")}
    submit_ratio = submit_bytes["fp32"] / submit_bytes["int8"]
    head_ratio = head_bytes["fp32"] / head_bytes["int8"]
    print(f"quant,wire,submit_fp32={submit_bytes['fp32']},"
          f"submit_int8={submit_bytes['int8']},"
          f"submit_ratio={submit_ratio:.2f},"
          f"head_fp32={head_bytes['fp32']},"
          f"head_int8={head_bytes['int8']},"
          f"head_ratio={head_ratio:.2f}", flush=True)

    # -- gate 4: convergence pin on the fig2 MNIST config ------------------
    from repro.fl import make_personalized_eval
    clients, cparams, closs, cacc, _ = setup("mnist", n_clients=16)
    pcfg2 = PersAFLConfig(option="C", lam=25.0, inner_steps=5,
                          inner_eta=0.02, beta=1.0)
    cbatches = [{"images": c.train_x[:16], "labels": c.train_y[:16]}
                for c in clients]
    cuids = [f"client{u}" for u in range(len(clients))]
    ev = make_personalized_eval(closs, cacc, clients,
                                ft_steps=1, ft_lr=0.01)
    accs = {}
    for dtype in ("fp32", "int8"):
        srv = PersonalizationServer(cparams, closs, pcfg2, modes=("C",),
                                    max_pending=2 * len(clients),
                                    delta_dtype=dtype)
        for _ in range(6 if FAST else 12):
            for uid, b in zip(cuids, cbatches):
                srv.submit(uid, b, mode="C")
            srv.flush()
            srv.advance_window()
        accs[dtype] = float(ev(srv.params))
    gap = abs(accs["fp32"] - accs["int8"])
    print(f"quant,convergence,acc_fp32={accs['fp32']:.3f},"
          f"acc_int8_ef={accs['int8']:.3f},gap={gap:.3f}", flush=True)
    print(f"quant,0,ring_ratio={ring_ratio:.2f}")

    wall_s = time.time() - t_bench0
    gates = {"kernel_parity": kernel_parity,
             "ring_ratio_ge_3p5": ring_ratio >= 3.5,
             "submit_ratio_ge_3p5": submit_ratio >= 3.5,
             "head_ratio_ge_3p5": head_ratio >= 3.5,
             "acc_gap_le_0p1": gap <= 0.1,
             "host_materializations_zero": host_mat["int8"] == 0}
    result = {
        "kernel_max_abs_diff": max_diff,
        "ring_bytes_per_user_fp32": bytes_per_user["fp32"],
        "ring_bytes_per_user_int8": bytes_per_user["int8"],
        "ring_ratio": ring_ratio,
        "submit_bytes_fp32": submit_bytes["fp32"],
        "submit_bytes_int8": submit_bytes["int8"],
        "submit_ratio": submit_ratio,
        "head_bytes_fp32": head_bytes["fp32"],
        "head_bytes_int8": head_bytes["int8"],
        "head_ratio": head_ratio,
        "acc_fp32": accs["fp32"], "acc_int8_ef": accs["int8"],
        "acc_gap": gap,
        "host_materializations": host_mat["int8"],
        "wall_s": wall_s, "gates": gates,
    }
    _save("quant", result)
    _bench_log("quant", result)
    for gate, ok in gates.items():
        if not ok:
            raise RuntimeError(f"quant gate failed: {gate} ({result})")
    return ring_ratio


def scale():
    """Scenario engine at 10^3 → 10^6 simulated clients (FAST caps at
    10^5): per window, the DeviceScheduler forms the cohort on device, a
    synthetic cohort delta bank is corrupted by the scenario's
    adversaries (``scale_rows``), admitted through the robust clip path
    and applied with one fused ``apply_admitted_rows`` pass.  Gates:

      * sub-linear wall-clock — growing n by 1000x (100x FAST) must grow
        s/window by well under that factor (the whole point of the
        device-resident scheduler; the Python heap is O(n log n) pops
        per simulated second);
      * ``host_materializations == 0`` — no per-client or per-delta
        array ever crosses to the host (cohort ids/times and row norms
        are the only device→host traffic, all [cohort_cap]-sized).
    """
    from repro.core import (apply_admitted_rows, bank_row_norms,
                            init_server_state, mask_rows,
                            robust_admission_weights, scale_rows)
    from repro.fl.engine import DeltaBank
    from repro.fl.scenario import (Adversarial, DeviceScheduler, Diurnal,
                                   ScenarioSpec, Tier)
    ns = [1_000, 10_000, 100_000] if FAST \
        else [1_000, 10_000, 100_000, 1_000_000]
    windows = 3 if FAST else 5
    d = 1 << 16                    # synthetic per-client delta width
    key = jax.random.PRNGKey(0)
    rows_out = []
    print("scale,n,s_per_window,arrivals,dropouts,corrupted,clipped")
    for n in ns:
        spec = ScenarioSpec(
            n_clients=n, seed=0,
            tiers=(Tier("fast", 0.5, 0.7), Tier("slow", 0.5, 1.6)),
            diurnal=Diurnal(period=400.0, floor=0.25), dropout=0.02,
            adversarial=Adversarial(frac=0.05, kinds=("scale",
                                                      "sign_flip")))
        model = spec.build()
        sched = DeviceScheduler(model, window_len=30.0, cohort_cap=256,
                                cycles_per_window=8)
        cap = sched.cohort_cap
        state = init_server_state({"w": jnp.zeros(d, jnp.float32)})
        base = {"w": 0.01 * jax.random.normal(key, (cap, d), jnp.float32)}
        bank_stats = {}
        corrupted = clipped = 0
        sched.next_window()                  # compile/warm-up window
        t0 = time.time()
        for _ in range(windows):
            ids, _times = sched.next_window()
            fill = len(ids)
            if fill == 0:
                continue
            bank = DeltaBank(stacked=base, k=fill, stats=bank_stats)
            fac = model.corruption_factors(ids)
            vec = np.ones(cap, np.float32)
            vec[:fill] = fac
            stacked = scale_rows(bank.stacked, vec)
            corrupted += int(np.sum(fac != 1.0))
            norms = bank_row_norms(stacked)
            weights, keep, info = robust_admission_weights(
                cap, [(j, 0) for j in range(fill)], norms, beta=0.1,
                count=fill, method="clip")
            clipped += info["clipped"]
            if not bool(keep.all()):
                stacked = mask_rows(stacked, keep)
            state = apply_admitted_rows(state, stacked, weights, fill,
                                        staleness_max=0,
                                        staleness_sum=0.0)
        jax.block_until_ready(state.params["w"])
        wall = time.time() - t0
        host_mat = bank_stats.get("host_materializations", 0)
        row = {"n": n, "s_per_window": wall / windows,
               "arrivals": sched.stats["arrivals"],
               "dropouts": sched.stats["dropouts"],
               "overflow_arrivals": sched.stats["overflow_arrivals"],
               "corrupted_rows": corrupted, "clipped": clipped,
               "host_materializations": host_mat}
        rows_out.append(row)
        print(f"scale,{n},{row['s_per_window']:.4f},"
              f"{row['arrivals']},{row['dropouts']},{corrupted},{clipped}")
    n_ratio = rows_out[-1]["n"] / rows_out[0]["n"]
    t_ratio = (rows_out[-1]["s_per_window"]
               / max(rows_out[0]["s_per_window"], 1e-9))
    gates = {
        # "sub-linear": 1000x clients may cost at most 0.2x that in time
        "sublinear_time": t_ratio <= 0.2 * n_ratio,
        "zero_host_materializations":
            all(r["host_materializations"] == 0 for r in rows_out),
        "cohorts_formed": all(r["arrivals"] > 0 for r in rows_out),
    }
    result = {"rows": rows_out, "n_ratio": n_ratio, "t_ratio": t_ratio,
              "windows": windows, "cohort_cap": 256, "fast": FAST,
              "gates": gates}
    _save("scale", result)
    _bench_log("scale", result)
    print(f"scale_sublinear,{t_ratio:.1f},n_ratio={n_ratio:.0f}")
    for gate, ok in gates.items():
        if not ok:
            raise RuntimeError(f"scale gate failed: {gate} ({result})")
    return t_ratio


def kernels():
    """µs/call for each Pallas kernel (interpret) and its jnp oracle."""
    from repro.kernels.flash_attention.kernel import flash_attention_fwd
    from repro.kernels.flash_attention.ref import attention_ref
    from repro.kernels.ssd.kernel import ssd_fwd
    from repro.kernels.ssd.ref import ssd_ref
    from repro.kernels.fused_update import kernel as FK, ref as FR

    def timeit(fn, n=3):
        jax.block_until_ready(fn())  # warm
        t0 = time.time()
        for _ in range(n):
            jax.block_until_ready(fn())
        return (time.time() - t0) / n * 1e6

    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (1, 256, 4, 64))
    k = jax.random.normal(ks[1], (1, 256, 2, 64))
    v = jax.random.normal(ks[2], (1, 256, 2, 64))
    t_kern = timeit(lambda: flash_attention_fwd(q, k, v, interpret=True))
    t_ref = timeit(lambda: attention_ref(q, k, v))
    print(f"kernel_flash_attention,{t_kern:.0f},ref_us={t_ref:.0f}")

    x = jax.random.normal(ks[0], (1, 256, 8, 64))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (1, 256, 8)))
    a_log = jnp.log(jnp.linspace(1, 8, 8))
    Bm = jax.random.normal(ks[2], (1, 256, 1, 32))
    Cm = jax.random.normal(ks[3], (1, 256, 1, 32))
    t_kern = timeit(lambda: ssd_fwd(x, dt, a_log, Bm, Cm, chunk=64,
                                    interpret=True))
    t_ref = timeit(lambda: ssd_ref(x, dt, a_log, Bm, Cm))
    print(f"kernel_ssd,{t_kern:.0f},ref_us={t_ref:.0f}")

    w = jax.random.normal(ks[0], (1 << 20,))
    g = jax.random.normal(ks[1], (1 << 20,))
    t_kern = timeit(lambda: FK.sgd_step(w, g, 0.01, interpret=True))
    t_ref = timeit(lambda: FR.sgd_step_ref(w, g, 0.01))
    print(f"kernel_fused_update,{t_kern:.0f},ref_us={t_ref:.0f}")

    from repro.core.quant import quantize_stack
    stack = 0.01 * jax.random.normal(ks[2], (8, 1 << 18))
    qs = quantize_stack(stack)
    q = jax.tree.leaves(qs.q)[0]
    sc = jax.tree.leaves(qs.scales)[0]
    wq = jax.random.normal(ks[3], (1 << 18,))
    wts = jnp.full((8,), 0.1, jnp.float32)
    t_kern = timeit(lambda: FK.apply_rows_q(wq, q, sc, wts,
                                            interpret=True))
    t_ref = timeit(lambda: FR.apply_rows_q_ref(wq, q, sc, wts))
    print(f"kernel_apply_rows_q,{t_kern:.0f},ref_us={t_ref:.0f}")


BENCHES = {
    "fig2a": fig2a_concurrency,
    "fig2b": fig2b_mnist,
    "fig2c": fig2c_cifar,
    "table1": table1_staleness,
    "engine": engine,
    "engine_sharded": engine_sharded,
    "serve": serve,
    "serve_transport": serve_transport,
    "serve_mesh": serve_mesh,
    "partial": partial,
    "quant": quant,
    "scale": scale,
    "kernels": kernels,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of " + ",".join(BENCHES))
    args, _ = ap.parse_known_args()
    enable_compile_cache()
    names = args.only.split(",") if args.only else list(BENCHES)
    print("name,us_per_call,derived")
    for name in names:
        t0 = time.time()
        BENCHES[name]()
        wall_s = time.time() - t0
        print(f"bench_{name}_total,{wall_s*1e6:.0f},ok", flush=True)
        _bench_log(name, {"bench": name, "wall_s": wall_s, "ok": True})


if __name__ == "__main__":
    main()
