#!/usr/bin/env python3
"""Bring-up smoke: PersA-FL's main path once on a TPU, at full width.

The model is mamba2-130m at its published config (24 layers, d_model 768,
vocab 50280; 167.6 M f32 parameters), with random weights from ``--seed``.
Every phase goes through the entry points a user calls:

  (a) kernels  -- ``apply_scaled``, ``apply_rows`` (M = 1, 3, 8, 128) and
                  ``apply_rows_q`` (M = 4, 64) through
                  ``kernels/fused_update/ops.py`` at mamba2-130m leaf
                  shapes, compared with their ``ref.py`` oracles.  The
                  compiled HLO must hold a ``tpu_custom_call``, so neither
                  the reference nor the interpreter can stand in.
  (b) training -- ``launch/train.py``'s ``run_arch`` (full config, batch
                  8 x 512, option C) for a few rounds, then its
                  ``run_paper_preset`` (paper-mnist) under a
                  ``buffered(4)`` schedule, so the cohort engine and the
                  ``apply_rows`` kernel close real windows.
  (c) serving  -- ``launch/serve.py``'s path: 4 users, mode C, submit,
                  flush, ``stacked_heads``, ``advance_window`` (the ring
                  apply), then a 16-token personalized decode.

``--mesh`` runs only the four-chip phase: one window of the same 4-user
mode-C workload served three ways in this one process (single-device
vmap; ``cohort_impl="shard_map"`` on the 1-D ``("cohort",)`` mesh; the
2 x 2 ``("cohort", "model")`` mesh of ``--model-axis 2``), comparing the
served heads and the post-window params across the three and reporting
per-device bytes.

Times printed here are smoke timings, not benchmark results.  The last
line of stdout is one JSON object, ``{"ok": true, "device": {...}}``.
Without a TPU, or when any phase fails, the script exits non-zero and
prints no such line.

  python3 chip_smoke.py            # one chip
  python3 chip_smoke.py --mesh     # a four-chip host
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(HERE, "chiprun_out", "chip_smoke")
ARCH = "mamba2-130m"
USERS = 4
KERNEL_ATOL = 1e-5          # f32 kernel vs jnp oracle, max abs error
STACK_BUDGET = 2 << 30      # bytes of one stacked-delta operand in (a)
BASE_LEN = 16777213         # prime; random base the operands in (a) tile
# XLA compile seconds (tracing and lowering nest across jit levels and
# would double count, so only the backend compile is summed)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_compile_s = [0.0]


def _on_duration(event, duration, **_):
    if event == COMPILE_EVENT:
        _compile_s[0] += duration


def log(msg: str) -> None:
    print(msg, flush=True)


def _peak_bytes(dev) -> int:
    stats = dev.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


# -- (a) kernels ------------------------------------------------------------

def phase_kernels(cfg, seed: int) -> None:
    """Each fused_update entry at mamba2-130m's real leaf shapes.  A leaf
    joins an M-row call when its ``[M, *leaf]`` operand fits STACK_BUDGET
    (at M = 128 only the small per-layer leaves do: a full-model ring of
    128 rows is 86 GB)."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.fused_update import ops, ref as R
    from repro.models import api

    shapes = jax.eval_shape(
        lambda: api.init_params(cfg, jax.random.PRNGKey(seed)))
    flat = tuple((jax.tree_util.keystr(p), leaf.shape)
                 for p, leaf in jax.tree_util.tree_leaves_with_path(shapes))
    # operands are windows of two random bases drawn once on the device,
    # each tree built by one jitted program: drawing every operand shape
    # with its own RNG and eager ops meant hundreds of compiles.  The base
    # length is prime and each leaf takes its own rotation, so rows of a
    # stack are not copies of each other.
    kf, kq = jax.random.split(jax.random.PRNGKey(seed))
    f_base = jax.random.normal(kf, (BASE_LEN,), jnp.float32)
    q_base = jax.random.randint(kq, (BASE_LEN,), -127, 128, jnp.int8)

    @functools.partial(jax.jit, static_argnums=(1, 2))
    def tile(base, shapes, scale, salt):
        return {name: scale * jnp.resize(jnp.roll(base, salt + 7919 * i),
                                         shape)
                for i, (name, shape) in enumerate(shapes)}

    @functools.partial(jax.jit, static_argnums=(1, 2))
    def row_weights(base, m, div, salt):
        # a positive [m] vector, entries in [0, ~4/div)
        return jnp.abs(jnp.roll(base, salt)[:m]) / div

    def leaves_for(m, itemsize):
        return tuple((name, shape) for name, shape in flat
                     if m * int(np.prod(shape)) * itemsize <= STACK_BUDGET)

    def stacked(m, sel):
        return tuple((n, (m,) + s) for n, s in sel)

    @jax.jit
    def max_abs_err(got, want):
        return jnp.max(jnp.stack([jnp.max(jnp.abs(g - w)) for g, w in zip(
            jax.tree.leaves(got), jax.tree.leaves(want))]))

    def check(label, fn, args, want_fn):
        compiled = jax.jit(fn).lower(*args).compile()
        if "tpu_custom_call" not in compiled.as_text():
            raise AssertionError(f"{label}: no tpu_custom_call in the "
                                 f"compiled HLO")
        got = compiled(*args)
        want = jax.jit(want_fn)(*args)
        err = float(max_abs_err(got, want))
        n = len(jax.tree.leaves(got))
        log(f"  {label}: {n} leaves, max_abs_err={err:.3e} "
            f"(tol {KERNEL_ATOL:g})")
        if not err <= KERNEL_ATOL:
            raise AssertionError(f"{label}: max abs error {err} > "
                                 f"{KERNEL_ATOL}")

    # apply_scaled: every leaf, full model
    w, d = tile(f_base, flat, 1.0, 0), tile(f_base, flat, 0.01, 1)
    check("apply_scaled", lambda w, d: ops.apply_delta_tree(w, d, 0.37),
          (w, d), lambda w, d: jax.tree.map(
              lambda a, b: R.apply_scaled_ref(a, b, 0.37), w, d))
    del w, d

    for m in (1, 3, 8, 128):
        sel = leaves_for(m, 4)
        w = tile(f_base, sel, 1.0, m)
        st = tile(f_base, stacked(m, sel), 0.01, m + 1)
        wts = row_weights(f_base, m, m, m + 2)
        check(f"apply_rows M={m}", ops.apply_rows_tree, (w, st, wts),
              lambda w, st, wts: jax.tree.map(
                  lambda a, b: R.apply_rows_ref(a, b, wts), w, st))
        del w, st

    for m in (4, 64):
        sel = leaves_for(m, 1)
        w = tile(f_base, sel, 1.0, m)
        q = tile(q_base, stacked(m, sel), 1, m + 1)
        sc = {n: row_weights(f_base, m, 127, m + 2 + 31 * i)
              for i, (n, _) in enumerate(sel)}
        wts = row_weights(f_base, m, m, m + 3)
        check(f"apply_rows_q M={m}", ops.apply_rows_q_tree,
              (w, q, sc, wts),
              lambda w, q, sc, wts: jax.tree.map(
                  lambda a, b, c: R.apply_rows_q_ref(a, b, c, wts),
                  w, q, sc))
        del w, q, sc


# -- (b) training -----------------------------------------------------------

def phase_train(seed: int) -> None:
    from repro.launch import train

    out = os.path.join(OUT, "train")
    args = train.build_parser().parse_args(
        ["--arch", ARCH, "--option", "C", "--rounds", "3", "--q", "2",
         "--inner-steps", "2", "--seed", str(seed), "--out", out])
    res = train.run_arch(args)
    losses = res["losses"]
    log(f"  run_arch {ARCH} 8x512 option C: losses={losses}")
    if not (all(map(math.isfinite, losses)) and losses[-1] < losses[0]):
        raise AssertionError(f"run_arch losses not finite and falling: "
                             f"{losses}")
    if not res["params_finite"]:
        raise AssertionError("run_arch params not finite")

    args = train.build_parser().parse_args(
        ["--preset", "paper-mnist", "--schedule", "buffered(4)",
         "--rounds", "16", "--eval-every", "8", "--seed", str(seed),
         "--out", out])
    res = train.run_paper_preset(args)
    losses = res["loss"]
    log(f"  paper-mnist buffered(4): eval losses={losses}, "
        f"acc={res['acc']}")
    if not (len(losses) >= 2 and all(map(math.isfinite, losses))
            and losses[-1] < losses[0]):
        raise AssertionError(f"paper-mnist losses not finite and "
                             f"falling: {losses}")
    if not res["params_finite"]:
        raise AssertionError("paper-mnist params not finite")


# -- (c) serving ------------------------------------------------------------

def _serve_args(*extra):
    from repro.launch import serve
    return serve.build_parser().parse_args(
        ["--arch", ARCH, "--personalize", "--requests", str(USERS),
         "--tokens", "16", "--mode", "C", "--personalize-len", "128",
         "--out", os.path.join(OUT, "serve"), *extra])


def phase_serve(cfg, seed: int) -> None:
    import jax
    import jax.numpy as jnp

    from repro.launch import serve
    from repro.launch.train import all_finite
    from repro.models import api

    args = _serve_args("--seed", str(seed))
    key = jax.random.PRNGKey(seed)
    server, _ = serve.make_server(args, cfg, api.init_params(cfg, key))
    users = serve.personalize(args, cfg, server)
    heads = server.stacked_heads(users)
    server.advance_window()
    max_len = args.prompt_len + args.tokens
    prompt = jax.random.randint(key, (USERS, args.prompt_len), 0, cfg.vocab)
    tokens = serve._decode_personalized(cfg, heads, prompt, max_len,
                                        args.prompt_len)
    host_mat = server.stats["host_materializations"]
    log(f"  served {USERS} users, window={server.window}, "
        f"decoded tokens shape={tokens.shape}, "
        f"host_materializations={host_mat}")
    if not all_finite(heads):
        raise AssertionError("personalized heads not finite")
    if not all_finite(server.params):
        raise AssertionError("params after advance_window not finite")
    if tokens.shape != (USERS, args.tokens) or not bool(
            jnp.all((tokens >= 0) & (tokens < cfg.vocab))):
        raise AssertionError(f"decoded tokens out of [0, {cfg.vocab}) or "
                             f"wrong shape {tokens.shape}")
    if host_mat != 0 or server.window != 1:
        raise AssertionError(f"host_materializations={host_mat}, "
                             f"window={server.window}")


# -- --mesh: one window three ways on four chips ----------------------------

def _device_bytes(server):
    """Bytes per device id of the ring's banks, snapshots and params."""
    import jax
    out = {"banks": {}, "snapshots": {}, "params": {}}

    def adder(kind):
        def add(x):
            for s in getattr(x, "addressable_shards", ()):
                d = out[kind]
                d[s.device.id] = d.get(s.device.id, 0) + s.data.nbytes
        return add
    for banks in server.ring._banks.values():
        for bank in banks:
            jax.tree.map(adder("banks"), bank.stacked)
    for snap in server.ring._snapshots.values():
        jax.tree.map(adder("snapshots"), snap)
    jax.tree.map(adder("params"), server.params)
    return out


def phase_mesh(cfg, seed: int) -> None:
    import jax
    import numpy as np

    from repro.launch import serve
    from repro.models import api
    from repro.serving import PersonalizationServer
    from repro.sharding.ctx import cohort_mesh, cohort_model_mesh

    devs = jax.devices()
    if len(devs) != 4:
        raise AssertionError(f"--mesh needs 4 devices, found {len(devs)}")
    m22 = cohort_model_mesh(2)
    log("  2x2 (cohort, model) mesh, device ids [coords]: " + "; ".join(
        " ".join(f"{d.id}{list(getattr(d, 'coords', []))}" for d in row)
        for row in m22.devices))
    key = jax.random.PRNGKey(seed)
    params = api.init_params(cfg, key)
    args = _serve_args("--seed", str(seed))
    ways = {}

    def vmap_server():
        return serve.make_server(args, cfg, params)[0]

    def shard_map_server():
        ref = ways["vmap"]["server_cfg"]
        return PersonalizationServer(params, ref[0], ref[1], modes=("C",),
                                     max_pending=USERS,
                                     cohort_impl="shard_map",
                                     mesh=cohort_mesh())

    def model_axis_server():
        return serve.make_server(_serve_args("--seed", str(seed),
                                             "--model-axis", "2"),
                                 cfg, params)[0]

    for name, build in (("vmap", vmap_server),
                        ("shard_map_1d", shard_map_server),
                        ("model_axis_2x2", model_axis_server)):
        t0, c0 = time.time(), _compile_s[0]
        server = build()
        users = serve.personalize(args, cfg, server)
        heads = jax.device_get(server.stacked_heads(users))
        server.advance_window()
        jax.block_until_ready(server.params)
        wall, comp = time.time() - t0, _compile_s[0] - c0
        by_dev = _device_bytes(server)
        in_use = {d.id: int((d.memory_stats() or {}).get("bytes_in_use", -1))
                  for d in devs}
        ways[name] = {"heads": heads,
                      "params": jax.device_get(server.params),
                      "bytes": by_dev, "in_use": in_use,
                      "server_cfg": (server.loss_fn, server.pcfg),
                      "host_mat": server.stats["host_materializations"]}
        log(f"  {name}: compile_s={comp:.1f} steady_s={wall - comp:.1f} "
            f"host_materializations={ways[name]['host_mat']}")
        for kind, d in by_dev.items():
            log(f"    {kind} bytes per device: "
                f"{dict(sorted(d.items()))}")
        log(f"    memory_stats bytes_in_use per device: {in_use}")
        del server, heads
        gc.collect()

    def diff(a, b):
        la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
        if all(np.array_equal(x, y) for x, y in zip(la, lb)):
            return "bit-equal", 0.0
        return "differs", max(float(np.max(np.abs(
            np.asarray(x, np.float64) - np.asarray(y, np.float64))))
            for x, y in zip(la, lb))

    for a, b in (("vmap", "shard_map_1d"), ("vmap", "model_axis_2x2"),
                 ("shard_map_1d", "model_axis_2x2")):
        for what in ("heads", "params"):
            verdict, err = diff(ways[a][what], ways[b][what])
            log(f"  parity {a} vs {b} {what}: {verdict} "
                f"(max_abs_diff={err:.3e})")

    for name, way in ways.items():
        if way["host_mat"] != 0:
            raise AssertionError(f"{name}: host_materializations="
                                 f"{way['host_mat']}")
        if not all(np.all(np.isfinite(x)) for x in
                   jax.tree.leaves((way["heads"], way["params"]))):
            raise AssertionError(f"{name}: non-finite heads or params")
    for name in ("shard_map_1d", "model_axis_2x2"):
        banks = ways[name]["bytes"]["banks"]
        if len(banks) != 4 or max(banks.values()) > 0.5 * sum(
                banks.values()):
            raise AssertionError(f"{name}: banks not spread over 4 "
                                 f"devices: {banks}")
    p22 = ways["model_axis_2x2"]["bytes"]["params"]
    p1 = ways["vmap"]["bytes"]["params"]
    if len(p22) != 4 or max(p22.values()) >= sum(p1.values()):
        raise AssertionError(f"2x2 params not model-sharded over 4 "
                             f"devices: {p22}")


# -- main -------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--mesh", action="store_true",
                    help="run only the four-chip mesh phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    sys.path.insert(0, os.path.join(HERE, "src"))
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()

    import jax
    from repro.configs import get_config

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    warm = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    log(f"platform={device['platform']} device_kind={device['kind']} "
        f"device_count={device['count']} jax={jax.__version__} "
        f"compile_cache={cache_dir} ({warm} entries at start)")
    log("(smoke timings, not benchmark results)")
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    os.makedirs(OUT, exist_ok=True)

    cfg = get_config(ARCH)
    phases = ([("mesh", lambda: phase_mesh(cfg, args.seed))] if args.mesh
              else [("kernels", lambda: phase_kernels(cfg, args.seed)),
                    ("train", lambda: phase_train(args.seed)),
                    ("serve", lambda: phase_serve(cfg, args.seed))])
    for name, run in phases:
        log(f"phase {name}:")
        t0, c0 = time.time(), _compile_s[0]
        try:
            run()
        except Exception:
            traceback.print_exc()
            log(f"phase {name}: FAILED")
            return 1
        wall, comp = time.time() - t0, _compile_s[0] - c0
        log(f"phase {name}: ok compile_s={comp:.1f} "
            f"steady_s={wall - comp:.1f} wall_s={wall:.1f} "
            f"peak_bytes_in_use={_peak_bytes(dev)}")
        gc.collect()
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
