"""Personalized serving driver.

Decode is driven through :class:`repro.serving.PersonalizationServer`:
each request is a *user* with their own token stream; the server coalesces
all users' personalization (mode "B" one-step fine-tune or mode "C"
Moreau-envelope prox solve) into one pow2-bucketed cohort call, and decode
runs vmapped over the stacked per-user heads — no per-user Python loop on
either side.  Prompt prefill is a single jitted ``lax.scan`` dispatch
(prompt tokens advance on device); the decode loop proper stays
step-by-step because each token depends on the previous argmax.

  PYTHONPATH=src python -m repro.launch.serve --arch mamba2-130m --smoke \
      --personalize --requests 4 --tokens 16

``--personal-subset PREFIXES`` switches personalization to partial-model
(head-only) form: only the named param subtrees are personalized, banked,
and stacked per user; decode merges the stacked heads over the one shared
backbone and vmaps with ``in_axes=None`` on backbone leaves, so backbone
memory stays O(1) in the user count.

``--listen PORT`` swaps the one-shot decode for a network front-end: the
PersonalizationServer is wrapped in a
:class:`repro.serving.transport.TransportServer` and a second OS process
(or a fleet of them) drives personalization over the socket with
:class:`repro.serving.transport.TransportClient` — submit a token batch
shaped like the model loss expects (``{"tokens": int32[1, L], "labels":
int32[1, L]}``, L a multiple of the arch's SSM chunk, plus ``visual`` /
``frames`` leaves for the archs that take them — see ``_user_batch``),
poll the personalized head back, decode locally or fetch it again later
via HEAD.  A malformed batch fails its flush group with a typed
``server_error`` reply; the server keeps serving.  ``--flush-ms`` bounds queueing latency,
``--window-ms`` drives the aggregation-window boundary on a wall clock,
``--max-inflight`` is the backpressure bound (queue full → BUSY frames).

  PYTHONPATH=src python -m repro.launch.serve --arch mamba2-130m --smoke \
      --listen 7777 --mode C

``--model-axis M`` serves over the 2-D ``("cohort", "model")`` mesh
(:func:`repro.sharding.ctx.cohort_model_mesh`): cohort slices are
model-parallel device groups, and every capacity-bound artifact — delta
banks, ring snapshots, head rows, the global params — is stored
model-axis-sharded per :func:`repro.sharding.rules.param_shardings`.
Served bits are identical to the 1-D path (the ``serve_mesh`` bench gates
it); what the model axis buys is per-device residency.

Multi-process serving: ``--coordinator HOST:PORT --num-processes N
--process-id I`` runs ``jax.distributed.initialize`` before any device
use, so N OS processes (one per host) form one JAX runtime whose global
device set backs the mesh.  ``--num-processes 1`` (the default when only
``--coordinator`` is given) is the single-host spelling and is what CI
boots:

  PYTHONPATH=src python -m repro.launch.serve --arch mamba2-130m --smoke \
      --listen 0 --serve-seconds 2 --coordinator 127.0.0.1:12377 \
      --num-processes 1 --process-id 0
"""
from __future__ import annotations

import argparse
import asyncio
import contextlib
import functools
import json
import os
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config, reduce_for_smoke
from repro.core.types import PersAFLConfig
from repro.data import synthetic_token_batch
from repro.launch.compile_cache import enable_compile_cache
from repro.models import api
from repro.spans import span


def _personalize_len(cfg, n: int) -> int:
    """SSM/hybrid archs run the chunked SSD scan over the personalization
    stream, so the length rounds up to the next chunk multiple."""
    chunk = cfg.ssm.chunk if getattr(cfg, "ssm", None) else 1
    return -(-max(n, 1) // chunk) * chunk


def _user_batch(cfg, seed: int, length: int):
    """One user's personalization stream (leaves lead with batch dim 1)."""
    data = synthetic_token_batch(seed, 1, length, cfg.vocab)
    batch = {k: jnp.asarray(v) for k, v in data.items()}
    if cfg.n_visual_tokens:
        batch["visual"] = jnp.zeros((1, cfg.n_visual_tokens, cfg.d_model),
                                    cfg.activation_dtype)
    if cfg.is_encdec:
        batch["frames"] = jnp.zeros((1, cfg.enc_len, cfg.d_model),
                                    cfg.activation_dtype)
    return batch


def make_prefill(cfg):
    """Single-dispatch prompt prefill.

    The prompt's first L−1 tokens only exist to warm the cache, so they
    advance inside one jitted ``lax.scan`` instead of paying one Python
    dispatch per token; the caller then decodes from the prompt's last
    token.
    """
    def prefill(params, cache, prompt):
        def body(c, t):
            tok = jax.lax.dynamic_slice_in_dim(prompt, t, 1, axis=1)
            _, c = api.decode_step(cfg, params, c, tok, t)
            return c, None
        steps = jnp.arange(prompt.shape[1] - 1, dtype=jnp.int32)
        cache, _ = jax.lax.scan(body, cache, steps)
        return cache
    return prefill


def _init_batch(cfg, tokens):
    """Cache-init batch: token ids plus the encdec encoder frames."""
    batch = {"tokens": tokens}
    if cfg.is_encdec:
        batch["frames"] = jnp.zeros(
            (tokens.shape[0], cfg.enc_len, cfg.d_model),
            cfg.activation_dtype)
    return batch


def _generate(step, params, cache, tok, start, stop):
    """The greedy token loop from position ``start`` to ``stop``: one step
    dispatch and one argmax per token, since each token is the previous
    step's argmax.  ``tok`` and every generated token are ``[..., 1]``;
    returns them joined on the last axis, or None for an empty range."""
    generated = []
    for pos in range(start, stop):
        # the first step waits for the prefill on the device, and on the
        # first call of a shape it also traces and compiles the step
        with span("decode.first_step" if pos == start else "decode.step"):
            logits, cache = step(params, cache, tok, jnp.int32(pos))
            tok = jnp.argmax(logits[..., -1, :], axis=-1)[..., None] \
                .astype(jnp.int32)
            generated.append(tok)
    with span("decode.collect"):
        jax.block_until_ready(tok)
        return jnp.concatenate(generated, axis=-1) if generated else None


@functools.lru_cache(maxsize=8)
def _decode_programs(cfg, max_len, personal):
    """The decode call's four device programs: the weights' cast to the
    compute dtype (``api.decode_weights``), cache init, prefill and token
    step, built once per key and kept.  JAX's jit cache is keyed on the
    function object, so fresh ``jax.jit`` objects in every call would
    retrace and reload the programs each time; kept ones run the
    executables of the call before, and compile again only for a new
    shape (user count, prompt length).  The cast runs once a call, so
    the token step reads weights already in the compute dtype instead of
    casting them again at every token.  Cache init and the prefill take
    the weights as given: the prefill's scan already hoists its casts
    out of its loop, and on TPU v5e its loop rounds differently on a
    pre-cast ``in_proj`` than on the float32 one, where the step is bit
    for bit the same on either.  The call casts after the prefill, so
    the cast and the prefill's own hoisted casts are never held at once.

    ``personal`` says which leaves carry the user axis: None for the
    shared params (nothing is vmapped), True for every leaf, or a
    personal-subset mask flattened to ``(treedef, bools)``.  The programs
    take params, cache, prompt and position as arguments and close over
    no array.  The step stays an anonymous lambda (``jit__lambda``, the
    program ``decode_step_ms`` reads); the others compile as
    ``jit_decode_weights``, ``jit_init_decode_cache`` and ``jit_prefill``.
    The cast is elementwise, so it needs no vmap over the user axis.
    """
    with span("decode.build", programs=4):
        if personal is None or personal is True:
            p_axes = 0
        else:
            treedef, mask = personal
            p_axes = treedef.unflatten([0 if m else None for m in mask])

        def vmap(fn, *axes):
            if personal is None:
                return fn
            return jax.vmap(fn, in_axes=(p_axes, *axes))

        def decode_weights(p):
            return api.decode_weights(cfg, p)

        def init_decode_cache(p, t):
            return api.init_cache(cfg, p, _init_batch(cfg, t[:, :1]),
                                  max_len, cfg.activation_dtype)

        return (jax.jit(decode_weights),
                jax.jit(vmap(init_decode_cache, 0)),
                jax.jit(vmap(make_prefill(cfg), 0, 0)),
                jax.jit(vmap(lambda p, c, t, pos: api.decode_step(
                    cfg, p, c, t, pos), 0, 0, None)))


def _decode_shared(cfg, params, prompt, max_len, prompt_len):
    """Batched decode with the shared global params (no personalization)."""
    with span("decode", rows=prompt.shape[0]):
        cast, init, prefill, step = _decode_programs(cfg, max_len, None)
        with span("decode.init"):
            cache = init(params, prompt)
        with span("decode.prefill"):
            cache = prefill(params, cache, prompt)
        with span("decode.cast"):
            view = cast(params)
        return _generate(step, view, cache, prompt[:, -1:],
                         prompt_len - 1, max_len - 1)


def _decode_personalized(cfg, heads, prompt, max_len, prompt_len,
                         params=None, spec=None):
    """Per-user decode: every request carries its own personalized head, so
    params/cache/tokens all vmap over the user axis (inner batch of 1).

    With a ``personal_subset`` (``spec``/``params`` given) ``heads`` is a
    stacked *subset* tree; merging it over the shared backbone yields a
    mixed tree whose personal leaves carry the user axis and whose backbone
    leaves do not, and a pytree ``in_axes`` (0 on personal leaves, None on
    backbone) vmaps it without replicating the backbone per user.  The
    token step runs on the merged tree cast to the compute dtype once per
    call, backbone leaves included; the cast is dropped with the call.
    """
    if spec is not None:
        from repro.core.subset import merge_subset
        heads = merge_subset(params, heads)
        mask, treedef = jax.tree.flatten(spec.mask(params))
        personal = (treedef, tuple(mask))
    else:
        personal = True
    prompt_u = prompt[:, None, :]                      # [U, 1, L]
    with span("decode", rows=prompt.shape[0]):
        cast, init, prefill, step = _decode_programs(cfg, max_len, personal)
        with span("decode.init"):
            cache = init(heads, prompt_u)
        with span("decode.prefill"):
            cache = prefill(heads, cache, prompt_u)
        with span("decode.cast"):
            view = cast(heads)
        out = _generate(step, view, cache, prompt_u[:, :, -1:],   # [U,1,1]
                        prompt_len - 1, max_len - 1)
    return None if out is None else out[:, 0]


def _serve_transport(args, server) -> None:
    """Run the socket front-end until --serve-seconds elapse or ^C."""
    from repro.serving.transport import PROTOCOL_VERSION, TransportServer
    ts = TransportServer(server, port=args.listen, flush_ms=args.flush_ms,
                         window_ms=args.window_ms,
                         max_inflight=args.max_inflight)

    async def run():
        await ts.start()
        print(f"serving personalization on 127.0.0.1:{ts.port} "
              f"(wire protocol v{PROTOCOL_VERSION}, mode {args.mode}, "
              f"flush_ms={args.flush_ms}, window_ms={args.window_ms}, "
              f"max_inflight={args.max_inflight})", flush=True)
        try:
            if args.serve_seconds is not None:
                await asyncio.sleep(args.serve_seconds)
            else:
                await asyncio.Event().wait()
        finally:
            await ts.stop()
            print(f"transport stopped after "
                  f"{ts.stats['connections']} connections / "
                  f"{ts.stats['frames']} frames "
                  f"(host_materializations="
                  f"{server.stats['host_materializations']})", flush=True)

    try:
        asyncio.run(run())
    except KeyboardInterrupt:
        pass


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=4,
                    help="concurrent users (decode batch size)")
    ap.add_argument("--tokens", type=int, default=16, help="tokens to decode")
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--personalize", action="store_true",
                    help="serve per-user personalized heads through "
                         "PersonalizationServer")
    ap.add_argument("--personalize-len", type=int, default=None,
                    help="per-user personalization stream length "
                         "(default: --prompt-len)")
    ap.add_argument("--mode", choices=("B", "C"), default="C",
                    help="personalization mode: B = one-step MAML "
                         "fine-tune, C = Moreau prox solve")
    ap.add_argument("--personal-subset", default=None, metavar="PREFIXES",
                    help="comma-separated param-path prefixes (checkpoint "
                         "spelling, e.g. 'head' or 'blocks/#11') — only "
                         "these leaves are personalized per user; the "
                         "backbone stays shared and is never banked")
    ap.add_argument("--delta-dtype", choices=("fp32", "int8"),
                    default="fp32",
                    help="delta banking codec: int8 quantizes banked "
                         "delta/residual rows (error feedback keeps "
                         "convergence) and compresses the transport wire "
                         "for codec_ok clients")
    ap.add_argument("--lam", type=float, default=30.0)
    ap.add_argument("--alpha", type=float, default=0.01)
    ap.add_argument("--inner-steps", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="experiments/serve")
    ap.add_argument("--listen", type=int, default=None, metavar="PORT",
                    help="serve personalization over a socket transport "
                         "on this port (0 = ephemeral) instead of the "
                         "one-shot decode; implies --personalize")
    ap.add_argument("--flush-ms", type=float, default=10.0,
                    help="transport deadline flush: a partial request "
                         "queue older than this is flushed by timer")
    ap.add_argument("--window-ms", type=float, default=None,
                    help="advance the aggregation window on this "
                         "wall-clock period (default: only on ADVANCE "
                         "frames)")
    ap.add_argument("--max-inflight", type=int, default=256,
                    help="transport backpressure: max open tickets "
                         "before SUBMIT gets a BUSY frame")
    ap.add_argument("--serve-seconds", type=float, default=None,
                    help="with --listen: stop after this many seconds "
                         "(default: serve until interrupted)")
    ap.add_argument("--model-axis", type=int, default=None, metavar="M",
                    help="serve over the 2-D ('cohort', 'model') mesh with "
                         "M-way model parallelism (device count must be a "
                         "multiple of M); banks/snapshots/heads/params are "
                         "stored model-axis-sharded, served bits match the "
                         "1-D path")
    ap.add_argument("--coordinator", default=None, metavar="HOST:PORT",
                    help="jax.distributed coordinator address; given alone "
                         "it implies --num-processes 1 (single-host boot)")
    ap.add_argument("--num-processes", type=int, default=None,
                    help="total process count for jax.distributed."
                         "initialize (multi-host serving: one process per "
                         "host, every process runs the same command with "
                         "its own --process-id)")
    ap.add_argument("--process-id", type=int, default=0,
                    help="this process's rank in [0, --num-processes)")
    ap.add_argument("--profile-dir", default=None, metavar="DIR",
                    help="record the serve and decode run in a "
                         "jax.profiler trace under DIR: device planes "
                         "and the program's persafl.* spans")
    return ap


def personalization_config(args) -> PersAFLConfig:
    """Mode B/C hyper-parameters from the CLI flags."""
    return PersAFLConfig(option="C", lam=args.lam, alpha=args.alpha,
                         inner_steps=args.inner_steps, inner_eta=0.01)


def make_server(args, cfg, params):
    """The PersonalizationServer the CLI flags describe, plus the resolved
    personal subset (None for full-model personalization)."""
    from repro.core.subset import SubsetSpec
    from repro.serving import PersonalizationServer
    loss = lambda p, b: api.loss_fn(cfg, p, b)              # noqa: E731
    pcfg = personalization_config(args)
    subset_spec = SubsetSpec.resolve(args.personal_subset, params)
    mesh_kw = {}
    if args.model_axis is not None:
        from repro.sharding.ctx import cohort_model_mesh
        from repro.sharding.rules import param_shardings
        mesh = cohort_model_mesh(args.model_axis)
        mesh_kw = {"cohort_impl": "shard_map", "mesh": mesh,
                   "param_shardings": param_shardings(cfg, params, mesh)}
        print(f"2-D mesh: cohort={mesh.devices.shape[0]} × "
              f"model={mesh.devices.shape[1]} over "
              f"{mesh.devices.size} devices", flush=True)
    server = PersonalizationServer(params, loss, pcfg, modes=(args.mode,),
                                   max_pending=max(args.requests, 1),
                                   personal_subset=subset_spec,
                                   delta_dtype=args.delta_dtype, **mesh_kw)
    return server, subset_spec


def personalize(args, cfg, server):
    """Submit one personalization stream per user (``--requests`` users)
    and flush them as one cohort; returns the user ids in order."""
    plen = _personalize_len(cfg, args.personalize_len
                            if args.personalize_len is not None
                            else args.prompt_len)
    tickets = [server.submit(f"user{u}",
                             _user_batch(cfg, args.seed + u, plen),
                             mode=args.mode)
               for u in range(args.requests)]
    server.flush()
    stats = server.stats
    print(f"personalized {args.requests} users through "
          f"PersonalizationServer (mode {args.mode}, len={plen}, "
          f"cohort_calls={stats['cohort_calls']}, "
          f"host_materializations={stats['host_materializations']})",
          flush=True)
    return [t.user for t in tickets]


def main(argv=None):
    args = build_parser().parse_args(argv)
    enable_compile_cache()

    if args.listen is not None:
        args.personalize = True

    if args.coordinator is not None or args.num_processes is not None:
        # must run before any device/backend use in this process
        jax.distributed.initialize(
            coordinator_address=args.coordinator or "127.0.0.1:12377",
            num_processes=args.num_processes or 1,
            process_id=args.process_id)
        print(f"jax.distributed: process {jax.process_index()}/"
              f"{jax.process_count()}, "
              f"{jax.local_device_count()}/{jax.device_count()} local "
              f"devices", flush=True)

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = reduce_for_smoke(cfg)
    key = jax.random.PRNGKey(args.seed)
    params = api.init_params(cfg, key)

    profile = (jax.profiler.trace(args.profile_dir) if args.profile_dir
               else contextlib.nullcontext())
    with profile:
        serve_and_decode(args, cfg, params, key)


def serve_and_decode(args, cfg, params, key):
    """Personalize and decode (or serve over the socket) as the flags
    say, and write the run's record under ``--out``."""
    B = args.requests
    max_len = args.prompt_len + args.tokens
    prompt = jax.random.randint(key, (B, args.prompt_len), 0, cfg.vocab)

    heads = None
    server_stats = None
    subset_spec = None
    if args.personalize:
        server, subset_spec = make_server(args, cfg, params)
        if args.listen is not None:
            _serve_transport(args, server)
            return
        heads = server.stacked_heads(personalize(args, cfg, server))
        server_stats = server.stats

    t0 = time.time()
    if heads is not None:
        out_tokens = _decode_personalized(cfg, heads, prompt, max_len,
                                          args.prompt_len,
                                          params=params, spec=subset_spec)
    else:
        out_tokens = _decode_shared(cfg, params, prompt, max_len,
                                    args.prompt_len)
    wall = time.time() - t0
    tps = B * args.tokens / wall
    print(f"decoded {args.tokens} tokens × {B} requests "
          f"in {wall:.2f}s ({tps:.1f} tok/s)")
    if out_tokens is not None:
        print("sample:", out_tokens[0].tolist())
    os.makedirs(args.out, exist_ok=True)
    record = {"arch": cfg.arch_id, "tok_per_s": tps,
              "personalized": args.personalize, "mode": args.mode,
              "users": B, "model_axis": args.model_axis,
              "personal_subset": (subset_spec.descriptor()
                                  if subset_spec is not None else None),
              "delta_dtype": args.delta_dtype}
    if server_stats is not None:
        record["ring_bytes_per_user"] = server_stats["ring_bytes_per_user"]
        record["ring_bytes_saved_per_user"] = \
            server_stats["ring_bytes_saved_per_user"]
    if server_stats is not None:
        record["host_materializations"] = \
            server_stats["host_materializations"]
    with open(os.path.join(args.out, f"serve_{cfg.arch_id}.json"), "w") as f:
        json.dump(record, f, indent=2)


if __name__ == "__main__":
    main()
