"""The decode call's device programs are built once per key and kept:
``launch/serve.py``'s ``_decode_programs`` returns the same jitted cast,
init, prefill and step for every call with the same config, ``max_len`` and
user-axis layout, so a call whose arrays alone change runs the programs
of the call before.  These tests check that the builder engages once per
key (the ``decode.build`` span), that kept programs serve the same tokens
as programs built fresh in the call, as decode was written before they
were kept, under every key they may be asked for, and that each program
compiles under the name the benchmark's readers expect."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduce_for_smoke
from repro.core.subset import SubsetSpec, merge_subset
from repro.launch import serve
from repro.models import api

from test_spans import _record

CFG = reduce_for_smoke(get_config("mamba2-130m"))
PARAMS = api.init_params(CFG, jax.random.PRNGKey(0))


def _heads(users):
    """Every leaf stacked per user, each user's leaves nudged apart, so a
    program that mixed users up would serve other tokens."""
    keys = jax.random.split(jax.random.PRNGKey(1), users)
    return jax.tree.map(
        lambda x: jnp.stack([x + 0.05 * jax.random.normal(k, x.shape, x.dtype)
                             for k in keys]), PARAMS)


def _prompt(users, prompt_len, seed=2):
    return jax.random.randint(jax.random.PRNGKey(seed), (users, prompt_len),
                              0, CFG.vocab)


def _fresh_personalized(cfg, heads, prompt, max_len, prompt_len,
                        params=None, spec=None, make_prefill=None):
    """Personalized decode with its programs built inside the call
    (``make_prefill(cfg)`` in place of ``serve.make_prefill``, where
    given)."""
    if spec is not None:
        heads = merge_subset(params, heads)
        p_axes = jax.tree.map(lambda m: 0 if m else None, spec.mask(params))
    else:
        p_axes = 0
    prompt_u = prompt[:, None, :]
    cache = jax.vmap(lambda p, t: api.init_cache(
        cfg, p, serve._init_batch(cfg, t[:, :1]), max_len,
        cfg.activation_dtype), in_axes=(p_axes, 0))(heads, prompt_u)
    prefill = jax.jit(jax.vmap((make_prefill or serve.make_prefill)(cfg),
                               in_axes=(p_axes, 0, 0)))
    step = jax.jit(jax.vmap(
        lambda p, c, t, pos: api.decode_step(cfg, p, c, t, pos),
        in_axes=(p_axes, 0, 0, None)))
    cache = prefill(heads, cache, prompt_u)
    out = serve._generate(step, heads, cache, prompt_u[:, :, -1:],
                          prompt_len - 1, max_len - 1)
    return out[:, 0]


def _fresh_shared(cfg, params, prompt, max_len, prompt_len):
    """Shared-params decode with its programs built inside the call."""
    cache = api.init_cache(cfg, params, serve._init_batch(cfg, prompt[:, :1]),
                           max_len, cfg.activation_dtype)
    prefill = jax.jit(serve.make_prefill(cfg))
    step = jax.jit(lambda p, c, t, pos: api.decode_step(cfg, p, c, t, pos))
    cache = prefill(params, cache, prompt)
    return serve._generate(step, params, cache, prompt[:, -1:],
                           prompt_len - 1, max_len - 1)


def _case(kind, users=2, prompt_len=4, max_len=7, subset="embed/unembed"):
    """(kept, fresh): the same decode through the kept programs and
    through programs built fresh."""
    prompt = _prompt(users, prompt_len)
    if kind == "shared":
        args = (CFG, PARAMS, prompt, max_len, prompt_len)
        return (lambda: serve._decode_shared(*args),
                lambda: _fresh_shared(*args))
    heads, kw = _heads(users), {}
    if kind == "subset":
        spec = SubsetSpec.resolve(subset, PARAMS)
        heads, kw = spec.extract(heads), {"params": PARAMS, "spec": spec}
    args = (CFG, heads, prompt, max_len, prompt_len)
    return (lambda: serve._decode_personalized(*args, **kw),
            lambda: _fresh_personalized(*args, **kw))


def test_programs_are_built_once_per_key(tmp_path):
    serve._decode_programs.cache_clear()
    kept, _ = _case("full")

    def two_calls():
        kept()
        kept()

    spans = _record(tmp_path, two_calls)
    calls = sorted((s, e) for n, s, e in spans if n == "decode")
    builds = [(s, e) for n, s, e in spans if n == "decode.build"]
    assert len(calls) == 2 and len(builds) == 1
    (bs, be), = builds
    assert calls[0][0] <= bs and be <= calls[0][1]     # in the first call


@pytest.mark.parametrize("kind", ["full", "subset", "shared"])
def test_kept_programs_serve_the_fresh_tokens(kind):
    kept, fresh = _case(kind)
    want = np.asarray(fresh())
    for _ in range(2):                     # the build, then the kept ones
        np.testing.assert_array_equal(np.asarray(kept()), want)


def test_alternating_keys_and_shapes_keep_their_own_programs():
    serve._decode_programs.cache_clear()
    cases = {
        "base": _case("full"),
        "users": _case("full", users=3),
        "prompt_len": _case("full", prompt_len=6, max_len=9),
        "max_len": _case("full", max_len=9),
        "unembed": _case("subset"),
        "in_proj": _case("subset", subset="layers/mamba/in_proj"),
        "shared": _case("shared", max_len=9),
    }
    want = {k: np.asarray(fresh()) for k, (_, fresh) in cases.items()}
    order = list(cases) + list(reversed(list(cases)))
    for k in order:
        np.testing.assert_array_equal(np.asarray(cases[k][0]()), want[k],
                                      err_msg=k)
    # one entry per config, max_len and layout: shapes share programs
    info = serve._decode_programs.cache_info()
    assert (info.misses, info.currsize) == (5, 5)
    assert info.hits == len(order) - 5


@pytest.mark.parametrize("personal", [None, True])
def test_programs_compile_under_their_own_names(personal):
    cast, init, prefill, step = serve._decode_programs(CFG, 7, personal)
    params = _heads(2) if personal else PARAMS
    prompt = _prompt(2, 4)[:, None, :] if personal else _prompt(2, 4)
    cache = init(params, prompt)
    lowered = {"cast": cast.lower(params),
               "init": init.lower(params, prompt),
               "prefill": prefill.lower(params, cache, prompt),
               "step": step.lower(params, cache, prompt[..., -1:],
                                  jnp.int32(3))}
    modules = {k: low.as_text().split("module @", 1)[1].split(" ", 1)[0]
               for k, low in lowered.items()}
    assert modules == {"cast": "jit_decode_weights",
                       "init": "jit_init_decode_cache",
                       "prefill": "jit_prefill",
                       "step": "jit__lambda"}
