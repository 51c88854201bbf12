"""The decode call's compute view of the weights (``api.decode_weights``):
each leaf the decode step reads only through ``.astype(compute dtype)``
is cast to bfloat16 once per call by the kept ``jit_decode_weights``
program, so the token step no longer casts the weights at every token.
These tests check, at smoke size with bfloat16 compute, that the step on
the view returns the logits and cache of the step on the float32 weights
bit for bit; that the kept decode paths serve the tokens of programs
built fresh on the float32 weights, under every user-axis layout; which
leaves the view casts and which it leaves in float32; that the step
program on the view holds no cast of a weight to bfloat16, where on the
float32 weights it holds one per matrix; and the ``decode.cast`` span,
once a call, after the prefill (which keeps the float32 weights) and
before the first token step."""
import dataclasses

import jax
import jax.extend.core as jex
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduce_for_smoke
from repro.core.subset import SubsetSpec
from repro.launch import serve
from repro.models import api

from test_decode_programs import (_fresh_personalized, _fresh_shared,
                                  _prompt)
from test_spans import _record

ARCHS = ("mamba2-130m", "zamba2-7b")
ADAPTERS = "hybrid/adapter_in,hybrid/adapter_out"
# leaves the decode step reads in float32, which the view leaves alone
F32_LEAVES = {"unembed", "a_log", "dt_bias", "d_skip", "gate_norm", "ln",
              "final_norm", "ln_in", "ln_mlp"}


def _bf16(arch):
    return dataclasses.replace(reduce_for_smoke(get_config(arch)),
                               dtype="bfloat16")


def _params(cfg):
    return api.init_params(cfg, jax.random.PRNGKey(0))


def _leaf_names(tree):
    return {jax.tree_util.keystr(k): k[-1].key
            for k, _ in jax.tree_util.tree_leaves_with_path(tree)}


def _heads(params, users):
    """Every leaf stacked per user, each user's leaves nudged apart."""
    keys = jax.random.split(jax.random.PRNGKey(1), users)
    return jax.tree.map(
        lambda x: jnp.stack([x + 0.05 * jax.random.normal(k, x.shape, x.dtype)
                             for k in keys]), params)


def _assert_trees_equal(got, want):
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.dtype == w.dtype
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("arch", ARCHS)
def test_view_keeps_the_tree_and_the_float32_leaves(arch):
    cfg = _bf16(arch)
    params = _params(cfg)
    view = api.decode_weights(cfg, params)
    assert jax.tree.structure(view) == jax.tree.structure(params)
    names = _leaf_names(params)
    flat = dict(zip(names, jax.tree.leaves(view)))
    orig = dict(zip(names, jax.tree.leaves(params)))
    for path, name in names.items():
        if name in F32_LEAVES:
            assert flat[path].dtype == jnp.float32, path
            assert flat[path] is orig[path], path
        else:
            assert flat[path].dtype == jnp.bfloat16, path
    assert {n for n in names.values() if n not in F32_LEAVES} >= {
        "tok", "in_proj", "out_proj", "conv_w", "conv_b"}


@pytest.mark.parametrize("arch", ["lm-family", "float32-compute"])
def test_view_is_the_params_where_decode_casts_nothing(arch):
    if arch == "lm-family":
        cfg = reduce_for_smoke(get_config("gemma2-2b"))
    else:
        cfg = reduce_for_smoke(get_config("mamba2-130m"))
    params = _params(cfg)
    view = api.decode_weights(cfg, params)
    if arch == "lm-family":
        assert view is params
    _assert_trees_equal(view, params)


@pytest.mark.parametrize("arch", ARCHS)
def test_step_on_the_view_is_bit_equal(arch):
    cfg = _bf16(arch)
    params = _params(cfg)
    view = api.decode_weights(cfg, params)
    step = jax.jit(lambda p, c, t, pos: api.decode_step(cfg, p, c, t, pos))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 6), 0, cfg.vocab)
    want = got = api.init_cache(cfg, params, {"tokens": toks[:, :1]}, 8,
                                cfg.activation_dtype)
    for pos in range(toks.shape[1]):
        tok = toks[:, pos:pos + 1]
        want_logits, want = step(params, want, tok, jnp.int32(pos))
        got_logits, got = step(view, got, tok, jnp.int32(pos))
        _assert_trees_equal(got_logits, want_logits)
        _assert_trees_equal(got, want)


def _case(kind, users=2, prompt_len=4, max_len=8):
    """(kept, fresh): one decode through the kept programs, which run on
    the view, and through programs built fresh on the float32 weights."""
    arch = "zamba2-7b" if kind == "adapters" else "mamba2-130m"
    cfg = _bf16(arch)
    params = _params(cfg)
    prompt = _prompt(users, prompt_len) % cfg.vocab
    if kind == "shared":
        args = (cfg, params, prompt, max_len, prompt_len)
        return (lambda: serve._decode_shared(*args),
                lambda: _fresh_shared(*args))
    heads, kw = _heads(params, users), {}
    if kind != "full":
        spec = SubsetSpec.resolve(
            ADAPTERS if kind == "adapters" else "embed/unembed", params)
        heads, kw = spec.extract(heads), {"params": params, "spec": spec}
    args = (cfg, heads, prompt, max_len, prompt_len)
    return (lambda: serve._decode_personalized(*args, **kw),
            lambda: _fresh_personalized(*args, **kw))


@pytest.mark.parametrize("kind", ["full", "subset", "adapters", "shared"])
def test_decode_on_the_view_serves_the_float32_tokens(kind):
    kept, fresh = _case(kind)
    want = np.asarray(fresh())
    for _ in range(2):                     # the build, then the kept ones
        np.testing.assert_array_equal(np.asarray(kept()), want)


_VIEWS = {"slice", "squeeze", "dynamic_slice", "gather", "reshape",
          "broadcast_in_dim", "transpose", "copy", "split"}


def _weight_casts(jaxpr, weights):
    """The casts to bfloat16, in ``jaxpr`` and the programs it calls, of a
    variable in ``weights`` or of a slice or reshape of one."""
    weights, casts = set(weights), []
    for eqn in jaxpr.eqns:
        src = eqn.invars[0] if eqn.invars else None
        held = isinstance(src, jex.Var) and src in weights
        if eqn.primitive.name == "convert_element_type":
            if held and eqn.params["new_dtype"] == jnp.bfloat16:
                casts.append(eqn)
        elif held and eqn.primitive.name in _VIEWS:
            weights.update(eqn.outvars)
        for sub in eqn.params.values():
            if isinstance(sub, jex.ClosedJaxpr):
                sub = sub.jaxpr
            if isinstance(sub, jex.Jaxpr):
                inner = {v for v, o in zip(sub.invars, eqn.invars)
                         if isinstance(o, jex.Var) and o in weights}
                casts += _weight_casts(sub, inner)
    return casts


@pytest.mark.parametrize("arch", ARCHS)
def test_step_program_on_the_view_casts_no_weight(arch):
    cfg = _bf16(arch)
    heads = _heads(_params(cfg), 2)
    cast, init, _, step = serve._decode_programs(cfg, 8, True)
    prompt = _prompt(2, 4)[:, None, :] % cfg.vocab
    cache = init(heads, prompt)

    def casts(p):
        closed = jax.make_jaxpr(step)(p, cache, prompt[..., -1:],
                                      jnp.int32(3))
        n = len(jax.tree.leaves(p))
        return _weight_casts(closed.jaxpr, closed.jaxpr.invars[:n])

    # on the float32 weights the step casts at every token the embedding,
    # and each layer's in_proj, conv_w, conv_b and out_proj (and more in
    # the hybrid)
    assert len(casts(heads)) >= 1 + 4 * cfg.n_layers
    assert casts(cast(heads)) == []


def test_each_call_casts_once_between_prefill_and_first_step(tmp_path):
    serve._decode_programs.cache_clear()
    kept, _ = _case("full")

    def two_calls():
        kept()
        kept()

    spans = _record(tmp_path, two_calls)
    calls = sorted((s, e) for n, s, e in spans if n == "decode")
    assert len(calls) == 2
    assert len([n for n, _, _ in spans if n == "decode.build"]) == 1
    for cs, ce in calls:
        inside = [(n, s, e) for n, s, e in spans if cs <= s and e <= ce]
        (_, cast_start, cast_end), = [x for x in inside
                                      if x[0] == "decode.cast"]
        (_, _, prefill_end), = [x for x in inside
                                if x[0] == "decode.prefill"]
        (_, first_start, _), = [x for x in inside
                                if x[0] == "decode.first_step"]
        assert prefill_end <= cast_start and cast_end <= first_start
