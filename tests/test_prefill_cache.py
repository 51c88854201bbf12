"""The full-sequence prefill (``ssm_lm.ssm_lm_prefill``): one forward over
the prompt's first L-1 tokens that writes the decode cache, reading each
weight once.  ``api.prefill`` runs it for the ``ssm`` family and the
hybrids; the other families step.  Its oracle, kept here, is that
stepping: a ``lax.scan`` of ``api.decode_step`` over those tokens.

At smoke size in float32 (SSD chunks of 16) the two caches must agree
within 1e-5 relative, leaf by leaf (each layer's conv rows and SSM state,
each invocation's K and V), and the logits of the steps that follow
within 5e-4, as ``test_prefill_decode_equivalence`` holds the step to the
forward.  The prompts run from one token (nothing to write) past two
chunks (the last chunk padded), on mamba2-130m, the Zamba2-7B cut with
its blocks invoked A B and A B A B, and Zamba2-1.2B, whose sliding window
makes each K/V cache a ring that the longest prompt wraps.  The
prefill's SSD contracts at HIGHEST and the training forward at the
default.  The kept decode programs still serve the tokens of programs
built fresh around the oracle."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduce_for_smoke
from repro.core.subset import SubsetSpec
from repro.launch import serve
from repro.models import api, ssm_lm

from test_decode_programs import _fresh_personalized

ZAMBA2_7B = reduce_for_smoke(get_config("zamba2-7b"))
CFGS = {
    "mamba2-130m": reduce_for_smoke(get_config("mamba2-130m")),
    "zamba2-7b-A-B": ZAMBA2_7B,
    # 4 invocations over the 2 blocks after one plain layer
    "zamba2-7b-A-B-A-B": dataclasses.replace(
        ZAMBA2_7B, n_layers=5, hybrid_layer_ids=(1, 2, 3, 4)),
    "zamba2-1.2b": reduce_for_smoke(get_config("zamba2-1.2b")),
}
GEN = 4                       # steps decoded after the prompt
ADAPTERS = "hybrid/adapter_in,hybrid/adapter_out"


def _lengths(cfg):
    s = cfg.ssm
    return sorted({1, 2, s.conv_width - 1, s.chunk - 1, s.chunk,
                   s.chunk + 1, 2 * s.chunk + 3})


CASES = [(name, n) for name, cfg in CFGS.items() for n in _lengths(cfg)]


def stepped_prefill(cfg):
    """The oracle: the decode step over the prompt's first L-1 tokens."""
    def prefill(params, cache, prompt):
        def body(c, t):
            tok = jax.lax.dynamic_slice_in_dim(prompt, t, 1, axis=1)
            _, c = api.decode_step(cfg, params, c, tok, t)
            return c, None
        steps = jnp.arange(prompt.shape[1] - 1, dtype=jnp.int32)
        return jax.lax.scan(body, cache, steps)[0]
    return prefill


def full_prefill(cfg):
    """The full-sequence prefill, as ``api.prefill`` calls it."""
    def prefill(params, cache, prompt):
        return ssm_lm.ssm_lm_prefill(cfg, params, cache, prompt[:, :-1])
    return prefill


@pytest.mark.parametrize("name,want", [("mamba2-130m", full_prefill),
                                       ("zamba2-7b-A-B", full_prefill),
                                       ("gemma2-2b", stepped_prefill)])
def test_api_prefill_runs_the_full_forward_for_ssm_and_hybrid(name, want):
    cfg = CFGS.get(name) or reduce_for_smoke(get_config(name))
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    toks = jnp.zeros((2, 19), jnp.int32)
    cache0 = api.init_cache(cfg, params, {"tokens": toks[:, :1]}, 19,
                            jnp.float32)
    jaxpr = lambda f: str(jax.make_jaxpr(f)(params, cache0, toks))
    assert jaxpr(functools.partial(api.prefill, cfg)) == jaxpr(want(cfg))


def test_cases_cover_both_families_and_a_wrapped_ring():
    assert all(cfg.family in ("ssm", "hybrid") for cfg in CFGS.values())
    # the ring of Zamba2-1.2B's longest prompt wraps
    cfg = CFGS["zamba2-1.2b"]
    assert max(_lengths(cfg)) - 1 > cfg.sliding_window


@pytest.mark.parametrize("name,L", CASES,
                         ids=[f"{n}-L{L}" for n, L in CASES])
def test_prefill_writes_the_stepped_cache(name, L):
    cfg = CFGS[name]
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(L), (2, L + GEN), 0,
                              cfg.vocab)
    cache0 = api.init_cache(cfg, params, {"tokens": toks[:, :1]}, L + GEN,
                            jnp.float32)
    got = jax.jit(full_prefill(cfg))(params, cache0, toks[:, :L])
    want = jax.jit(stepped_prefill(cfg))(params, cache0, toks[:, :L])
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for path, g in jax.tree_util.tree_leaves_with_path(got):
        w = functools.reduce(lambda t, k: t[k.key], path, want)
        assert g.shape == w.shape and g.dtype == w.dtype
        scale = float(jnp.max(jnp.abs(w)))
        gap = float(jnp.max(jnp.abs(g - w)))
        if L == 1:                        # nothing written: the initial cache
            assert gap == 0 and scale == 0, jax.tree_util.keystr(path)
        else:
            assert gap <= 1e-5 * scale, (jax.tree_util.keystr(path), gap,
                                         scale)

    step = jax.jit(lambda c, t, pos: api.decode_step(cfg, params, c, t, pos))
    for pos in range(L - 1, L + GEN - 1):
        tok = toks[:, pos:pos + 1]
        lg, got = step(got, tok, jnp.int32(pos))
        lw, want = step(want, tok, jnp.int32(pos))
        err = float(jnp.max(jnp.abs(lg - lw)))
        assert err < 5e-4, (pos, err)


def _highest_dots(lowered):
    """(dot_generals, those at Precision.HIGHEST) of a lowered program."""
    dots = [ln for ln in lowered.as_text().splitlines() if "dot_general" in ln]
    return len(dots), sum("precision = [HIGHEST, HIGHEST]" in ln
                          for ln in dots)


@pytest.mark.parametrize("name", ["mamba2-130m", "zamba2-7b-A-B"])
def test_only_the_prefill_contracts_its_scan_at_highest(name):
    """A TPU rounds a default-precision float32 contraction's inputs to
    bfloat16; the prefill's SSD state is read by every later step, so its
    contractions run at HIGHEST, while the training forward keeps the
    default (its program must not change)."""
    cfg = CFGS[name]
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    toks = jnp.zeros((2, 2 * cfg.ssm.chunk), jnp.int32)
    cache0 = api.init_cache(cfg, params, {"tokens": toks[:, :1]},
                            toks.shape[1], jnp.float32)
    dots, highest = _highest_dots(jax.jit(full_prefill(cfg)).lower(
        params, cache0, toks))
    assert 0 < highest < dots           # the scan's, not the projections'
    batch = {"tokens": toks, "labels": toks}
    assert _highest_dots(jax.jit(lambda w: api.loss_fn(cfg, w, batch))
                         .lower(params))[1] == 0


def _heads(cfg, users):
    """Every leaf stacked per user, each user's leaves nudged apart."""
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    keys = jax.random.split(jax.random.PRNGKey(1), users)
    return params, jax.tree.map(
        lambda x: jnp.stack([x + 0.05 * jax.random.normal(k, x.shape, x.dtype)
                             for k in keys]), params)


@pytest.mark.parametrize("name,subset", [
    ("mamba2-130m", None), ("mamba2-130m", "embed/unembed"),
    ("zamba2-7b-A-B", None), ("zamba2-7b-A-B", ADAPTERS)],
    ids=["mamba2-130m-full", "mamba2-130m-subset", "zamba2-7b-full",
         "zamba2-7b-adapters"])
def test_kept_programs_serve_the_stepped_tokens(name, subset):
    cfg, users, prompt_len = CFGS[name], 2, 19
    params, heads = _heads(cfg, users)
    kw = {}
    if subset is not None:
        spec = SubsetSpec.resolve(subset, params)
        heads, kw = spec.extract(heads), {"params": params, "spec": spec}
    prompt = jax.random.randint(jax.random.PRNGKey(2), (users, prompt_len),
                                0, cfg.vocab)
    args = (cfg, heads, prompt, prompt_len + GEN, prompt_len)
    want = np.asarray(_fresh_personalized(*args, **kw,
                                          make_prefill=stepped_prefill))
    for _ in range(2):                     # the build, then the kept ones
        np.testing.assert_array_equal(
            np.asarray(serve._decode_personalized(*args, **kw)), want)
