"""The Zamba2 hybrid (``models/ssm_lm.py``) against the plain reference
``bench/reference/zamba2.py`` at smoke size on the CPU, on the benchmark's
seeded weights (``bench/weights_zamba2.py``): the forward, prefill with
cached decode, a server flush over the adapter subset, and decode with the
subset heads; the table of invocations over shared blocks; the names its
scopes leave in the lowered program; and the mamba2-130m path, which the
hybrid's code must leave bit for bit as it was.

The program computes in float32 here, so it and the reference differ only
in the order of their sums: the chunked scan against the quadratic dual
form, fused against separate matrix products.  That leaves relative gaps
near 1e-6.  TOL = 1e-4 sits two orders above them and below what a lower
precision leaves: each test that uses it also shows the float8 reference
failing it (bfloat16 rounding alone moves these logits by about 4e-3).
"""
import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.drivers.hybrid_personalize import program_dims
from bench.reference import personalize as ref_personalize
from bench.reference import ssm_lm as ref_ssm
from bench.reference import zamba2 as ref
from bench.weights_zamba2 import zamba2_weights
from repro.configs import get_config, reduce_for_smoke
from repro.core import PersAFLConfig
from repro.core.subset import SubsetSpec
from repro.launch import serve
from repro.models import api, ssm_lm
from repro.models.layers import unembed
from repro.serving import PersonalizationServer

TOL = 1e-4
SMOKE = dataclasses.replace(reduce_for_smoke(get_config("zamba2-7b")),
                            dtype="float32")
# 4 invocations over the 2 blocks (A B A B) after one plain layer
FOUR = dataclasses.replace(SMOKE, n_layers=5, hybrid_layer_ids=(1, 2, 3, 4))
ADAPTERS = ("hybrid/adapter_in", "hybrid/adapter_out")
S = 32                                   # two SSD chunks of 16


def weights(cfg, seed=0):
    return zamba2_weights(jax.random.PRNGKey(seed), program_dims(cfg))


def tokens(n, seed=1, cfg=SMOKE):
    return jax.random.randint(jax.random.PRNGKey(seed), (n, S), 0, cfg.vocab)


def rel_gap(got, want):
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


def program_logits(cfg, w, toks):
    return unembed(w["embed"], ssm_lm.ssm_lm_hidden(cfg, w, toks))


@pytest.mark.parametrize("cfg", [SMOKE, FOUR], ids=["A-B", "A-B-A-B"])
def test_forward_logits_match_reference(cfg):
    w, toks = weights(cfg), tokens(2, cfg=cfg)
    got = jax.jit(lambda w, t: program_logits(cfg, w, t))(w, toks)
    d = program_dims(cfg)
    for rnd, ok in ((ref_ssm.exact, True), (ref_ssm.fp8, False)):
        want = jnp.stack([ref.logits(w, t, d, rnd) for t in toks])
        assert (rel_gap(got, want) <= TOL) == ok, rnd


def test_scale_and_grouped_norm_are_the_published_ones():
    # the smoke config keeps the published score scale relative to its
    # head: (hd / 2) ** -0.5, and two groups of gated-norm channels
    assert SMOKE.attn_scale == pytest.approx(
        program_dims(SMOKE).attn_scale)
    assert SMOKE.ssm.n_groups == 2
    full = get_config("zamba2-7b")
    assert full.attn_scale == pytest.approx(112 ** -0.5)
    assert (full.d_model, full.resolved_head_dim, full.n_heads,
            full.adapter_rank, full.num_mem_blocks) == (3584, 224, 32, 128, 2)


@pytest.mark.parametrize("cfg", [SMOKE, FOUR], ids=["A-B", "A-B-A-B"])
def test_prefill_then_cached_decode_match_reference(cfg):
    """Prefill a prompt through the decode cache, then step the rest of
    the sequence one token at a time: each step's logits against the
    reference's full forward at that position."""
    w, toks = weights(cfg), tokens(2, cfg=cfg)
    P = 12
    cache = api.init_cache(cfg, w, {"tokens": toks}, S, jnp.float32)
    cache = jax.jit(serve.make_prefill(cfg))(w, cache, toks[:, :P])
    step = jax.jit(lambda w, c, t, pos: api.decode_step(cfg, w, c, t, pos))
    got = []
    for pos in range(P - 1, S):
        lg, cache = step(w, cache, toks[:, pos:pos + 1], jnp.int32(pos))
        got.append(lg[:, 0])
    got = jnp.stack(got, axis=1)
    d = program_dims(cfg)
    want = jnp.stack([ref.logits(w, t, d) for t in toks])[:, P - 1:]
    assert rel_gap(got, want) <= TOL


def test_invocations_share_blocks_not_adapters():
    """Four invocations run two blocks (A B A B): the blocks' weights are
    stacked once per block, the linears and adapters once per invocation,
    and the loss depends on every invocation's adapter and both blocks."""
    assert ssm_lm.hybrid_table(FOUR) == (-1, 0, 1, 2, 3)
    w = api.init_params(FOUR, jax.random.PRNGKey(3))
    assert {x.shape[0] for x in jax.tree.leaves(w["shared"])} == {2}
    assert {x.shape[0] for x in jax.tree.leaves(w["hybrid"])} == {4}
    batch = {"tokens": tokens(1, cfg=FOUR), "labels": tokens(1, 2, FOUR)}
    g = jax.grad(lambda p: api.loss_fn(FOUR, p, batch))(w)
    for leaf in ("adapter_in", "adapter_out", "linear"):
        per_inv = jnp.sum(jnp.abs(g["hybrid"][leaf]),
                          axis=tuple(range(1, g["hybrid"][leaf].ndim)))
        assert bool(jnp.all(per_inv > 0)), leaf
    per_block = jnp.sum(jnp.abs(g["shared"]["mlp"]["gate_up"]), axis=(1, 2))
    assert bool(jnp.all(per_block > 0))
    # invocations 0 and 2 run the same block with their own adapters:
    # swapping their adapters changes the model
    swapped = jax.tree.map(lambda x: x, w)
    swapped["hybrid"] = {k: v.at[jnp.array([0, 2])].set(v[jnp.array([2, 0])])
                         for k, v in w["hybrid"].items()}
    toks = batch["tokens"]
    assert rel_gap(program_logits(FOUR, swapped, toks),
                   program_logits(FOUR, w, toks)) > TOL


def _served_deltas(w, batches, pcfg):
    loss = lambda p, b: api.loss_fn(SMOKE, p, b)          # noqa: E731
    srv = PersonalizationServer(w, loss, pcfg, modes=("C",),
                                personal_subset=ADAPTERS,
                                delta_dtype="fp32")
    snap = srv.personal_subset.extract(srv.params)
    tickets = [srv.submit(f"u{i}", b) for i, b in enumerate(batches)]
    srv.flush()
    assert all(t.status == "done" for t in tickets)
    return [jax.tree.map(lambda a, b: a - b, snap, srv.poll(t))
            for t in tickets]


def test_server_flush_over_adapters_matches_reference_prox_delta():
    w = weights(SMOKE)
    pcfg = PersAFLConfig(option="C", lam=30.0, inner_steps=3,
                         inner_eta=0.05, beta=1.0)
    toks = jax.random.randint(jax.random.PRNGKey(5), (2, S + 1), 0,
                              SMOKE.vocab)
    batches = [{"tokens": toks[i:i + 1, :-1], "labels": toks[i:i + 1, 1:]}
               for i in range(2)]
    got = _served_deltas(w, batches, pcfg)
    sub0 = SubsetSpec(ADAPTERS).extract(w)
    d = program_dims(SMOKE)
    for rnd, ok in ((ref_ssm.exact, True), (ref_ssm.fp8, False)):
        fn = jax.jit(lambda s, b: ref_personalize.prox_delta(
            ref.subset_loss(d, rnd), s, b, pcfg.lam, pcfg.inner_eta,
            pcfg.inner_steps))
        for g, b in zip(got, batches):
            want = fn(sub0, (w, b["tokens"][0], b["labels"][0]))
            gaps = jax.tree.leaves(jax.tree.map(rel_gap, g, want))
            assert (max(gaps) <= TOL) == ok, (rnd, gaps)


def test_decode_with_adapter_heads_serves_reference_best_tokens():
    """``_decode_personalized`` with subset heads: every served token's
    reference logit (the user's adapters over the backbone) is the
    reference's best at its position, within TOL of the logits' scale."""
    w = weights(SMOKE)
    spec = SubsetSpec.resolve(ADAPTERS, w)
    sub = spec.extract(w)
    heads = jax.tree.map(lambda x: jnp.stack([x, 1.5 * x]), sub)
    prompt = tokens(2)[:, :8]
    out = serve._decode_personalized(SMOKE, heads, prompt, 16, 8,
                                     params=w, spec=spec)
    assert out.shape == (2, 8)
    d = program_dims(SMOKE)
    for u in range(2):
        head = ref.merge(w, jax.tree.map(lambda x: x[u], heads))
        seq = jnp.concatenate([prompt[u], out[u]])[:-1]
        lg = ref.logits(head, seq, d)[7:]
        gap = jnp.max(lg, axis=-1) - jnp.take_along_axis(
            lg, out[u][:, None], axis=-1)[:, 0]
        assert float(jnp.max(gap)) <= TOL * float(jnp.max(jnp.abs(lg)))


def test_scopes_reach_the_lowered_program():
    w = api.init_params(SMOKE, jax.random.PRNGKey(0))
    text = jax.jit(lambda w, t: ssm_lm.ssm_lm_hidden(SMOKE, w, t)).lower(
        w, tokens(1)).as_text(debug_info=True)
    assert "persafl.zamba2.shared" in text
    assert "persafl.zamba2.mamba" in text
    step = jax.jit(lambda w, c, t: api.decode_step(SMOKE, w, c, t, 0))
    cache = api.init_cache(SMOKE, w, {"tokens": tokens(1)}, S, jnp.float32)
    text = step.lower(w, cache, tokens(1)[:, :1]).as_text(debug_info=True)
    assert "persafl.zamba2.shared" in text
    mamba = reduce_for_smoke(get_config("mamba2-130m"))
    wm = api.init_params(mamba, jax.random.PRNGKey(0))
    text = jax.jit(lambda w, t: ssm_lm.ssm_lm_hidden(mamba, w, t)).lower(
        wm, tokens(1, cfg=mamba)).as_text(debug_info=True)
    assert "persafl.zamba2" not in text


# sha256 prefixes of mamba2-130m's smoke logits, loss gradient and 8
# cached decode steps (PRNG keys 7 and 8), as computed before the hybrid's
# table, grouped norm and scopes went into the shared code
MAMBA2_DIGESTS = {
    "float32": ("52691d4704203c6e", "3a4799039f0fcd90", "30f7f46aa2601789"),
    "bfloat16": ("2a99240f03784272", "113245412a83689c", "9937b520e62aa95e"),
}


def _digest(x):
    return hashlib.sha256(np.asarray(x).tobytes()).hexdigest()[:16]


@pytest.mark.parametrize("dtype", sorted(MAMBA2_DIGESTS))
def test_mamba2_path_is_bit_identical(dtype):
    cfg = dataclasses.replace(reduce_for_smoke(get_config("mamba2-130m")),
                              dtype=dtype)
    w = api.init_params(cfg, jax.random.PRNGKey(7))
    toks = jax.random.randint(jax.random.PRNGKey(8), (2, 32), 0, cfg.vocab)
    fwd = jax.jit(lambda p, t: program_logits(cfg, p, t))(w, toks)
    g = jax.jit(jax.grad(lambda p, b: api.loss_fn(cfg, p, b)))(
        w, {"tokens": toks, "labels": toks})
    cache = api.init_cache(cfg, w, {"tokens": toks}, 8, jnp.float32)
    step = jax.jit(lambda p, c, t, pos: api.decode_step(cfg, p, c, t, pos))
    outs = []
    for t in range(8):
        lg, cache = step(w, cache, toks[:, t:t + 1], jnp.int32(t))
        outs.append(lg)
    got = (_digest(fwd),
           _digest(jnp.concatenate([x.ravel() for x in jax.tree.leaves(g)])),
           _digest(jnp.stack(outs)))
    assert got == MAMBA2_DIGESTS[dtype]
