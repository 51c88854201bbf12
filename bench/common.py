"""Pieces the traffic drivers share: the program's objects built from a
configuration file, weights and requests from the seed, and the leaf
norms and sketches that ``correct`` compares."""
from __future__ import annotations

import math
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.ssm_lm import Dims
from bench.weights import BUILDERS


def jax_key(seed: int):
    """A JAX key from any whole-number seed (numpy takes arbitrary ints)."""
    return jax.random.PRNGKey(
        int(np.random.default_rng([seed, 0]).integers(0, 2 ** 31 - 1)))


def make_weights(conf: dict, seed: int):
    return BUILDERS[conf["init"]](jax_key(seed), Dims.from_config(conf))


def program_config(conf: dict):
    """The program's ArchConfig for this file, checked size by size so the
    file states what is run."""
    from repro.configs import get_config, reduce_for_smoke
    cfg = get_config(conf["program"]["arch"])
    if conf["program"].get("smoke"):
        cfg = reduce_for_smoke(cfg)       # CPU rehearsal sizes only
    d, s = Dims.from_config(conf), cfg.ssm
    have = (cfg.d_model, cfg.n_layers, cfg.vocab, s.state_dim,
            s.conv_width, s.expand, s.head_dim, s.n_groups, cfg.norm_eps)
    if have != tuple(d):
        raise ValueError(f"program config {cfg.arch_id} has sizes {have}, "
                         f"the configuration file states {tuple(d)}")
    if cfg.dtype != conf["compute_dtype"]:
        raise ValueError(f"program computes in {cfg.dtype}, the "
                         f"configuration file states {conf['compute_dtype']}")
    return cfg


def make_server(conf: dict, cfg, params, max_pending: int,
                head_cache: int = None):
    """The PersonalizationServer the configuration file describes
    (``head_cache``, where given, in place of the file's)."""
    from repro.core.types import PersAFLConfig
    from repro.models import api
    from repro.serving import PersonalizationServer
    p, s = conf["personalization"], conf["serving"]
    pcfg = PersAFLConfig(option=p["mode"], lam=p["lam"],
                         inner_steps=p["inner_steps"],
                         inner_eta=p["inner_eta"], beta=p["beta"])
    return PersonalizationServer(
        params, lambda w, b: api.loss_fn(cfg, w, b), pcfg,
        modes=(p["mode"],), windows=s["windows"], max_pending=max_pending,
        head_cache=head_cache or s["head_cache"], delta_dtype=s["delta_dtype"],
        personal_subset=s["personal_subset"])


class Requests:
    """Personalization streams from the seed.

    User ``u`` has a vocabulary of its own (an affine map of token ranks
    onto the published vocabulary, chosen by the seed) and draws ranks
    from a Zipf law, so users' data differ from each other more than one
    user's requests do.  Request ``i`` of the run is a fresh stream.
    """

    def __init__(self, seed: int, vocab: int, length: int, mix: dict):
        self.seed, self.vocab, self.length = seed, vocab, length
        self.mix = mix

    def _user_map(self, user: str):
        g = np.random.default_rng([self.seed, 1, _stable(user)])
        a = int(g.integers(1, self.vocab))
        while math.gcd(a, self.vocab) != 1:
            a += 1
        return a, int(g.integers(0, self.vocab))

    def stream(self, user: str, i: int, length: int = None) -> np.ndarray:
        n = (length or self.length) + 1
        a, b = self._user_map(user)
        g = np.random.default_rng([self.seed, 2, i])
        ranks = np.minimum(g.zipf(self.mix["token_zipf_s"], n) - 1,
                           self.mix["user_vocab"] - 1)
        return ((a * ranks + b) % self.vocab).astype(np.int32)

    def batch(self, user: str, i: int) -> Dict[str, np.ndarray]:
        t = self.stream(user, i)
        return {"tokens": t[None, :-1], "labels": t[None, 1:]}


def _stable(user: str) -> int:
    return int.from_bytes(user.encode()[-8:].rjust(8, b"\0"), "little")


def zipf_users(seed: int, n: int, population: int, s: float) -> List[str]:
    p = 1.0 / np.arange(1, population + 1) ** s
    g = np.random.default_rng([seed, 3])
    return [f"user-{u}" for u in g.choice(population, n, p=p / p.sum())]


@jax.jit
def _norms(tree):
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for x in jax.tree.leaves(tree)]


@jax.jit
def _diff_norms(a, b):
    return _norms(jax.tree.map(lambda x, y: x.astype(jnp.float32)
                               - y.astype(jnp.float32), a, b))


def leaf_norms(tree, minus=None) -> np.ndarray:
    """Per-leaf L2 norms on the host; with ``minus``, of ``tree - minus``."""
    norms = _norms(tree) if minus is None else _diff_norms(tree, minus)
    return np.asarray(jax.device_get(norms), np.float64)


SKETCH_BUCKETS = 4096


@jax.jit
def _sketch(tree):
    """Every element of every leaf times a fixed random sign, folded into
    ``SKETCH_BUCKETS`` sums: a linear map under which inner products, and
    so cosines, keep their value to about 1/sqrt(buckets) relative."""
    out = jnp.zeros((SKETCH_BUCKETS,), jnp.float32)
    for i, x in enumerate(jax.tree.leaves(tree)):
        x = x.astype(jnp.float32).ravel()
        x = jnp.pad(x, (0, -x.size % SKETCH_BUCKETS))
        sign = jax.random.rademacher(
            jax.random.fold_in(jax.random.PRNGKey(0), i), x.shape, jnp.float32)
        out = out + jnp.sum((x * sign).reshape(-1, SKETCH_BUCKETS), axis=0)
    return out


@jax.jit
def _diff_sketch(a, b):
    return _sketch(jax.tree.map(lambda x, y: x.astype(jnp.float32)
                                - y.astype(jnp.float32), a, b))


def sketch(tree, minus=None) -> np.ndarray:
    """The whole tree's sketch on the host; with ``minus``, of
    ``tree - minus``.  Small enough to keep one per served request."""
    s = _sketch(tree) if minus is None else _diff_sketch(tree, minus)
    return np.asarray(jax.device_get(s), np.float64)


def cos_dist(got: np.ndarray, want: np.ndarray) -> float:
    """1 - cosine of two sketches: 0 for the same direction, 2 for the
    opposite one; a zero vector against a nonzero one reads 1."""
    ng, nw = np.linalg.norm(got), np.linalg.norm(want)
    if ng == 0 or nw == 0:
        return 0.0 if ng == nw else 1.0
    return worst([0.5 * np.sum(np.square(got / ng - want / nw))])


def worst(values) -> float:
    """The largest reading; one that is not a finite number (a NaN or an
    overflow on either side) reads as the largest float, so it fails every
    limit and still prints as a number."""
    v = np.asarray(values, np.float64).ravel()
    return float(np.max(np.where(np.isfinite(v), v, np.finfo(np.float64).max),
                        initial=0.0))


def norm_gap(got: np.ndarray, want: np.ndarray) -> float:
    """Worst leaf's gap between the program's and the reference's norm,
    each leaf against the larger of its own reference norm and the median
    leaf's (so a leaf that barely moves is not judged by its own size)."""
    den = np.maximum(want, np.median(want))
    return worst(np.abs(got - want) / np.where(den > 0, den, 1.0))
