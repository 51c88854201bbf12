"""Plain reference of served personalization: the Moreau prox solve, the
int8 error-feedback banking codec, and the aggregation-window apply.

Semantics, as the configuration file states them:

* prox solve (PersA-FL option C, pFedMe): theta_0 = w and K steps of
  theta <- theta - eta (grad f(theta) + lam (theta - w)); the delta is
  w - theta_K;
* int8 banking with error feedback: adj = delta + the user's carried
  residual (credited to the user's first row of a cohort only);
  per row and leaf scale = max|adj| / 127, q = clip(round(adj / scale));
  the served head is w - scale q; the new residual is the error
  adj - scale q, itself stored as int8 codes with its own scale; residuals
  live in an LRU of ``head_cache`` users;
* window apply: w <- w - sum over admitted rows of (beta / M) scale q.

One request at a time, in float32 (``rnd`` as in ``ssm_lm``).
"""
from __future__ import annotations

import collections
from typing import Callable, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32


def prox_delta(loss: Callable, w, batch, lam: float, eta: float,
               steps: int):
    """w - theta_K for one request (``loss(params, batch)``)."""
    grad = jax.grad(loss)

    def step(theta, _):
        g = grad(theta, batch)
        return jax.tree.map(lambda t, gg, ww: t - eta * (gg + lam * (t - ww)),
                            theta, g, w), None

    theta, _ = jax.lax.scan(step, w, None, length=steps)
    return jax.tree.map(lambda a, b: a - b, w, theta)


def quantize(x):
    """Symmetric absmax int8 codes of one leaf of one row, and its scale."""
    scale = jnp.max(jnp.abs(x)) / 127.0
    safe = jnp.where(scale > 0, scale, 1.0)
    return jnp.clip(jnp.round(x / safe), -127, 127), scale


def ef_bank(delta, residual):
    """-> (banked delta scale*q, new residual) for one row, per leaf."""
    def one(d, r):
        adj = d + r
        q, s = quantize(adj)
        banked = q * s
        rq, rs = quantize(adj - banked)
        return banked, rq * rs
    pairs = jax.tree.map(one, delta, residual)
    first = jax.tree.map(lambda p: p[0], pairs,
                         is_leaf=lambda x: isinstance(x, tuple))
    second = jax.tree.map(lambda p: p[1], pairs,
                          is_leaf=lambda x: isinstance(x, tuple))
    return first, second


def served_head(w, delta, codec: str):
    """The head served to a user with no carried residual: w - banked."""
    if codec == "int8":
        delta = ef_bank(delta, jax.tree.map(jnp.zeros_like, delta))[0]
    return jax.tree.map(lambda a, b: a - b, w, delta)


class ServedReference:
    """Replays windows of requests from the initial weights.

    ``delta_fn(w, batch) -> delta`` is the prox solve bound to the model's
    loss; ``window(requests)`` serves one cohort: every request is solved
    against the same snapshot, banked, and the window's apply closes it.
    Returns the banked delta of each request (what the served head
    subtracts from the snapshot) and keeps the post-window weights in
    ``self.w``.
    """

    def __init__(self, w0, delta_fn: Callable, *, beta: float,
                 head_cache: int, codec: str):
        if codec not in ("int8", "fp32"):
            raise ValueError(f"unknown banking codec {codec!r}")
        self.w = w0
        self.delta_fn = jax.jit(delta_fn)
        self.beta = beta
        self.head_cache = head_cache
        self.codec = codec
        self.residuals: "collections.OrderedDict" = collections.OrderedDict()
        self._ef = jax.jit(ef_bank)

    def window(self, requests: Sequence[Tuple[object, Dict]]) -> List:
        snapshot = self.w
        zero = jax.tree.map(jnp.zeros_like, snapshot)
        credited, banked, new_res = set(), [], []
        for user, batch in requests:
            delta = self.delta_fn(snapshot, batch)
            if self.codec == "fp32":
                banked.append(delta)
                new_res.append(None)
                continue
            res = zero
            if user not in credited and user in self.residuals:
                res = self.residuals[user]
            credited.add(user)
            b, r = self._ef(delta, res)
            banked.append(b)
            new_res.append(r)
        if self.codec == "int8":
            # residuals are read before any of this cohort's are stored
            for (user, _), r in zip(requests, new_res):
                self.residuals[user] = r
                self.residuals.move_to_end(user)
                while len(self.residuals) > self.head_cache:
                    self.residuals.popitem(last=False)
        m = len(requests)
        total = jax.tree.map(lambda *xs: sum(xs), *banked)
        self.w = jax.tree.map(lambda w, t: w - (self.beta / m) * t,
                              snapshot, total)
        return banked
