"""The ``personalize`` driver (``bench/drivers/personalize.py``) with its
program, weights and server as methods a new traffic kind overrides, and
``correct`` read over the personal subset when the server has one.

Set-up is the personalize driver's: the replayed windows, captured as
they are served, then the warm-up windows and the user draw.  With a
``personal_subset`` the served heads hold only the personal leaves, so the
served deltas (snapshot - head) and the weights' change after each window
are taken over those leaves; the frozen backbone never changes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import common
from bench.drivers import personalize


class Driver(personalize.Driver):

    def program(self):
        """The program's ArchConfig for the configuration file."""
        return common.program_config(self.conf)

    def weights(self):
        """The seed's weights, as the server is handed them."""
        return common.make_weights(self.conf, self.seed)

    def make_server(self, w0):
        return common.make_server(self.conf, self.cfg, w0,
                                  max_pending=self.C + 1)

    def personal(self, tree):
        """The leaves ``correct`` compares: the server's personal subset,
        or every leaf."""
        spec = self.server.personal_subset
        return tree if spec is None else spec.extract(tree)

    def setup(self):
        self.cfg = self.program()
        self.mode = self.conf["personalization"]["mode"]
        self.server = self.make_server(self.weights())
        # a copy: the window's apply donates the server's buffers
        w0 = jax.tree.map(jnp.copy, self.personal(self.server.params))
        self.check_windows = personalize.check_users(
            self.C, self.conf["serving"]["windows"] + 1)
        self.captured = []
        first = self._next
        for users in self.check_windows:
            snap = self.personal(self.server.params)
            _, heads, _ = self._serve(users)
            deltas = [(common.leaf_norms(snap, minus=h),
                       common.sketch(snap, minus=h)) for h in heads]
            del heads, snap
            self._advance()
            now = self.personal(self.server.params)
            self.captured.append((deltas,
                                  (common.leaf_norms(now, minus=w0),
                                   common.sketch(now, minus=w0))))
        self.check_first = first
        del w0
        for users in personalize.warm_users(self.C):
            self._serve(users)
            self._advance()
        jax.block_until_ready(self.server.params)
        pool = self.mix["request_pool"]
        self.users = common.zipf_users(self.seed, pool,
                                       self.mix["population"],
                                       self.mix["user_zipf_s"])
