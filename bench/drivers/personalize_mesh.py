"""Driver of the ``personalize_mesh`` traffic kind: served personalization
on the two-dimensional ``("cohort", "model")`` mesh of every chip of the
host, built as ``launch/serve.py --model-axis M`` builds it.

The traffic, the window's call and ``correct`` are the ``personalize``
driver's (``bench/drivers/personalize.py``).  The server runs the cohort
engine under ``cohort_impl="shard_map"`` (cohort rows split over the
"cohort" axis, the parameters all-gathered into each slice), and stores
the parameters, delta banks, ring snapshots and head rows sharded over
the "model" axis by the program's ``param_shardings``.  The mix sets the
model axis and the banking codec (``delta_dtype``), which replaces the
configuration file's for the server, the reference and the counts.
"""
from __future__ import annotations

import jax

from bench import served


class Driver(served.Driver):
    def __init__(self, spec: dict, seed: int):
        super().__init__(spec, seed)
        self.conf = dict(self.conf, serving=dict(
            self.conf["serving"], delta_dtype=self.mix["delta_dtype"]))

    def make_server(self, w0):
        from repro.core.types import PersAFLConfig
        from repro.models import api
        from repro.serving import PersonalizationServer
        from repro.sharding.ctx import cohort_model_mesh
        from repro.sharding.rules import param_shardings
        cfg, p, s = self.cfg, self.conf["personalization"], \
            self.conf["serving"]
        mesh = cohort_model_mesh(self.mix["model_axis"],
                                 jax.devices()[:self.mix["devices"]])
        pcfg = PersAFLConfig(option=p["mode"], lam=p["lam"],
                             inner_steps=p["inner_steps"],
                             inner_eta=p["inner_eta"], beta=p["beta"])
        return PersonalizationServer(
            w0, lambda w, b: api.loss_fn(cfg, w, b), pcfg,
            modes=(p["mode"],), windows=s["windows"],
            max_pending=self.C + 1, head_cache=s["head_cache"],
            delta_dtype=s["delta_dtype"],
            personal_subset=s["personal_subset"],
            cohort_impl="shard_map", mesh=mesh,
            param_shardings=param_shardings(cfg, w0, mesh))
