"""Driver of the ``hybrid_personalize`` traffic kind: served
personalization of the Zamba2 hybrid's per-invocation adapters through
``repro.serving.PersonalizationServer``.

The traffic and the window's call are the ``personalize`` driver's
(``bench/drivers/personalize.py``): a closed loop, one cohort and one
window apply per iteration.  The configuration's ``personal_subset`` names
the adapters, so the prox solve runs over them against the frozen
backbone, and rows, snapshots and heads hold only them.  ``correct``
compares the same four readings over the adapter leaves, against the plain
reference ``bench/reference/zamba2.py``.
"""
from __future__ import annotations

import dataclasses
import math

import jax

from bench import common, served
from bench.reference import personalize as ref_personalize
from bench.reference import ssm_lm, zamba2
from bench.weights_zamba2 import make_weights


def program_dims(cfg) -> zamba2.Dims:
    """The reference's sizes of a program config."""
    s = cfg.ssm
    return zamba2.Dims(
        d_model=cfg.d_model, n_layer=cfg.n_layers, vocab=cfg.vocab,
        d_state=s.state_dim, d_conv=s.conv_width, expand=s.expand,
        headdim=s.head_dim, ngroups=s.n_groups, eps=cfg.norm_eps,
        attn_heads=cfg.n_heads, attn_head_dim=cfg.resolved_head_dim,
        d_ff=cfg.d_ff,
        hybrid_ids=tuple(i for i in cfg.hybrid_layer_ids if i < cfg.n_layers),
        n_blocks=cfg.num_mem_blocks, rank=cfg.adapter_rank,
        rope_theta=cfg.rope_theta)


def program_config(conf: dict):
    """The program's ArchConfig at the file's depth, checked size by size
    against the file, so the file states what is run."""
    from repro.configs import get_config, reduce_for_smoke
    cfg = get_config(conf["program"]["arch"])
    if conf["program"].get("smoke"):
        cfg = reduce_for_smoke(cfg)       # CPU rehearsal sizes only
    cfg = dataclasses.replace(cfg, n_layers=conf["num_hidden_layers"])
    d = zamba2.Dims.from_config(conf)
    want = d._replace(hybrid_ids=tuple(i for i in d.hybrid_ids
                                       if i < d.n_layer))
    have = program_dims(cfg)
    if have != want or (cfg.n_kv_heads, cfg.ssm.chunk) != (
            conf["num_key_value_heads"], conf["chunk_size"]):
        raise ValueError(f"program config {cfg.arch_id} has sizes {have}, "
                         f"the configuration file states {want}")
    if not math.isclose(cfg.attn_scale, d.attn_scale):
        raise ValueError(f"program scores scale by {cfg.attn_scale}, the "
                         f"reference by {d.attn_scale}")
    if cfg.dtype != conf["compute_dtype"]:
        raise ValueError(f"program computes in {cfg.dtype}, the "
                         f"configuration file states {conf['compute_dtype']}")
    return cfg


def host_weights(conf: dict, seed: int):
    """The seed's weights on the host: the server copies what it is handed
    onto the device, and two device copies of a model this size would
    not fit one chip beside each other."""
    w = make_weights(conf, common.jax_key(seed))
    out = jax.device_get(w)
    del w
    return out


def subset(tree, prefixes):
    """The leaves of a dict tree that the ``a/b`` path prefixes name, as a
    tree of their own."""
    out = {}
    for prefix in prefixes:
        *inner, last = prefix.split("/")
        src, dst = tree, out
        for k in inner:
            src, dst = src[k], dst.setdefault(k, {})
        dst[last] = src[last]
    return out


class Driver(served.Driver):
    def program(self):
        return program_config(self.conf)

    def weights(self):
        return host_weights(self.conf, self.seed)

    def counts(self) -> dict:
        from bench.counts import zamba2 as counts
        return {"flops_per_request": counts.flops_per_request(self.conf,
                                                              self.mix)}

    def reference(self, rnd=ssm_lm.exact):
        """The plain reference serving the replayed windows over the
        adapters; yields, per window, the banked deltas' and the adapters'
        change's leaf norms and sketches."""
        conf, d = self.conf, zamba2.Dims.from_config(self.conf)
        p = conf["personalization"]
        full = make_weights(conf, common.jax_key(self.seed))
        sub0 = subset(full, conf["serving"]["personal_subset"])
        loss = zamba2.subset_loss(d, rnd)
        ref = ref_personalize.ServedReference(
            sub0, lambda w, b: ref_personalize.prox_delta(
                loss, w, b, p["lam"], p["inner_eta"], p["inner_steps"]),
            beta=p["beta"], head_cache=conf["serving"]["head_cache"],
            codec=conf["serving"]["delta_dtype"])
        i = self.check_first
        for users in self.check_windows:
            reqs = []
            for u in users:
                b = self.requests.batch(u, i)
                i += 1
                reqs.append((u, (full, b["tokens"][0], b["labels"][0])))
            banked = ref.window(reqs)
            deltas = [(common.leaf_norms(x), common.sketch(x))
                      for x in banked]
            del banked
            yield deltas, (common.leaf_norms(ref.w, minus=sub0),
                           common.sketch(ref.w, minus=sub0))
