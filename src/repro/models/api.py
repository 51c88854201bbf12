"""Unified model API — family dispatch used by PersA-FL core, the launch
layer, tests and benchmarks.

    init_params(cfg, key)                 -> params pytree
    loss_fn(cfg, params, batch)           -> scalar loss  (the f_i of Eq. 2)
    init_cache(cfg, params?, batch, ...)  -> decode cache
    decode_step(cfg, params, cache, tokens, pos) -> (logits, cache)
    decode_weights(cfg, params)           -> params as decode computes with them
    make_train_batch_spec / make_decode_spec come from repro.launch.specs
"""
from __future__ import annotations

from typing import Dict

import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import encdec as ed
from repro.models import lm
from repro.models import ssm_lm


def init_params(cfg: ArchConfig, key) -> Dict:
    if cfg.family in ("ssm", "hybrid"):
        return ssm_lm.init_ssm_lm(cfg, key)
    if cfg.is_encdec:
        return ed.init_encdec(cfg, key)
    return lm.init_lm(cfg, key)


def loss_fn(cfg: ArchConfig, params, batch: Dict) -> jnp.ndarray:
    """The client loss f_i(w; D_i) — Eq. (2) of the paper, per-arch."""
    if cfg.family in ("ssm", "hybrid"):
        return ssm_lm.ssm_lm_loss(cfg, params, batch)
    if cfg.is_encdec:
        return ed.encdec_loss(cfg, params, batch)
    return lm.lm_loss(cfg, params, batch)


def prefill_logits(cfg: ArchConfig, params, batch: Dict) -> jnp.ndarray:
    """Inference-prefill: full forward, last-position logits (B, V)."""
    from repro.models.layers import unembed
    if cfg.family in ("ssm", "hybrid"):
        h = ssm_lm.ssm_lm_hidden(cfg, params, batch["tokens"],
                                 window=cfg.sliding_window)
        return unembed(params["embed"], h[:, -1:, :], cfg.final_softcap)[:, 0]
    if cfg.is_encdec:
        enc_h = ed.encode(cfg, params, batch["frames"])
        h = ed.decode_full(cfg, params, batch["tokens"], enc_h)
        return unembed(params["embed"], h[:, -1:, :], cfg.final_softcap)[:, 0]
    h, _ = lm.lm_hidden(cfg, params, batch["tokens"], batch.get("visual"))
    return unembed(params["embed"], h[:, -1:, :], cfg.final_softcap)[:, 0]


def init_cache(cfg: ArchConfig, params, batch: Dict, max_len: int, dtype):
    """Decode cache; enc-dec additionally runs the encoder on batch['frames']."""
    B = batch["tokens"].shape[0]
    if cfg.family in ("ssm", "hybrid"):
        return ssm_lm.init_ssm_cache(cfg, B, max_len, dtype)
    if cfg.is_encdec:
        return ed.init_encdec_cache(cfg, params, batch["frames"], max_len,
                                    dtype)
    return lm.init_lm_cache(cfg, B, max_len, dtype)


def decode_step(cfg: ArchConfig, params, cache, tokens, pos):
    if cfg.family in ("ssm", "hybrid"):
        return ssm_lm.ssm_lm_decode_step(cfg, params, cache, tokens, pos)
    if cfg.is_encdec:
        return ed.encdec_decode_step(cfg, params, cache, tokens, pos)
    return lm.lm_decode_step(cfg, params, cache, tokens, pos)


def decode_weights(cfg: ArchConfig, params):
    """The weights in the dtype the decode step computes with, cast once
    so a call's steps do not re-cast them at every token (``ssm_lm``
    family); same treedef, same served bits.  The other families' decode
    takes ``params`` as they are."""
    if cfg.family in ("ssm", "hybrid"):
        return ssm_lm.decode_weights(cfg, params)
    return params
