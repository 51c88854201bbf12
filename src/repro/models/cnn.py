"""The paper's experimental models (§5, Appendix D): small CNNs with
pooling + dropout + cross-entropy for MNIST / CIFAR-10 classification.

Pure-functional JAX; used by the FLRun event loop, the examples and the
sweeps that reproduce the paper's Figure 2 (``experiments/sweeps/``).
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from repro.configs.paper_models import CNNConfig
from repro.models.layers import cross_entropy, dense_init


def init_cnn(cfg: CNNConfig, key) -> Dict:
    ks = jax.random.split(key, len(cfg.conv_channels) + len(cfg.fc_sizes))
    params: Dict = {"conv": [], "fc": []}
    c_in = cfg.channels
    for i, c_out in enumerate(cfg.conv_channels):
        params["conv"].append({
            "w": dense_init(ks[i], (3, 3, c_in, c_out), scale=0.1),
            "b": jnp.zeros((c_out,), jnp.float32),
        })
        c_in = c_out
    # spatial size after len(conv) stride-2 maxpools
    side = cfg.image_size
    for _ in cfg.conv_channels:
        side = side // 2
    d = side * side * c_in
    for j, width in enumerate(cfg.fc_sizes):
        params["fc"].append({
            "w": dense_init(ks[len(cfg.conv_channels) + j], (d, width)),
            "b": jnp.zeros((width,), jnp.float32),
        })
        d = width
    return params


def _maxpool2(x):
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                 (1, 2, 2, 1), (1, 2, 2, 1), "VALID")


def cnn_logits(cfg: CNNConfig, params, images, *, rng=None,
               train: bool = False):
    """images: (B, H, W, C) f32 -> (B, n_classes)."""
    h = images.astype(jnp.float32)
    for cp in params["conv"]:
        h = jax.lax.conv_general_dilated(
            h, cp["w"], (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        h = jax.nn.relu(h + cp["b"])
        h = _maxpool2(h)
    h = h.reshape(h.shape[0], -1)
    n_fc = len(params["fc"])
    for j, fp in enumerate(params["fc"]):
        h = h @ fp["w"] + fp["b"]
        if j < n_fc - 1:
            h = jax.nn.relu(h)
            if train and rng is not None and cfg.dropout > 0:
                rng, sub = jax.random.split(rng)
                keep = jax.random.bernoulli(sub, 1.0 - cfg.dropout, h.shape)
                h = jnp.where(keep, h / (1.0 - cfg.dropout), 0.0)
    return h


def cnn_loss(cfg: CNNConfig, params, batch: Dict, *, rng=None,
             train: bool = True) -> jnp.ndarray:
    """batch: images (B,H,W,C), labels (B,) int32."""
    logits = cnn_logits(cfg, params, batch["images"], rng=rng, train=train)
    return cross_entropy(logits, batch["labels"])


def cnn_accuracy(cfg: CNNConfig, params, batch: Dict) -> jnp.ndarray:
    logits = cnn_logits(cfg, params, batch["images"], train=False)
    return jnp.mean((jnp.argmax(logits, -1) == batch["labels"])
                    .astype(jnp.float32))
