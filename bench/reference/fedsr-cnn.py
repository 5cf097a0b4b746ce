"""Plain float32 forward pass of the fedsr-cnn classifier.

Written from the layout in ``configs/fedsr-cnn.json`` and independent of
the program's model code: 3x3 convolutions with SAME padding, each
followed by a bias and a ReLU, a 2x2 max-pool after the first ``pools``
of them, then the features flattened in (height, width, channel) order
into FC -> ReLU -> FC logits.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _pool(x):
    n, h, w, c = x.shape
    if h % 2 or w % 2:
        raise ValueError(f"2x2 pooling of an odd map {h}x{w}")
    return x.reshape(n, h // 2, 2, w // 2, 2, c).max(axis=(2, 4))


def conv(x, w, **kw):
    return jax.lax.conv_general_dilated(
        x, w, window_strides=(1, 1), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), **kw)


# the products of the forward pass (a control replaces them)
PRODUCTS = {"dot": jnp.matmul, "conv": conv}


def apply(params: dict, images: jax.Array, cfg: dict, dot=jnp.matmul,
          conv=conv) -> jax.Array:
    """(N, H, W, C) images -> (N, classes) logits. ``dot`` and ``conv`` are
    the products (a control passes lower-precision ones)."""
    x = images
    for i in range(len(cfg["cnn_channels"])):
        x = conv(x, params[f"conv{i}_w"])
        x = jnp.maximum(x + params[f"conv{i}_b"], 0)
        if i < cfg["pools"]:
            x = _pool(x)
    x = x.reshape(x.shape[0], -1)
    x = jnp.maximum(dot(x, params["fc0_w"]) + params["fc0_b"], 0)
    return dot(x, params["fc1_w"]) + params["fc1_b"]
