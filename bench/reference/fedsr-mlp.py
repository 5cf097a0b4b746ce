"""Plain float32 forward pass of the fedsr-mlp classifier.

Written from ``configs/fedsr-mlp.json`` and independent of the program's
model code: the image flattened, then dense layers w0/b0, w1/b1, ... with
a ReLU after every layer but the last.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


# the products of the forward pass (a control replaces them)
PRODUCTS = {"dot": jnp.matmul}


def apply(params: dict, images: jax.Array, cfg: dict,
          dot=jnp.matmul) -> jax.Array:
    """(N, H, W, C) images -> (N, classes) logits. ``dot`` is the product (a
    control passes a lower-precision one)."""
    x = images.reshape(images.shape[0], -1)
    layers = len(cfg["mlp_hidden"]) + 1
    for i in range(layers):
        x = dot(x, params[f"w{i}"]) + params[f"b{i}"]
        if i < layers - 1:
            x = jnp.maximum(x, 0)
    return x
