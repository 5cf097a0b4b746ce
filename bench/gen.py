"""Seeded inputs of the benchmark: image data sets and model weights.

Everything is made on the device, in bulk, from ``--seed``, and no shape
depends on the seed, so one compiled program serves every seed.

The image generator is a vectorised copy of the distribution of the
repo's ``data/synthetic.make_image_dataset``: one smooth template per
class (a sum of four 2-D cosine modes per channel, scaled to [0, 1]), and
per sample a cyclic shift of -2..2 pixels on each axis, a brightness scale
from U(0.7, 1.3), Gaussian noise of std ``noise`` and a clip to [0, 1].
Classes are balanced and the order is shuffled.

The weights are LeCun-normal: a kernel is N(0, 1/fan_in), where fan_in
is the product of all its dimensions but the last (3*3*cin for a 3x3
convolution, the rows of a dense layer), and a bias is zero. The
program's own ``init_small_model`` takes fan_in = cin for a convolution,
3x the standard deviation; with it the Table IV CNN training collapses
on some seeds, in the program and the plain reference alike (PERF.md).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np


def key_from_seed(seed: int, stream: int) -> jax.Array:
    """A PRNG key for one named stream of a run. ``seed`` may be any whole
    number (the driver's exceed 32 bits); it is folded through numpy's
    SeedSequence into 32 bits."""
    ss = np.random.SeedSequence([int(seed) % 2**64, stream])
    return jax.random.PRNGKey(int(ss.generate_state(1, np.uint32)[0]))


def _templates(key, num_classes: int, size: int, channels: int):
    """(classes, H, W, C) smooth class templates in [0, 1]."""
    kf, kp, ka = jax.random.split(key, 3)
    shape = (num_classes, channels, 4)
    fx, fy = jax.random.uniform(kf, (2,) + shape, minval=0.5, maxval=3.0)
    px, py = jax.random.uniform(kp, (2,) + shape, minval=0.0,
                                maxval=2 * np.pi)
    amp = jax.random.uniform(ka, shape, minval=0.3, maxval=1.0)
    g = jnp.linspace(0.0, 1.0, size)
    # modes[k, c, m, y, x] = amp cos(2 pi (fx x + px)) cos(2 pi (fy y + py))
    cx = jnp.cos(2 * np.pi * (fx[..., None] * g + px[..., None]))
    cy = jnp.cos(2 * np.pi * (fy[..., None] * g + py[..., None]))
    img = jnp.einsum("kcm,kcmy,kcmx->kyxc", amp, cy, cx)
    lo = img.min(axis=(1, 2, 3), keepdims=True)
    img = img - lo
    hi = img.max(axis=(1, 2, 3), keepdims=True)
    return img / jnp.maximum(hi, 1e-6)


def _samples(key, templates, n_per_class: int, noise: float):
    """(n, H * W * C) float32 images and (n,) int32 labels. Images are
    flat rows (reshaped on the host), so that no array on the device has
    a small minor dimension that the chip's tiling would pad."""
    num_classes, size, _, channels = templates.shape
    n = num_classes * n_per_class
    kperm, kshift, kscale, knoise = jax.random.split(key, 4)
    labels = jax.random.permutation(
        kperm, jnp.repeat(jnp.arange(num_classes, dtype=jnp.int32),
                          n_per_class))
    shift = jax.random.randint(kshift, (n, 2), -2, 3)
    # np.roll by s: out[i] = in[(i - s) mod size], on rows and on columns
    ar = jnp.arange(size)
    rows = (ar[None, :] - shift[:, :1]) % size                  # (n, H)
    cols = (ar[None, :] - shift[:, 1:]) % size                  # (n, W)
    src = (cols[:, :, None] * channels
           + jnp.arange(channels)[None, None, :]).reshape(n, -1)   # (n, W*C)
    flat = templates.reshape(num_classes * size, size * channels)
    imgs = jnp.take_along_axis(
        flat[(labels[:, None] * size + rows)],                  # (n, H, W*C)
        src[:, None, :], axis=2).reshape(n, -1)
    scale = jax.random.uniform(kscale, (n, 1), minval=0.7, maxval=1.3)
    imgs = imgs * scale + noise * jax.random.normal(knoise, imgs.shape)
    return jnp.clip(imgs, 0.0, 1.0).astype(jnp.float32), labels


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5, 6))
def _image_task(key, num_classes, size, channels, train_per_class,
                test_per_class, noise):
    kt, ktr, kte = jax.random.split(key, 3)
    tmpl = _templates(kt, num_classes, size, channels)
    train = (_samples(ktr, tmpl, train_per_class, noise)
             if train_per_class else None)
    return train, _samples(kte, tmpl, test_per_class, noise)


def image_task(seed: int, data: dict):
    """((train_images, train_labels), (test_images, test_labels)), the
    images as (n, H * W * C) device arrays, from a traffic file's ``data``
    block (no train split when its ``train_per_class`` is 0)."""
    return _image_task(key_from_seed(seed, 1), data["num_classes"],
                       data["image_size"], data["channels"],
                       data["train_per_class"], data["test_per_class"],
                       float(data["noise"]))


def host_images(flat, data: dict) -> np.ndarray:
    """Flat device images as a host (n, H, W, C) float32 array."""
    side = data["image_size"]
    return np.asarray(flat).reshape(-1, side, side, data["channels"])


def param_shapes(cfg: dict) -> dict:
    """Leaf name -> shape of the paper's classifier, from a configuration
    file. The names are those of the program's parameter tree."""
    classes = cfg["num_classes"]
    if cfg["family"] == "mlp":
        dims = ([cfg["image_size"] ** 2 * cfg["image_channels"]]
                + list(cfg["mlp_hidden"]) + [classes])
        out = {}
        for i, (a, b) in enumerate(zip(dims[:-1], dims[1:])):
            out[f"w{i}"], out[f"b{i}"] = (a, b), (b,)
        return out
    chans = [cfg["image_channels"]] + list(cfg["cnn_channels"])
    out = {}
    for i, (cin, cout) in enumerate(zip(chans[:-1], chans[1:])):
        out[f"conv{i}_w"], out[f"conv{i}_b"] = (3, 3, cin, cout), (cout,)
    s = cfg["image_size"]
    for _ in range(cfg["pools"]):
        s = (s + 1) // 2
    feat = s * s * chans[-1]
    out["fc0_w"], out["fc0_b"] = (feat, cfg["fc_hidden"]), (cfg["fc_hidden"],)
    out["fc1_w"] = (cfg["fc_hidden"], classes)
    out["fc1_b"] = (classes,)
    return out


def _init(key, shapes: dict) -> dict:
    keys = jax.random.split(key, len(shapes))
    out = {}
    for k, (name, shape) in zip(keys, sorted(shapes.items())):
        if len(shape) == 1:
            out[name] = jnp.zeros(shape, jnp.float32)
        else:
            std = 1.0 / math.sqrt(math.prod(shape[:-1]))
            out[name] = std * jax.random.normal(k, shape, jnp.float32)
    return out


def init_model(seed: int, cfg: dict) -> dict:
    """One model's float32 weights, in one jitted call."""
    shapes = param_shapes(cfg)
    return jax.jit(lambda k: _init(k, shapes))(key_from_seed(seed, 2))
