"""Operation and byte counts of the fedsr-mlp classifier, from its shapes.

A multiply-add counts two operations. Only the matrix products are
counted: bias adds, ReLUs and the loss are left out, so the counts are
lower bounds of the work any implementation does.
"""
from __future__ import annotations


def _dims(cfg: dict):
    return ([cfg["image_size"] ** 2 * cfg["image_channels"]]
            + list(cfg["mlp_hidden"]) + [cfg["num_classes"]])


def param_count(cfg: dict) -> int:
    d = _dims(cfg)
    return sum(a * b + b for a, b in zip(d[:-1], d[1:]))


def forward_flops(cfg: dict) -> int:
    """Operations of one image's forward pass."""
    d = _dims(cfg)
    return 2 * sum(a * b for a, b in zip(d[:-1], d[1:]))


def train_flops(cfg: dict) -> int:
    """Operations of one image's forward and backward pass: the forward,
    the weight gradient of every layer, and the input gradient of every
    layer but the first (nothing needs the image's gradient)."""
    d = _dims(cfg)
    macs = [a * b for a, b in zip(d[:-1], d[1:])]
    return 2 * (2 * sum(macs) + sum(macs[1:]))


def image_bytes(cfg: dict) -> int:
    """Bytes of one float32 image and its int32 label."""
    return 4 * cfg["image_size"] ** 2 * cfg["image_channels"] + 4
