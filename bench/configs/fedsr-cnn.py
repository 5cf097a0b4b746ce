"""Operation and byte counts of the fedsr-cnn classifier, from its shapes.

A multiply-add counts two operations. Only convolutions and matrix
products are counted: bias adds, ReLUs, pooling and the loss are left out,
so the counts are lower bounds of the work any implementation does.
"""
from __future__ import annotations


def _layers(cfg: dict):
    """(multiply-adds per image, is_first) of each conv / dense layer."""
    size, cin = cfg["image_size"], cfg["image_channels"]
    out = []
    for i, cout in enumerate(cfg["cnn_channels"]):
        out.append(size * size * cout * 9 * cin)
        cin = cout
        if i < cfg["pools"]:
            size = (size + 1) // 2
    feat = size * size * cin
    out.append(feat * cfg["fc_hidden"])
    out.append(cfg["fc_hidden"] * cfg["num_classes"])
    return out


def param_count(cfg: dict) -> int:
    chans = [cfg["image_channels"]] + list(cfg["cnn_channels"])
    n = sum(9 * a * b + b for a, b in zip(chans[:-1], chans[1:]))
    size = cfg["image_size"]
    for _ in range(cfg["pools"]):
        size = (size + 1) // 2
    feat = size * size * chans[-1]
    n += feat * cfg["fc_hidden"] + cfg["fc_hidden"]
    n += cfg["fc_hidden"] * cfg["num_classes"] + cfg["num_classes"]
    return n


def forward_flops(cfg: dict) -> int:
    """Operations of one image's forward pass."""
    return 2 * sum(_layers(cfg))


def train_flops(cfg: dict) -> int:
    """Operations of one image's forward and backward pass: the forward,
    the weight gradient of every layer, and the input gradient of every
    layer but the first (nothing needs the image's gradient)."""
    macs = _layers(cfg)
    return 2 * (2 * sum(macs) + sum(macs[1:]))


def image_bytes(cfg: dict) -> int:
    """Bytes of one float32 image and its int32 label."""
    return 4 * cfg["image_size"] ** 2 * cfg["image_channels"] + 4
