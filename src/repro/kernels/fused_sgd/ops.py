"""Jit'd public wrapper: arbitrary-shape params -> padded (rows, 128) tiles."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import default_interpret
from repro.kernels.fused_sgd.kernel import BLOCK, LANES, fused_sgd_2d


@functools.partial(
    jax.jit, static_argnames=("momentum", "nesterov", "block", "interpret")
)
def fused_sgd_update(
    p: jax.Array,
    g: jax.Array,
    m: jax.Array,
    *,
    lr,
    momentum: float,
    nesterov: bool = False,
    block: int = BLOCK,
    interpret: bool | None = None,
):
    """Returns (new_p, new_m) for one parameter tensor of any shape.
    ``block`` is the tile size in elements, rounded up to whole 128-lane
    rows."""
    if interpret is None:
        interpret = default_interpret()
    shape = p.shape
    n = p.size
    block_rows = -(-block // LANES)
    tile = block_rows * LANES
    pad = (-n) % tile

    def tiles(x):
        return jnp.pad(x.reshape(-1), (0, pad)).reshape(-1, LANES)

    p_new, m_new = fused_sgd_2d(
        tiles(p), tiles(g), tiles(m), lr,
        momentum=momentum, nesterov=nesterov, block_rows=block_rows,
        interpret=interpret,
    )

    def untile(x):
        return x.reshape(-1)[:n].reshape(shape)

    return untile(p_new), untile(m_new)
