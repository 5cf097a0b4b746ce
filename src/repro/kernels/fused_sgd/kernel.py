"""Fused momentum-SGD update — Pallas TPU kernel.

The FL inner loop (ring hop) applies `m = mu*m + g; p = p - lr*d` to every
parameter after every batch. Unfused this is 3 HBM-bound passes (read p/g/m,
write m, write p); the fused kernel does one read of (p, g, m) and one write
of (p, m) per VMEM tile — the minimal memory traffic for the update, which
is exactly the dominant roofline term of the FL client step.

Layout: the parameter vector is viewed as a 2-D ``(rows, 128)`` array and
tiled in ``(block_rows, 128)`` blocks, so the block's last two dimensions
meet the TPU's (8, 128) tiling rule both for one vector and when a caller
``jax.vmap``s the update over a leading lane axis (the batched block then
is ``(Squeezed, block_rows, 128)``). ``lr`` is one float32 scalar in SMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
# one tile: 512 rows x 128 lanes = 64k f32 elements = 256 KiB per input,
# a large multiple of the (8, 128) f32 native tile to amortize grid overhead
BLOCK = 65_536


def _fused_sgd_kernel(lr_ref, p_ref, g_ref, m_ref, p_out_ref, m_out_ref, *,
                      momentum: float, nesterov: bool):
    p = p_ref[...]
    g = g_ref[...]
    m = m_ref[...]
    lr = lr_ref[0]
    m_new = momentum * m + g
    d = g + momentum * m_new if nesterov else m_new
    p_out_ref[...] = (p - lr * d).astype(p_out_ref.dtype)
    m_out_ref[...] = m_new.astype(m_out_ref.dtype)


def fused_sgd_2d(
    p: jax.Array,
    g: jax.Array,
    m: jax.Array,
    lr: jax.Array,
    *,
    momentum: float,
    nesterov: bool = False,
    block_rows: int = BLOCK // LANES,
    interpret: bool = False,
):
    """p, g, m: (rows, 128) arrays with rows % block_rows == 0.
    lr: (1,) float32."""
    if not (p.ndim == 2 and p.shape[1] == LANES
            and p.shape == g.shape == m.shape):
        raise ValueError(f"fused_sgd_2d needs equal (rows, {LANES}) arrays, "
                         f"got {p.shape}, {g.shape}, {m.shape}")
    rows = p.shape[0]
    if rows % block_rows:
        raise ValueError(f"rows={rows} must be a multiple of "
                         f"block_rows={block_rows}")
    spec = pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))
    kernel = functools.partial(
        _fused_sgd_kernel, momentum=momentum, nesterov=nesterov
    )
    return pl.pallas_call(
        kernel,
        grid=(rows // block_rows,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),     # lr, every tile
            spec, spec, spec,
        ],
        out_specs=[spec, spec],
        out_shape=[
            jax.ShapeDtypeStruct(p.shape, p.dtype),
            jax.ShapeDtypeStruct(m.shape, m.dtype),
        ],
        interpret=interpret,
    )(jnp.asarray(lr, jnp.float32).reshape(1), p, g, m)
