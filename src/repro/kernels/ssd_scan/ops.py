"""Jit'd wrapper in the model layout: x (B,L,H,P), B/C (B,L,G,N)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import default_interpret
from repro.kernels.ssd_scan.kernel import ssd_scan_bhcqp


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(
    x: jax.Array,          # (B, L, H, P)
    dt: jax.Array,         # (B, L, H)
    a: jax.Array,          # (H,)
    b_mat: jax.Array,      # (B, L, G, N)
    c_mat: jax.Array,      # (B, L, G, N)
    *,
    chunk: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    if interpret is None:
        interpret = default_interpret()
    bsz, l, h, p = x.shape
    g, n = b_mat.shape[2], b_mat.shape[3]
    rep = h // g
    pad = (-l) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        b_mat = jnp.pad(b_mat, ((0, 0), (0, pad), (0, 0), (0, 0)))
        c_mat = jnp.pad(c_mat, ((0, 0), (0, pad), (0, 0), (0, 0)))
    lp = l + pad
    nc = lp // chunk

    x_k = x.reshape(bsz, nc, chunk, h, p).transpose(0, 3, 1, 2, 4)
    dt_k = dt.reshape(bsz, nc, chunk, h).transpose(0, 3, 1, 2)[..., None]
    bh = jnp.repeat(b_mat, rep, axis=2)   # expand groups to heads
    ch = jnp.repeat(c_mat, rep, axis=2)
    b_k = bh.reshape(bsz, nc, chunk, h, n).transpose(0, 3, 1, 2, 4)
    c_k = ch.reshape(bsz, nc, chunk, h, n).transpose(0, 3, 1, 2, 4)
    a_k = a.reshape(h, 1).astype(jnp.float32)

    y = ssd_scan_bhcqp(x_k, dt_k, a_k, b_k, c_k, interpret=interpret)
    y = y.transpose(0, 2, 3, 1, 4).reshape(bsz, lp, h, p)
    return y[:, :l]
