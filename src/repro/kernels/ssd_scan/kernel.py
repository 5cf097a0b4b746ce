"""Mamba2 SSD chunked scan — Pallas TPU kernel.

TPU adaptation of the SSD algorithm (arXiv:2405.21060): the original CUDA
kernel leans on warp-level scans; here the *chunk* axis is a sequential
Pallas grid dimension with the inter-chunk state (N, P) carried in VMEM
scratch, and all intra-chunk work is (Q x Q) / (Q x N) / (N x P) matmuls —
MXU-shaped with Q = chunk = 128 and f32 accumulation.

Layout: per-head, pre-expanded (the ops wrapper repeats B/C over head
groups): x (B, H, NC, Q, P), dt (B, H, NC, Q, 1), b/c (B, H, NC, Q, N),
a (H, 1); out y (B, H, NC, Q, P).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _ssd_kernel(a_ref, x_ref, dt_ref, b_ref, c_ref, y_ref, state_ref, *,
                chunk: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    a = a_ref[0, 0]                                    # () scalar decay rate
    x = x_ref[0, 0, 0].astype(jnp.float32)             # (Q, P)
    dt = dt_ref[0, 0, 0, :, 0].astype(jnp.float32)     # (Q,)
    bm = b_ref[0, 0, 0].astype(jnp.float32)            # (Q, N)
    cm = c_ref[0, 0, 0].astype(jnp.float32)            # (Q, N)

    da = dt * a                                        # (Q,) negative
    cs = jnp.cumsum(da)                                # (Q,)

    # intra-chunk quadratic (dual) form
    seg = cs[:, None] - cs[None, :]                    # (Q, Q)
    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    seg = jnp.where(rows >= cols, seg, NEG_INF)
    decay = jnp.exp(seg)
    cb = jax.lax.dot_general(
        cm, bm, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
    )                                                  # (Q, Q)
    att = cb * decay * dt[None, :]
    y = jax.lax.dot_general(
        att, x, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
    )                                                  # (Q, P)

    # inter-chunk contribution from the carried state (state BEFORE chunk)
    s_prev = state_ref[...]                            # (N, P)
    c_scaled = cm * jnp.exp(cs)[:, None]               # (Q, N)
    y = y + jax.lax.dot_general(
        c_scaled, s_prev, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    y_ref[0, 0, 0] = y.astype(y_ref.dtype)

    # state update: S_new = exp(sum da) S_prev + sum_j exp(cs_Q - cs_j) dt_j B_j x_j^T
    total = cs[-1]
    w = jnp.exp(total - cs) * dt                       # (Q,)
    b_scaled = bm * w[:, None]                         # (Q, N)
    outer = jax.lax.dot_general(
        b_scaled, x, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32,
    )                                                  # (N, P)
    state_ref[...] = jnp.exp(total) * s_prev + outer


def ssd_scan_bhcqp(
    x: jax.Array,          # (B, H, NC, Q, P)
    dt: jax.Array,         # (B, H, NC, Q, 1)
    a: jax.Array,          # (H, 1)
    b_mat: jax.Array,      # (B, H, NC, Q, N)
    c_mat: jax.Array,      # (B, H, NC, Q, N)
    *,
    interpret: bool = False,
) -> jax.Array:
    bsz, h, nc, q, p = x.shape
    n = b_mat.shape[-1]
    grid = (bsz, h, nc)
    kernel = functools.partial(_ssd_kernel, chunk=q)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda bb, hh, ic: (hh, 0)),
            pl.BlockSpec((1, 1, 1, q, p), lambda bb, hh, ic: (bb, hh, ic, 0, 0)),
            pl.BlockSpec((1, 1, 1, q, 1), lambda bb, hh, ic: (bb, hh, ic, 0, 0)),
            pl.BlockSpec((1, 1, 1, q, n), lambda bb, hh, ic: (bb, hh, ic, 0, 0)),
            pl.BlockSpec((1, 1, 1, q, n), lambda bb, hh, ic: (bb, hh, ic, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, q, p), lambda bb, hh, ic: (bb, hh, ic, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(a, x, dt, b_mat, c_mat)
