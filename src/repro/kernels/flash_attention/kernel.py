"""Blockwise causal GQA flash attention — Pallas TPU kernel.

TPU adaptation notes (vs the CUDA flash-attention the literature assumes):
* tiles are MXU-shaped — (Bq, hd) x (hd, Bk) matmuls with Bq = Bk = 128
  multiples, f32 accumulation in VMEM scratch;
* the kv dimension is a *sequential* grid axis with carried scratch
  (online-softmax m/l/acc), not a warp-level loop;
* causal + sliding-window block skipping happens at the grid level with
  pl.when, so skipped tiles cost no MXU cycles.

Layout contract: q (B, H, Sq, hd); k, v (B, KV, T, hd); out (B, H, Sq, hd).
The ops.py wrapper transposes from the model's (B, S, H, hd) layout.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref,
    acc_ref, m_ref, l_ref,
    *, block_q: int, block_k: int, seq_k: int, causal: bool, window: int,
    scale: float,
):
    iq, ik = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    q_start = iq * block_q
    k_start = ik * block_k

    # first / last kv block this q block actually needs
    if causal:
        ik_last = jax.lax.div(q_start + block_q - 1, block_k)
    else:
        ik_last = nk - 1
    if window > 0:
        ik_first = jax.lax.max(0, jax.lax.div(q_start - window + 1, block_k))
    else:
        ik_first = 0

    @pl.when(ik == ik_first)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(jnp.logical_and(ik >= ik_first, ik <= ik_last))
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)          # (Bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)          # (Bk, hd)
        v = v_ref[0, 0].astype(jnp.float32)          # (Bk, hd)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                     # (Bq, Bk)

        rows = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        cols = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = cols < seq_k
        if causal:
            mask &= rows >= cols
        if window > 0:
            mask &= (rows - cols) < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev, l_prev = m_ref[...], l_ref[...]       # (Bq, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_new
        l_ref[...] = l_new

    @pl.when(ik == ik_last)
    def _finalize():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(
            o_ref.dtype
        )


def flash_attention_bhsd(
    q: jax.Array,          # (B, H, Sq, hd)
    k: jax.Array,          # (B, KV, T, hd)
    v: jax.Array,          # (B, KV, T, hd)
    *,
    causal: bool = True,
    window: int = 0,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
) -> jax.Array:
    b, h, sq, hd = q.shape
    kv, t = k.shape[1], k.shape[2]
    group = h // kv
    block_q = min(block_q, sq)
    block_k = min(block_k, t)
    assert sq % block_q == 0 and t % block_k == 0, (sq, t, block_q, block_k)
    grid = (b, h, sq // block_q, t // block_k)
    scale = 1.0 / (hd ** 0.5)

    kernel = functools.partial(
        _flash_kernel, block_q=block_q, block_k=block_k, seq_k=t,
        causal=causal, window=window, scale=scale,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd), lambda bb, hh, iq, ik: (bb, hh, iq, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda bb, hh, iq, ik, g=group: (bb, hh // g, ik, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda bb, hh, iq, ik, g=group: (bb, hh // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd),
                               lambda bb, hh, iq, ik: (bb, hh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, hd), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
