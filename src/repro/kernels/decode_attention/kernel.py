"""Flash-decoding: single-query GQA attention over a long KV cache.

serve_step's hot kernel for decode_32k / long_500k. The KV cache length is
the sequential grid axis; each step loads one (Bk, hd) KV tile into VMEM and
updates the online-softmax accumulator for all G = H/KV query heads of the
kv head at once — the (G, Bk) score tile keeps the MXU busy even at batch 1.

Layout: q (B, KV, G, hd); k, v (B, KV, T, hd); lengths (B,) valid length per
sequence (current position + 1); out (B, KV, G, hd).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_K = 512
NEG_INF = -1e30


def _decode_kernel(
    len_ref, q_ref, k_ref, v_ref, o_ref,
    acc_ref, m_ref, l_ref,
    *, block_k: int, window: int,
):
    ik = pl.program_id(2)
    nk = pl.num_programs(2)
    length = len_ref[0]                              # valid tokens in cache
    k_start = ik * block_k

    if window > 0:
        lo = jnp.maximum(length - window, 0)
    else:
        lo = 0
    # block range that intersects [lo, length)
    ik_first = jax.lax.div(lo, block_k)
    ik_last = jax.lax.div(jnp.maximum(length - 1, 0), block_k)

    @pl.when(ik == ik_first)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(jnp.logical_and(ik >= ik_first, ik <= ik_last))
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)          # (G, hd)
        k = k_ref[0, 0].astype(jnp.float32)          # (Bk, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        hd = q.shape[-1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        ) / (hd ** 0.5)                               # (G, Bk)
        cols = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        valid = (cols < length) & (cols >= lo)
        s = jnp.where(valid, s, NEG_INF)

        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_new

    @pl.when(ik == ik_last)
    def _finalize():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(
            o_ref.dtype
        )


def decode_attention_bkgd(
    q: jax.Array,          # (B, KV, G, hd)
    k: jax.Array,          # (B, KV, T, hd)
    v: jax.Array,          # (B, KV, T, hd)
    lengths: jax.Array,    # (B,) int32
    *,
    window: int = 0,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
) -> jax.Array:
    b, kv, g, hd = q.shape
    t = k.shape[2]
    block_k = min(block_k, t)
    assert t % block_k == 0, (t, block_k)
    grid = (b, kv, t // block_k)
    kernel = functools.partial(_decode_kernel, block_k=block_k, window=window)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1,), lambda bb, kk, ik: (bb,)),
            pl.BlockSpec((1, 1, g, hd), lambda bb, kk, ik: (bb, kk, 0, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda bb, kk, ik: (bb, kk, ik, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda bb, kk, ik: (bb, kk, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, hd), lambda bb, kk, ik: (bb, kk, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, kv, g, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g, hd), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(lengths, q, k, v)
