"""Jit'd wrapper in the model's decode layout: q (B,1,H,hd), cache (B,T,KV,hd)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import default_interpret
from repro.kernels.decode_attention.kernel import decode_attention_bkgd


@functools.partial(
    jax.jit, static_argnames=("window", "block_k", "interpret")
)
def decode_attention(
    q: jax.Array,          # (B, 1, H, hd)
    k_cache: jax.Array,    # (B, T, KV, hd)
    v_cache: jax.Array,    # (B, T, KV, hd)
    lengths: jax.Array,    # (B,) int32 — current position + 1
    *,
    window: int = 0,
    block_k: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    if interpret is None:
        interpret = default_interpret()
    b, _, h, hd = q.shape
    kv = k_cache.shape[2]
    g = h // kv
    q_bkgd = q[:, 0].reshape(b, kv, g, hd)
    out = decode_attention_bkgd(
        q_bkgd,
        k_cache.transpose(0, 2, 1, 3),
        v_cache.transpose(0, 2, 1, 3),
        lengths.astype(jnp.int32),
        window=window, block_k=block_k, interpret=interpret,
    )
    return out.reshape(b, 1, h, hd)
