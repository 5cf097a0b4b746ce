"""Jit'd public wrapper in the model's (B, S, H, hd) layout."""
from __future__ import annotations

import functools

import jax

from repro.kernels import default_interpret
from repro.kernels.flash_attention.kernel import flash_attention_bhsd


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_k", "interpret"),
)
def flash_attention(
    q: jax.Array,          # (B, S, H, hd)
    k: jax.Array,          # (B, T, KV, hd)
    v: jax.Array,          # (B, T, KV, hd)
    *,
    causal: bool = True,
    window: int = 0,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    if interpret is None:
        interpret = default_interpret()
    out = flash_attention_bhsd(
        q.transpose(0, 2, 1, 3),
        k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3),
        causal=causal, window=window,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )
    return out.transpose(0, 2, 1, 3)
