"""The control's arithmetic: every product from one bfloat16 pass, as the
chip computes a float32 product at its default precision — each operand
rounded to bfloat16, the partial products summed in float32.

The rounding is spelled out on the bits (round to nearest even, then the
low 16 bits cleared), which no compiler folds away and which is the same
on every backend. A product of two such values is exact in float32, so
the control differs from float32 by the operands' rounding alone. The
backward pass rounds its operands the same way.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def round_bf16(x):
    """``x`` (float32) rounded to the nearest bfloat16, kept as float32."""
    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    bits = bits + jnp.uint32(0x7FFF) + ((bits >> 16) & jnp.uint32(1))
    return jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                        jnp.float32)


def one_pass(op):
    """The bilinear ``op(a, b)`` (a matmul or a convolution) from one
    bfloat16 pass, forward and backward."""

    @jax.custom_vjp
    def f(a, b):
        return op(round_bf16(a), round_bf16(b))

    def fwd(a, b):
        return f(a, b), (round_bf16(a), round_bf16(b))

    def bwd(res, g):
        a, b = res
        g = round_bf16(g)
        ga = jax.vjp(lambda x: op(x, b), a)[1](g)[0]
        gb = jax.vjp(lambda y: op(a, y), b)[1](g)[0]
        return ga, gb

    f.defvjp(fwd, bwd)
    return f
