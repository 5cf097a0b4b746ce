"""SGD with (optional) momentum — the paper's client optimizer (momentum 0.5).

optax-like stateless API: ``init(params) -> state``,
``update(grads, state, params, lr) -> (new_params, new_state)``.

The parameter update itself is delegated to the fused Pallas kernel
(`repro.kernels.fused_sgd`) when ``fused=True`` and is pure jnp otherwise;
both paths are bitwise-checked in tests.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

Pytree = Any


@dataclasses.dataclass(frozen=True)
class SGD:
    momentum: float = 0.0
    nesterov: bool = False
    weight_decay: float = 0.0
    fused: bool = False

    def init(self, params: Pytree) -> Pytree:
        if self.momentum == 0.0:
            return ()
        return jax.tree.map(jnp.zeros_like, params)

    def update(self, grads: Pytree, state: Pytree, params: Pytree, lr):
        wd = self.weight_decay
        if wd:
            grads = jax.tree.map(lambda g, p: g + wd * p, grads, params)
        if self.momentum == 0.0:
            new_params = jax.tree.map(lambda p, g: p - lr * g, params, grads)
            return new_params, ()
        if self.fused:
            from repro.kernels.fused_sgd.ops import fused_sgd_update

            def leaf(p, g, m):
                return fused_sgd_update(
                    p, g, m, lr=lr, momentum=self.momentum,
                    nesterov=self.nesterov,
                )
            out = jax.tree.map(leaf, params, grads, state)
            new_params = jax.tree.map(lambda t: t[0], out,
                                      is_leaf=lambda x: isinstance(x, tuple))
            new_state = jax.tree.map(lambda t: t[1], out,
                                     is_leaf=lambda x: isinstance(x, tuple))
            return new_params, new_state

        def step(p, g, m):
            m_new = self.momentum * m + g
            d = g + self.momentum * m_new if self.nesterov else m_new
            return p - lr * d, m_new

        out = jax.tree.map(step, params, grads, state)
        new_params = jax.tree.map(lambda t: t[0], out,
                                  is_leaf=lambda x: isinstance(x, tuple))
        new_state = jax.tree.map(lambda t: t[1], out,
                                 is_leaf=lambda x: isinstance(x, tuple))
        return new_params, new_state
