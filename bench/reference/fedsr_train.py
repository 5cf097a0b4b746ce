"""Plain reference of FedSR training (Algorithm 1 of arXiv:2403.14718).

It replays the rounds a run drew — which devices each edge ring holds, the
order of the hops and each visit's batch indices — and computes them the
plain way: for every ring, the global model goes round the ring, each
device runs its local epoch of momentum SGD on its own shard (momentum
starts at zero at every visit), and the cloud then averages the ring
models weighted by their data (eq. 11). It imports nothing of the
program: the shards come from its own pathological partition of the data
set, the learning rates from its own cosine schedule, and the eq.-11
weights from its own shard sizes.

It computes in float32 at ``highest`` precision; the control
(``control=True``) makes every product from one bfloat16 pass, as the
chip's default precision does (``precision.one_pass``).
"""
from __future__ import annotations

import functools
import math
from typing import Callable, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.precision import one_pass


def pathological_partition(labels: np.ndarray, devices: int, xi: int,
                           seed: int) -> List[np.ndarray]:
    """Sort by label, cut into devices*xi equal shards, deal xi shards to
    each device in the order of a permutation drawn from ``seed``."""
    order = np.argsort(labels, kind="stable")
    shards = np.array_split(order, devices * xi)
    deal = np.random.default_rng(seed).permutation(devices * xi)
    return [np.sort(np.concatenate([shards[s] for s in deal[d * xi:(d + 1) * xi]]))
            for d in range(devices)]


def cosine_lr(t: int, init: float, final: float, total: int) -> float:
    frac = min(max(t / max(total, 1), 0.0), 1.0)
    return float(np.float32(final + 0.5 * (init - final)
                            * (1.0 + math.cos(math.pi * frac))))


def _loss(apply, params, images, labels, cfg):
    logits = apply(params, images, cfg)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=1) - picked)


def make_visit(apply: Callable, cfg: dict, momentum: float):
    """One device visit: its batch plan (steps, batch) of momentum SGD."""

    @jax.jit
    def visit(params, images, labels, plan, lr):
        grad = jax.grad(lambda p, x, y: _loss(apply, p, x, y, cfg))
        mom = jnp.float32(momentum)

        def step(carry, ix):
            p, m = carry
            g = grad(p, images[ix], labels[ix])
            m = jax.tree.map(lambda mi, gi: mom * mi + gi, m, g)
            p = jax.tree.map(lambda pi, mi: pi - lr * mi, p, m)
            return (p, m), None

        zeros = jax.tree.map(jnp.zeros_like, params)
        (params, _), _ = jax.lax.scan(step, (params, zeros), plan)
        return params

    return visit


def accuracy(fwd: Callable, params, images, labels,
             chunk: int = 1000) -> float:
    """Share of ``labels`` that ``fwd(params, images)``'s argmax hits."""
    hits = 0
    for i in range(0, len(labels), chunk):
        x = jnp.asarray(images[i:i + chunk])
        hits += int(jnp.sum(fwd(params, x) == jnp.asarray(labels[i:i + chunk])))
    return hits / len(labels)


def replay(model, cfg: dict, w0: dict, train_images: np.ndarray,
           train_labels: np.ndarray, test_images: np.ndarray,
           test_labels: np.ndarray, fl: dict, seed: int,
           blocks: Sequence[Sequence[Sequence[tuple]]], control: bool = False):
    """Replay ``blocks`` of rounds from ``w0`` with ``model``'s forward
    (``bench/reference/<config>.py``).

    ``blocks[k][r]`` is round r of block k as a list of hops; hop h is a
    list over rings of ``(device, plan)`` (``plan`` a (steps, batch) array
    of indices into the device's shard, or None for no visit). Returns the
    model after each block (host float32 trees) and the test accuracy of
    each."""
    apply = model.apply
    if control:
        apply = functools.partial(apply, **{k: one_pass(op) for k, op
                                            in model.PRODUCTS.items()})
    parts = pathological_partition(train_labels, fl["num_devices"], fl["xi"],
                                   seed)
    shards = [(jnp.asarray(train_images[p]), jnp.asarray(train_labels[p]))
              for p in parts]
    sizes = np.asarray([len(p) for p in parts], np.float64)
    visit = make_visit(apply, cfg, fl["momentum"])
    fwd = jax.jit(lambda p, x: jnp.argmax(apply(p, x, cfg), axis=1))
    w = jax.tree.map(jnp.asarray, w0)
    models, accs = [], []
    t = 0
    with jax.default_matmul_precision("highest"):
        for block in blocks:
            for hops in block:
                lr = jnp.float32(cosine_lr(t, fl["init_lr"], fl["final_lr"],
                                           fl["rounds"]))
                rings = len(hops[0])
                members = [sorted({hop[c][0] for hop in hops})
                           for c in range(rings)]
                data = np.asarray([sizes[m].sum() for m in members])
                weights = data / data.sum()
                new = None
                for c in range(rings):
                    p = w
                    for hop in hops:
                        dev, plan = hop[c]
                        if plan is not None:
                            p = visit(p, *shards[dev], jnp.asarray(plan), lr)
                    a = jnp.float32(weights[c])
                    term = jax.tree.map(lambda x, a=a: a * x, p)
                    new = term if new is None else jax.tree.map(
                        jnp.add, new, term)
                w = new
                t += 1
            models.append(jax.tree.map(
                lambda x: np.asarray(x, np.float32), w))
            accs.append(accuracy(fwd, w, test_images, test_labels))
    return models, accs
