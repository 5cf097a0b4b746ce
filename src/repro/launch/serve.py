"""Batched serving driver: prefill a prompt batch, then decode with cache.

Runs on the host mesh (the production mesh path is exercised by dryrun.py);
used by examples/serve_batch.py and the serving integration test.

``--fleet K`` serves a *personalized fleet* instead of one model: K
per-client model variants stack into a ``(K, ...)`` params arena and each
request routes to its client's row by int32 lane id — prefill and decode
then run across ALL the batch's models as one dispatch per step
(``repro.serve.fleet``), with host-resident cohort staging
(``--fleet-host``) for fleets larger than device memory.
"""
from __future__ import annotations

import argparse
import time
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.configs.registry import get_smoke_config
from repro.models.transformer import decode_step, init_cache, init_model
from repro.utils.compile_cache import use_compile_cache


@partial(jax.jit, static_argnames=("cfg",))
def _prefill(params, prompts, cache, cfg: ModelConfig):
    """ONE compiled prefill dispatch: a ``lax.scan`` over prompt positions
    fills the whole cache in a single call (the per-token python loop this
    replaces cost O(S0) dispatches). Returns (last logits (B, V), cache)."""
    def body(c, x):
        tok, i = x                                   # (B,), ()
        logits, c = decode_step(params, tok[:, None], c, i, cfg)
        return c, logits[:, 0]

    s0 = prompts.shape[1]
    cache, logits = jax.lax.scan(body, cache, (prompts.T, jnp.arange(s0)))
    return logits[-1], cache


def prefill_and_decode(
    cfg: ModelConfig,
    params,
    prompts: jax.Array,           # (B, S0) int32
    *,
    max_len: int,
    new_tokens: int,
    temperature: float = 0.0,
    seed: int = 0,
) -> Tuple[jax.Array, dict]:
    """Greedy/temperature batched generation. Returns (tokens (B, S0+N), stats).

    Timers are fenced (``jax.block_until_ready`` before every clock read —
    async dispatch would otherwise report enqueue time, not compute time),
    prefill is one compiled dispatch, and decoded tokens collect into a
    list joined ONCE, so decode cost is linear in ``new_tokens`` instead
    of the O(n^2) per-token host concatenate."""
    b, s0 = prompts.shape
    cache = init_cache(cfg, b, max_len, dtype=jnp.float32)
    step = jax.jit(lambda p, t, c, pos: decode_step(p, t, c, pos, cfg))

    rng = jax.random.PRNGKey(seed)
    jax.block_until_ready(prompts)
    t0 = time.perf_counter()
    last_logits, cache = _prefill(params, prompts, cache, cfg)
    jax.block_until_ready(last_logits)
    t1 = time.perf_counter()

    new = []
    for i in range(new_tokens):
        if temperature > 0:
            rng, sub = jax.random.split(rng)
            nxt = jax.random.categorical(sub, last_logits / temperature)
        else:
            nxt = jnp.argmax(last_logits, axis=-1)
        nxt = nxt.astype(jnp.int32)
        new.append(nxt)
        logits, cache = step(params, nxt[:, None], cache,
                             jnp.asarray(s0 + i))
        last_logits = logits[:, -1]
    toks = jnp.concatenate([prompts] + [n[:, None] for n in new], axis=1)
    jax.block_until_ready(toks)
    t2 = time.perf_counter()
    decode_s = t2 - t1
    return toks, {
        "prefill_s": t1 - t0,
        "decode_s": decode_s,
        "decode_tok_s": b * new_tokens / max(decode_s, 1e-9),
    }


def _serve_fleet(args) -> None:
    """Fleet mode: K model variants, batch requests routed by lane id,
    one dispatch per step across all of them (repro.serve.fleet)."""
    from repro.serve.fleet import FleetParams, fleet_prefill_and_decode

    cfg = get_smoke_config(args.arch)
    rng = np.random.default_rng(0)
    base = init_model(jax.random.PRNGKey(0), cfg)
    # per-client variants: the global model plus a per-lane perturbation
    # (stand-in for a personalized fine-tune of each client)
    keys = jax.random.split(jax.random.PRNGKey(1), args.fleet)
    stacked = jax.vmap(lambda k: jax.tree.map(
        lambda x: x + 0.01 * jax.random.normal(k, x.shape, x.dtype),
        base))(keys)
    fleet = FleetParams(stacked, device=not args.fleet_host)
    lanes = rng.integers(0, args.fleet, size=args.batch)
    prompts = jnp.asarray(
        rng.integers(0, cfg.vocab_size, size=(args.batch, args.prompt_len)),
        jnp.int32)
    toks, stats = fleet_prefill_and_decode(
        cfg, fleet, lanes, prompts,
        max_len=args.prompt_len + args.new_tokens,
        new_tokens=args.new_tokens)
    fleet.close()
    print(f"fleet={args.fleet} generated shape: {toks.shape}")
    print({k: round(v, 3) if isinstance(v, float) else v
           for k, v in stats.items()})


def main() -> None:
    ap = argparse.ArgumentParser(description="FedSR-framework batched serving")
    ap.add_argument("--arch", default="yi-9b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--fleet", type=int, default=0,
                    help=">0: serve a K-model personalized fleet, requests "
                         "routed by lane id (repro.serve.fleet)")
    ap.add_argument("--fleet-host", action="store_true",
                    help="keep the fleet arena host-resident and stage "
                         "only each batch's cohort (fleets larger than "
                         "device memory)")
    args = ap.parse_args()
    use_compile_cache()

    if args.fleet > 0:
        _serve_fleet(args)
        return

    cfg = get_smoke_config(args.arch)
    params = init_model(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    prompts = jnp.asarray(
        rng.integers(0, cfg.vocab_size, size=(args.batch, args.prompt_len)),
        jnp.int32,
    )
    toks, stats = prefill_and_decode(
        cfg, params, prompts,
        max_len=args.prompt_len + args.new_tokens,
        new_tokens=args.new_tokens,
    )
    print(f"generated shape: {toks.shape}")
    print({k: round(v, 3) for k, v in stats.items()})


if __name__ == "__main__":
    main()
