"""Large-architecture FedSR training driver (runs on the host mesh; the
production mesh path is exercised by dryrun.py).

Maps FedSR onto the datacenter runtime exactly as DESIGN.md §3 describes:
a stacked client dimension over the mesh "data" axis, per-step ring hop
(collective-permute), cloud aggregation every R steps (all-reduce mean).
Clients see non-IID token streams (different Markov generators), so the
paper's setting — heterogeneous private shards — is preserved.
"""
from __future__ import annotations

import argparse
import math
import time
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, TrainConfig
from repro.configs.registry import get_config, get_smoke_config
from repro.data.synthetic import make_token_stream
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import fl_stack, make_train_step
from repro.models.transformer import init_model, model_specs
from repro.nn.module import param_count
from repro.utils.compile_cache import use_compile_cache
from repro.utils.logging import MetricLogger


class ClientTokenStore:
    """Host-resident non-IID client token streams, staged one step at a
    time — the LM driver's analogue of ``FLConfig.store="host"``
    (``repro.data.store``): the full ``(steps, n_clients, batch, seq+1)``
    tensor is never materialized; only the current step's
    ``(n_clients, ...)`` slice is assembled and shipped to device. Stream
    content and seeding are identical to the old eager builder (one Markov
    generator per client, so shards stay non-IID across clients)."""

    def __init__(self, cfg: ModelConfig, n_clients: int, batch: int,
                 seq: int, steps: int, seed: int = 0):
        self.streams = [
            make_token_stream(
                vocab_size=cfg.vocab_size,
                num_tokens=steps * batch * (seq + 1),
                seed=seed * 1000 + c,
            ).reshape(steps, batch, seq + 1)
            for c in range(n_clients)
        ]

    def step_batch(self, t: int) -> np.ndarray:
        """The ``(n_clients, batch, seq+1)`` token slice of step ``t``."""
        return np.stack([s[t] for s in self.streams])


def train_loop(
    cfg: ModelConfig,
    tcfg: TrainConfig,
    *,
    steps: int,
    batch_per_client: int,
    seq_len: int,
    log: MetricLogger,
    seed: int = 0,
) -> Dict[str, float]:
    mesh = make_host_mesh()
    stack, _ = fl_stack(mesh)
    n_clients = math.prod(stack)
    train_step, cloud_sync = make_train_step(cfg, tcfg, mesh)
    train_step = jax.jit(train_step)
    cloud_sync = jax.jit(cloud_sync)

    rng = jax.random.PRNGKey(seed)
    base = init_model(rng, cfg)
    dtype = jnp.dtype(tcfg.param_dtype)
    params = jax.tree.map(
        lambda x: jnp.broadcast_to(x.astype(dtype), stack + x.shape), base
    )
    state = {
        "params": params,
        "mom": jax.tree.map(jnp.zeros_like, params),
        "step": jnp.zeros((), jnp.int32),
    }
    data = ClientTokenStore(cfg, n_clients, batch_per_client, seq_len,
                            steps, seed)
    n_params = param_count(model_specs(cfg))
    print(f"model: {cfg.name}  params={n_params/1e6:.1f}M  "
          f"clients={n_clients}  ring_mode={tcfg.ring_mode}")

    losses = []
    t0 = time.perf_counter()
    for t in range(steps):
        batch_np = data.step_batch(t).reshape(
            stack + (batch_per_client, seq_len + 1))
        batch = {
            "inputs": jnp.asarray(batch_np[..., :-1]),
            "labels": jnp.asarray(batch_np[..., 1:]),
        }
        state, loss = train_step(state, batch)
        if (t + 1) % tcfg.cloud_sync_every == 0:
            state = cloud_sync(state)          # eq. 11 cloud aggregation
        losses.append(float(loss))
        if (t + 1) % 10 == 0 or t == 0:
            log.log(t + 1, loss=float(loss),
                    tok_s=batch_per_client * n_clients * seq_len
                    * (t + 1) / (time.perf_counter() - t0))
    return {"final_loss": losses[-1], "first_loss": losses[0],
            "params_m": n_params / 1e6,
            "seconds": time.perf_counter() - t0}


def main() -> None:
    ap = argparse.ArgumentParser(description="FedSR large-arch training")
    ap.add_argument("--arch", default="fedsr-lm-100m")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke config of --arch")
    ap.add_argument("--sync-every", type=int, default=5)
    ap.add_argument("--fused-sgd", action="store_true",
                    help="fused Pallas momentum update (see kernels/fused_sgd)")
    ap.add_argument("--log", default=None)
    args = ap.parse_args()
    use_compile_cache()

    if args.arch == "fedsr-lm-100m":
        cfg = lm_100m_config()
    elif args.smoke:
        cfg = get_smoke_config(args.arch)
    else:
        cfg = get_config(args.arch)
    tcfg = TrainConfig(param_dtype="float32", learning_rate=0.3,
                       momentum=0.5, cloud_sync_every=args.sync_every,
                       fused_sgd=args.fused_sgd)
    log = MetricLogger(args.log)
    out = train_loop(cfg, tcfg, steps=args.steps,
                     batch_per_client=args.batch, seq_len=args.seq, log=log)
    print({k: round(v, 4) for k, v in out.items()})
    assert out["final_loss"] < out["first_loss"], "training must reduce loss"


def lm_100m_config() -> ModelConfig:
    """~100M-param dense decoder for the end-to-end driver
    (12 x [4*640^2 + 3*640*2560] + 2*32768*640 = 120M params)."""
    return ModelConfig(
        name="fedsr-lm-100m", family="dense", num_layers=12, d_model=640,
        num_heads=10, num_kv_heads=10, d_ff=2560, vocab_size=32768,
        rope_theta=10_000.0, dtype="float32",
        source="end-to-end driver (deliverable b)",
    )


if __name__ == "__main__":
    main()
