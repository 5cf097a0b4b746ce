"""Bring-up check: FedSR training and personalized fleet serving, end to end
on a TPU, through the entry points a user calls.

    python chip_smoke.py              # one chip: phases 1 and 2
    python chip_smoke.py --chips 4    # only the lane axis over 4 chips,
                                      # against the same run on one chip

Phase 1 trains FedSR with ``run_experiment(engine="fused")`` on the
paper's models at their published widths — ``fedsr-cnn`` on
``cifar10_like`` and ``fedsr-mlp`` on ``mnist_like`` — in the paper's
Table IV deployment: K=100 devices, 25 edge rings of 4, E=1, R=5,
pathological xi=2, participation 0.4. Four rounds run as two eval blocks:
the first pays compilation, the second runs warm as one dispatch. The
reference is the sequential engine on the same config, run under float32
matmul precision; the fused run keeps the chip's default precision, so its
deviation is bounded by the tolerances below, and the fused engine is run
under float32 too for a tighter bound. Phase 2 serves the CNN run's
head-personalized fleet through ``FleetClassifier``, 256 requests in one
batch that reaches every one of the 100 clients, against ``loop_classify``
under float32 precision.

Every phase prints one JSON line; its ``compile_s`` is the seconds of the
backend compiles (cache loads included) that the program's span log
counted (``repro.utils.trace``). The last line of stdout is
``{"ok": true, "device": {"platform", "kind", "count"}}``. Without a TPU,
or when any check fails, the script exits non-zero and prints no result.
Nothing here is a metric: the times are single bring-up readings.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax
import jax.numpy as jnp
import numpy as np
from jax.flatten_util import ravel_pytree

from repro.configs import get_config
from repro.configs.base import FLConfig, PersonalizeConfig
from repro.core.executor import run_experiment
from repro.data.synthetic import make_task
from repro.serve.fleet import FleetClassifier, FleetParams, loop_classify
from repro.utils import trace
from repro.utils.compile_cache import use_compile_cache

# Largest deviations admitted, each relative to the reference's largest
# magnitude (max over leaves of max|x - ref| / max|ref|), set with a margin
# from what one TPU v5e chip showed (CHANGES.md records the readings).
# The chip's default precision multiplies float32 matrices in bfloat16, so
# a default-precision run and its float32 reference drift apart (0.235 for
# the CNN, 0.037 for the MLP); under float32 on both sides only the
# engines' own rounding is left (2.8e-7 for the MLP). The CNN's first
# gradients are large (|dL/dW| up to ~26 at init), so over 80 SGD steps per
# ring it amplifies even that rounding (0.030 on the chip, 3.2e-3 on a CPU).
PRECISION_TOL = 0.5     # default-precision run vs float32 reference
ACC_TOL = 0.05          # per-eval accuracy, same two runs
ENGINE_TOL = {"fedsr-mlp": 1e-5, "fedsr-cnn": 0.1}
                        # fused vs sequential (or 4 chips vs 1), float32
SERVE_TOL = 1e-2        # FleetClassifier vs loop_classify (float32)
SERVE_F32_TOL = 1e-5    # the same, both under float32

# the paper's Table IV FedSR deployment (benchmarks/fl_tables.py)
TABLE4 = dict(num_devices=100, num_edges=25, local_epochs=1, ring_rounds=5,
              partition="pathological", xi=2, participation=0.4)
HEAD = PersonalizeConfig(epochs=3, lr=0.02, mode="head")
NO_PERSONALIZE = PersonalizeConfig()


def compile_s(spans: dict) -> float:
    """Backend-compile seconds in a span-log snapshot, over every span."""
    return sum(c["seconds"] for c in spans["compiles"].values())


def rel_dev(x, ref) -> float:
    """max over leaves of max|x - ref| / max|ref|."""
    out = 0.0
    for a, b in zip(jax.tree.leaves(x), jax.tree.leaves(ref)):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        out = max(out, float(np.max(np.abs(a - b)))
                  / max(float(np.max(np.abs(b))), 1e-30))
    return out


def acc_dev(res, ref) -> float:
    """Largest per-eval accuracy difference of two runs."""
    return max(abs(a.accuracy - b.accuracy)
               for a, b in zip(res.history, ref.history))


def peak_bytes(device=None):
    """The device's ``peak_bytes_in_use``, where the backend reports it."""
    stats = (device or jax.devices()[0]).memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def emit(line: dict) -> None:
    print(json.dumps(line), flush=True)


def fedsr(engine: str, rounds: int, **overrides) -> FLConfig:
    return FLConfig(algorithm="fedsr", engine=engine, rounds=rounds,
                    **{**TABLE4, **overrides})


def train_phase(model: str, task: str, *,
                rounds: int = 4, eval_every: int = 2,
                personalize: PersonalizeConfig = NO_PERSONALIZE,
                task_kwargs=None, **overrides):
    """Phase 1: one FedSR run through the fused engine at the chip's
    default precision, checked against the sequential engine under float32,
    and the fused engine under float32 against the same reference. Returns
    the default-precision run's ``ExperimentResult`` and the test set."""
    cfg = get_config(model)
    train, test = make_task(task, **(task_kwargs or {}))
    blocks = rounds // eval_every

    def run(engine, **kw):
        return run_experiment(
            task=task, model_cfg=cfg, fl=fedsr(engine, rounds, **kw),
            eval_every=eval_every, train=train, test=test)

    res = run("fused", personalize=personalize, **overrides)
    with jax.default_matmul_precision("float32"):
        ref = run("sequential", **overrides)
        res32 = run("fused", **overrides)
    dev, dev32 = (rel_dev(r.final_model, ref.final_model)
                  for r in (res, res32))
    flat, _ = ravel_pytree(res.final_model)
    emit({"phase": f"train/{model}", "params": int(flat.size),
          "compile_s": compile_s(res.spans),
          "first_block_s": res.history[0].seconds,
          "warm_block_s": res.history[-1].seconds,
          "rounds_per_block": res.history[-1].rounds,
          "accuracy": [h.accuracy for h in res.history],
          "ref_accuracy": [h.accuracy for h in ref.history],
          "max_acc_dev": acc_dev(res, ref), "max_rel_dev": dev,
          "max_rel_dev_float32": dev32, "dispatches": res.dispatches,
          "personalized_accuracy": res.personalized_accuracy,
          "peak_bytes_in_use": peak_bytes()})
    check(bool(jnp.all(jnp.isfinite(flat))), f"{model}: non-finite model")
    check(len(res.history) == len(ref.history) == blocks,
          f"{model}: {len(res.history)} evals for {blocks} blocks")
    check(res.dispatches == blocks,
          f"{model}: {res.dispatches} dispatches for {blocks} blocks")
    check(acc_dev(res, ref) <= ACC_TOL, f"{model}: accuracy drift")
    check(dev <= PRECISION_TOL, f"{model}: deviation {dev}")
    check(dev32 <= ENGINE_TOL[model], f"{model}: float32 deviation {dev32}")
    return res, test


def serve_phase(model: str, fleet_arena, test, *,
                requests: int = 256, seed: int = 0) -> None:
    """Phase 2: one batch of ``requests`` routed over every client of the
    personalized fleet through ``FleetClassifier``, against the per-model
    loop under float32 matmul precision."""
    cfg = get_config(model)
    fleet = FleetParams(fleet_arena, device=True)
    rng = np.random.default_rng(seed)
    # every client of the fleet is served; clients repeat only when the
    # batch is larger than the fleet
    lanes = rng.permutation(np.arange(requests) % fleet.num_clients)
    images = test.images[rng.integers(0, len(test), requests)]
    clf = FleetClassifier(cfg)
    c0 = compile_s(trace.snapshot())
    t0 = time.perf_counter()
    jax.block_until_ready(clf(fleet, lanes, images))
    first_s = time.perf_counter() - t0
    first_compile_s = compile_s(trace.snapshot()) - c0
    t0 = time.perf_counter()
    logits = jax.block_until_ready(clf(fleet, lanes, images))
    warm_s = time.perf_counter() - t0
    with jax.default_matmul_precision("float32"):
        ref = loop_classify(cfg, fleet, lanes, images)
        logits32 = clf(fleet, lanes, images)
    dev, dev32 = rel_dev(logits, ref), rel_dev(logits32, ref)
    agree = float(np.mean(np.argmax(np.asarray(logits), -1)
                          == np.argmax(np.asarray(ref), -1)))
    emit({"phase": f"serve/{model}", "requests": requests,
          "distinct_lanes": int(np.unique(lanes).size),
          "compile_s": first_compile_s, "first_batch_s": first_s,
          "warm_batch_s": warm_s, "max_rel_dev": dev,
          "max_rel_dev_float32": dev32, "argmax_agreement": agree,
          "peak_bytes_in_use": peak_bytes()})
    check(logits.shape == (requests, cfg.num_classes),
          f"serve: logits shape {logits.shape}")
    check(bool(jnp.all(jnp.isfinite(logits))), "serve: non-finite logits")
    check(dev <= SERVE_TOL, f"serve: deviation {dev}")
    check(dev32 <= SERVE_F32_TOL, f"serve: float32 deviation {dev32}")


def mesh_phase(*, rounds: int = 4, eval_every: int = 2,
               task_kwargs=None, **overrides) -> None:
    """The lane axis and the data plane over every visible device
    (``mesh_data_axis="data"``) against the same run on one device, at the
    chip's default precision and under float32. The mesh run goes first,
    so each device's peak bytes are its own."""
    cfg = get_config("fedsr-cnn")
    train, test = make_task("cifar10_like", **(task_kwargs or {}))

    def run(**kw):
        return run_experiment(
            task="cifar10_like", model_cfg=cfg,
            fl=fedsr("fused", rounds, **overrides, **kw),
            eval_every=eval_every, train=train, test=test)

    mesh = run(mesh_data_axis="data")
    per_device = {str(d.id): peak_bytes(d) for d in jax.devices()}
    one = run()
    with jax.default_matmul_precision("float32"):
        mesh32, one32 = run(mesh_data_axis="data"), run()
    dev = rel_dev(mesh.final_model, one.final_model)
    dev32 = rel_dev(mesh32.final_model, one32.final_model)
    emit({"phase": "mesh/fedsr-cnn", "devices": len(jax.devices()),
          "compile_s": compile_s(mesh.spans),
          "warm_block_s": mesh.history[-1].seconds,
          "one_chip_warm_block_s": one.history[-1].seconds,
          "accuracy": [h.accuracy for h in mesh.history],
          "one_chip_accuracy": [h.accuracy for h in one.history],
          "max_acc_dev": acc_dev(mesh, one), "max_rel_dev": dev,
          "max_rel_dev_float32": dev32, "dispatches": mesh.dispatches,
          "peak_bytes_in_use_per_device": per_device})
    check(mesh.dispatches == rounds // eval_every,
          f"mesh: {mesh.dispatches} dispatches")
    check(acc_dev(mesh, one) <= ACC_TOL, "mesh: accuracy drift")
    check(dev <= PRECISION_TOL, f"mesh: deviation {dev}")
    check(dev32 <= ENGINE_TOL["fedsr-cnn"], f"mesh: float32 deviation {dev32}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the lane axis over 4 chips vs one chip")
    args = ap.parse_args(argv)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU; JAX found {devices[0].platform}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} "
              "device(s)", file=sys.stderr)
        return 1
    use_compile_cache()
    trace.watch_host()
    if args.chips == 4:
        mesh_phase()
    else:
        cnn, test = train_phase("fedsr-cnn", "cifar10_like",
                                personalize=HEAD)
        train_phase("fedsr-mlp", "mnist_like")
        serve_phase("fedsr-cnn", cnn.personalized_fleet, test)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
