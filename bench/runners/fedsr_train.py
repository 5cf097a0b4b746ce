"""Runner: FedSR training through the program's fused engine.

Set-up builds what the program's ``run_experiment`` builds — the clients
(``make_clients``), ``LocalTrainer``, the algorithm (``make_algorithm``),
``CommMeter`` and the jitted eval — from a data set and initial weights
that the benchmark makes from the seed. ``run_experiment`` cannot stop at
a deadline, so the runner drives the executor's serial loop (the
``prefetch=0`` path) itself: ``algo.run_schedule`` for a block of
``eval_every`` rounds, then the eval, read back to the host.

Set-up runs the first ``check_blocks`` blocks through that same loop (the
first compiles) and keeps the model after each; the window goes on with
the same objects and stops after the first whole block that ends past
``--seconds``. ``round_s`` is the window's wall time over the rounds its
blocks completed.

After the window the reference (``bench/reference/fedsr_train.py``)
replays the set-up blocks' rounds as the planner drew them, and the
numbers that the traffic file gives a limit are compared:

* ``acc_gap``: the largest gap between the program's eval accuracy and
  the reference's, over the set-up blocks;
* ``d1_gap``: the first block's change of the global model, per leaf:
  the gap between the norm of the program's change and the reference's,
  over the larger of the reference's norm of that leaf and of the median
  leaf; the worst leaf;
* ``dN_gap``: the same for the change over all ``check_blocks`` blocks;
* ``dN_diff``: the change over all set-up blocks by the norm of the
  difference of the two changes, over the same denominator; the median
  leaf. It is the number that sees a lower precision (PERF.md);
* ``d1_diff``: the same for the first block's change, read before the
  rounding of later blocks adds to it.

Every run prints the readings on an earlier line of stdout.

Leaves whose reference change is under a thousandth of the median leaf's
are left out of all of them (none is, in these models).
"""
from __future__ import annotations

import gc
import time

import numpy as np

from bench import compare, gen
from bench.harness import (BENCH, Check, Context, Outcome, load_module,
                           model_config)


def _deployment(traffic: dict, seed: int):
    from repro.configs.base import FLConfig
    return FLConfig(seed=int(seed), **traffic["fl"])


def build(ctx: Context):
    """Everything ``run_experiment`` builds, from the seed. Returns a dict
    of the program's objects and the benchmark's own inputs."""
    import jax
    import jax.numpy as jnp

    from repro.core.algorithms import make_algorithm
    from repro.core.comm import CommMeter
    from repro.core.local import LocalTrainer
    from repro.data.pipeline import make_clients
    from repro.data.synthetic import Dataset
    from repro.models.small import classifier_accuracy
    from repro.optim.schedules import cosine_decay
    from repro.utils.tree import tree_bytes

    cfgj, traffic = ctx.cell.config, ctx.cell.traffic
    data = traffic["data"]
    cfg = model_config(cfgj)
    with ctx.phase("data"):
        (xtr, ytr), (xte, yte) = gen.image_task(ctx.seed, data)
        train = Dataset(gen.host_images(xtr, data), np.asarray(ytr),
                        cfgj["num_classes"])
        test_host = (gen.host_images(xte, data), np.asarray(yte))
        del xtr, ytr, xte
    with ctx.phase("program"):
        fl = _deployment(traffic, ctx.seed)
        rng = np.random.default_rng(ctx.seed)
        clients = make_clients(train, scheme=fl.partition,
                               num_devices=fl.num_devices, rng=rng, xi=fl.xi,
                               alpha=fl.alpha)
        w0 = gen.init_model(ctx.seed, cfgj)
        compare.same_structure(w0, cfg)
        trainer = LocalTrainer(cfg, fl)
        algo = make_algorithm(fl.algorithm, trainer, clients, fl)
        meter = CommMeter(model_bytes=tree_bytes(w0))
        lr_fn = cosine_decay(fl.init_lr, fl.final_lr, fl.rounds)
        acc_fn = jax.jit(lambda p, x, y: classifier_accuracy(p, x, y, cfg))
        test = (jnp.asarray(test_host[0]), yte)
    return dict(fl=fl, rng=rng, algo=algo, trainer=trainer, meter=meter,
                lr_fn=lr_fn, acc_fn=acc_fn, w=w0, state={},
                w0=jax.device_get(w0), train=train, test=test,
                test_host=test_host)


class Loop:
    """The executor's serial block loop over a built run."""

    def __init__(self, ctx: Context, run: dict):
        self.ctx = ctx
        self.run = run
        self.t = 0
        self.every = int(ctx.cell.traffic["eval_every"])
        self.schedules = []         # the planner's draws, while recording
        self.recording = False
        plan = run["algo"].plan_schedule

        def recorded(*args, **kwargs):
            sched = plan(*args, **kwargs)
            if self.recording:
                self.schedules.append(sched)
            return sched

        run["algo"].plan_schedule = recorded

    def block(self) -> float:
        """One block and its eval; returns the accuracy read back."""
        r, span = self.run, self.ctx.span
        stop = self.t + self.every
        lrs = np.asarray([float(r["lr_fn"](i)) for i in range(self.t, stop)])
        with span("run_schedule"):
            r["w"], r["state"] = r["algo"].run_schedule(
                r["w"], self.t, lrs, r["rng"], r["meter"], r["state"])
        with span("eval"):
            acc = float(r["acc_fn"](r["w"], *r["test"]))
        self.t = stop
        return acc


def lane_steps(schedules) -> int:
    """Valid (lane, step) pairs of the schedules: every visit's steps."""
    return sum(int(p.shape[0]) for s in schedules for plan in s.plans
               for g in plan.groups for hop in g.hops for p in hop.plans
               if p is not None)


def work(ctx: Context, schedules, blocks: int) -> dict:
    """The operations and bytes the window's work requires (see PERF.md):
    every valid lane-step's forward and backward and momentum update, the
    eq.-11 reduce, and every eval's forward; bytes are each step's batch
    read once, the global model read and written once a round, and each
    eval's test set read once."""
    cfgj, counts = ctx.cell.config, ctx.cell.counts
    fl = ctx.cell.traffic["fl"]
    n_params = counts.param_count(cfgj)
    steps = lane_steps(schedules)
    rounds = sum(s.rounds for s in schedules)
    lanes = sum(g.lanes for s in schedules for p in s.plans for g in p.groups)
    n_test = cfgj["num_classes"] * ctx.cell.traffic["data"]["test_per_class"]
    b = fl["batch_size"]
    flops = (steps * (b * counts.train_flops(cfgj) + 4 * n_params)
             + lanes * 2 * n_params
             + blocks * n_test * counts.forward_flops(cfgj))
    nbytes = (steps * b * counts.image_bytes(cfgj)
              + rounds * 8 * n_params
              + blocks * (n_test * counts.image_bytes(cfgj) + 4 * n_params))
    return {"flops": flops, "bytes": nbytes, "lane_steps": steps,
            "rounds": rounds}


def reference_models(ctx: Context, run: dict, schedules,
                     control: bool = False):
    """The reference's models and accuracies over ``schedules``; the
    control's with ``control``."""
    ref = load_module(BENCH / "reference" / "fedsr_train.py")
    blocks = [[[[(dev, plan) for dev, plan in zip(hop.ids, hop.plans)]
                for hop in p.groups[0].hops] for p in s.plans]
              for s in schedules]
    if any(len(p.groups) != 1 for s in schedules for p in s.plans):
        raise RuntimeError("a FedSR round is one visit group")
    train = run["train"]
    return ref.replay(ctx.cell.reference, ctx.cell.config, run["w0"],
                      train.images, train.labels, *run["test_host"],
                      ctx.cell.traffic["fl"], ctx.seed, blocks, control=control)


def readings(w0, models, accs, ref_models, ref_accs) -> dict:
    """The numbers the program's set-up blocks are read by; a cell
    compares those its traffic file gives a limit."""
    return {
        "acc_gap": max(abs(a - b) for a, b in zip(accs, ref_accs)),
        "d1_gap": compare.norm_gap(w0, models[0], ref_models[0]),
        "dN_gap": compare.norm_gap(w0, models[-1], ref_models[-1]),
        "dN_diff": compare.median_diff(w0, models[-1], ref_models[-1]),
        "d1_diff": compare.median_diff(w0, models[0], ref_models[0]),
    }


def setup_blocks(ctx: Context, run: dict, loop: Loop):
    """The first ``check_blocks`` blocks, recorded; the first compiles."""
    import jax
    models, accs = [], []
    loop.recording = True
    for k in range(int(ctx.cell.traffic["check_blocks"])):
        c0 = ctx.clock.seconds
        t0 = time.perf_counter()
        accs.append(loop.block())
        models.append(jax.device_get(run["w"]))
        ctx.emit(setup_block=k, seconds=time.perf_counter() - t0,
                 compile_s=ctx.clock.seconds - c0, accuracy=accs[-1])
    loop.recording = False
    return models, accs


def run(ctx: Context) -> Outcome:
    run_ = build(ctx)
    loop = Loop(ctx, run_)
    models, accs = setup_blocks(ctx, run_, loop)
    checked = list(loop.schedules)

    loop.recording = True           # the window's draws, for the work count
    loop.schedules = []
    blocks, failed, ends = 0, 0, []
    with ctx.window():
        start = time.perf_counter()
        while True:
            acc = loop.block()
            blocks += 1
            failed += not np.isfinite(acc)
            ends.append(time.perf_counter() - start)
            if ends[-1] >= ctx.seconds:
                break
    ctx.read_memory_peak()
    window = work(ctx, loop.schedules, blocks)
    rounds = window["rounds"]
    ctx.emit(window_blocks=blocks, window_rounds=rounds,
             lane_steps=window["lane_steps"], flops=window["flops"],
             bytes=window["bytes"], memory_peak_bytes=ctx.memory_peak_bytes,
             dispatches=run_["trainer"].dispatches)
    block_s = np.diff(ends, prepend=0.0)
    ctx.emit(block_s_median=float(np.median(block_s)),
             block_s_max=float(block_s.max()),
             block_s_slowest=sorted(block_s.tolist())[-5:])

    # free the program's state before the reference runs
    w0 = run_["w0"]
    for key in ("algo", "trainer", "w", "state", "test", "acc_fn"):
        run_.pop(key)
    loop.run = None
    gc.collect()
    t0 = time.perf_counter()
    ref_models, ref_accs = reference_models(ctx, run_, checked)
    ctx.emit(reference_s=time.perf_counter() - t0, accuracy=accs,
             ref_accuracy=ref_accs)
    if ctx.control:         # calibration: the one-pass reference in its place
        models, accs = reference_models(ctx, run_, checked, control=True)
    got = readings(w0, models, accs, ref_models, ref_accs)
    ctx.emit(readings=got)
    lim = ctx.cell.traffic["limits"]
    return Outcome(metrics={"round_s": ctx.window_s / rounds},
                   attempted=blocks, failed=failed,
                   checks=[Check(k, got[k], lim[k]) for k in lim],
                   record=dict(window, blocks=blocks, readings=got))
