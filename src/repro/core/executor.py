"""FL experiment executor: dataset -> partition -> T rounds -> history.

This is the engine behind every paper table (benchmarks/) and the FL
integration tests. ``w_glob`` stays device-resident for the whole run:
planners reference it only through the GLOBAL sentinel and the engines
aggregate in-jit (see ``core.plan``), so rounds chain device array ->
device array with no host unstack/restack; the host only sees it at
checkpoint time (``jax.device_get`` inside ``checkpoint.io.save``).

The driver is *chunked* (PR 5): rounds run in eval-to-eval blocks —
plan block -> run block -> eval -> record -> checkpoint — through
``algo.run_schedule``, so the host re-enters the loop only at eval /
checkpoint boundaries. Under the fused engine a whole block is ONE
compiled dispatch (``core.plan.Schedule``); the block boundaries are
computed from absolute round indices, so a resumed run re-aligns to the
same blocks and stays bit-exact.

The block boundary is also the residency protocol's boundary (PR 7,
``FLConfig.store="host"``): each ``run_schedule`` call stages only the
block's visited clients' data + state rows onto device and writes the
trained rows back afterwards, so fleet size K is decoupled from device
memory; ``ExperimentResult.peak_device_bytes`` reports the peak
(``core.comm.ResidencyMeter``).

``FLConfig.prefetch=1`` runs the same blocks through a *pipelined*
driver: while block ``t``'s dispatch is in flight (JAX async dispatch —
``dispatch_block`` returns as soon as the work is enqueued), the host
plans block ``t+1`` (pure host RNG work), hands its cohort arena to the
store's background staging thread (``ClientStore.prefetch``), eagerly
stages its state rows when the visited sets are disjoint, and defers the
eval readback so the only host sync points are block retirement
(``finish_block``'s state write-back) and eval consumption. Planning
order is identical to the serial driver (block t fully planned before
block t+1), so the RNG stream — and therefore every result — is
bit-exact to ``prefetch=0``; checkpoints snapshot the RNG state *between*
the two plans so a resumed run re-plans the lookahead block identically.
"""
from __future__ import annotations

import copy
import dataclasses
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import FLConfig, ModelConfig
from repro.core.algorithms import make_algorithm
from repro.core.comm import CommMeter
from repro.core.local import LocalTrainer
from repro.data.pipeline import make_clients
from repro.data.synthetic import Dataset, make_task
from repro.models.small import classifier_accuracy, init_small_model
from repro.optim.schedules import cosine_decay
from repro.utils import trace
from repro.utils.tree import tree_bytes

Pytree = Any


@dataclasses.dataclass
class RoundRecord:
    """One eval point. ``seconds`` covers the wall time since the PREVIOUS
    record (the whole block of ``rounds`` rounds plus this eval), not just
    the final round — under ``eval_every > 1`` the old per-round timing
    silently dropped all but the last round's cost. ``rounds`` is the
    round count the record covers (old checkpoints default to 1)."""

    round: int
    accuracy: float
    comm: Dict[str, float]
    lr: float
    seconds: float
    rounds: int = 1


@dataclasses.dataclass
class ExperimentResult:
    algorithm: str
    task: str
    partition: str
    history: List[RoundRecord]
    final_model: Optional[Pytree] = None    # the run's last w_glob (device-
                                            # resident; exact-resume tests
                                            # compare it tree-for-tree)
    peak_device_bytes: int = 0              # residency meter readout: max
                                            # over blocks of staged data +
                                            # state bytes (FLConfig.store;
                                            # O(cohort) under "host", both
                                            # pipeline buffers counted under
                                            # prefetch=1)
    dp_epsilon: Optional[float] = None      # (eps, delta) spent by the run's
    dp_delta: Optional[float] = None        # DP-SGD ledger (dp_clip > 0 only)
    stage_seconds: float = 0.0              # host->device staging wall
                                            # (store gathers + uploads)
    overlapped_stage_seconds: float = 0.0   # staging wall hidden behind an
                                            # in-flight dispatch (prefetch=1)
    dispatch_seconds: float = 0.0           # per-block dispatch-to-sync wall
    personalized_accuracy: Optional[float] = None
                                            # mean per-client accuracy of the
                                            # personalized fleet on label-
                                            # matched test draws (PersonalizeC
                                            # onfig.active runs only)
    global_client_accuracy: Optional[float] = None
                                            # the global model on the SAME
                                            # draws — the like-for-like
                                            # baseline the lift is against
    personalized_fleet: Optional[Pytree] = None
                                            # host (K, ...) stacked arena of
                                            # per-client fine-tuned params
                                            # (feeds serve.fleet routing)
    dispatches: int = 0                     # the training rounds' compiled
                                            # calls (LocalTrainer.dispatches;
                                            # one per block under the fused
                                            # engine)
    spans: Dict[str, Any] = dataclasses.field(default_factory=dict)
                                            # the span log over the run
                                            # (``utils.trace.snapshot``)

    @property
    def overlap_fraction(self) -> float:
        """Fraction of the staging wall the prefetch pipeline hid (0.0
        when nothing was staged or prefetch=0)."""
        if self.stage_seconds <= 0.0:
            return 0.0
        return self.overlapped_stage_seconds / self.stage_seconds

    @property
    def final_accuracy(self) -> float:
        return self.history[-1].accuracy if self.history else float("nan")

    def rounds_to_accuracy(self, target: float) -> Optional[int]:
        for rec in self.history:
            if rec.accuracy >= target:
                return rec.round
        return None

    def comm_to_accuracy(self, target: float) -> Optional[int]:
        """Total model transfers when target accuracy is first hit (Table III)."""
        for rec in self.history:
            if rec.accuracy >= target:
                return rec.comm["total_transfers"]
        return None


def run_experiment(
    *,
    task: str,
    model_cfg: ModelConfig,
    fl: FLConfig,
    eval_every: int = 1,
    train: Optional[Dataset] = None,
    test: Optional[Dataset] = None,
    quiet: bool = True,
    checkpoint_dir: Optional[str] = None,
    checkpoint_every: int = 0,
    resume: bool = False,
    stop_after: Optional[int] = None,   # simulate interruption after round N
) -> ExperimentResult:
    trace.reset()       # ExperimentResult.spans covers this run
    if train is None or test is None:
        train, test = make_task(task, seed=fl.seed)
    rng = np.random.default_rng(fl.seed)
    clients = make_clients(
        train, scheme=fl.partition, num_devices=fl.num_devices,
        rng=rng, xi=fl.xi, alpha=fl.alpha,
    )
    if fl.adversary.active and fl.adversary.kind == "label_flip":
        # data poison: attacker shards get permuted labels once, before
        # any training (the adversary's own seed picks the attackers)
        from repro.core.adversary import AdversaryState
        clients = AdversaryState(fl.adversary, fl.num_devices).poison_clients(
            clients, model_cfg.num_classes)
    trainer = LocalTrainer(model_cfg, fl)
    w_glob = init_small_model(jax.random.PRNGKey(fl.seed), model_cfg)
    algo = make_algorithm(fl.algorithm, trainer, clients, fl)
    meter = CommMeter(model_bytes=tree_bytes(w_glob))
    lr_fn = cosine_decay(fl.init_lr, fl.final_lr, fl.rounds)
    state: Dict = {}
    start_round = 0
    history: List[RoundRecord] = []

    if resume and checkpoint_dir:
        ck = _restore_checkpoint(checkpoint_dir)
        if ck is not None:
            w_glob = ck["w_glob"]
            start_round = int(ck["round"])
            rng.bit_generator.state = ck["rng_state"]
            for k, v in ck["comm"].items():
                setattr(meter, k,
                        float(v) if k == "sim_seconds" else int(v))
            # pre-checkpoint history rides along so rounds_to_accuracy /
            # comm_to_accuracy see the full run, not just the resumed tail
            history = [RoundRecord(**h) for h in ck.get("history", [])]
            # algorithm memory (MOON's prev locals, SCAFFOLD's control
            # variates) resumes too — dropping it silently resets those
            # algorithms to round-0 behaviour mid-run. The msgpack layout
            # is per-client-id dicts; the algorithm unpacks it into its
            # device-resident carry (core.state)
            state = algo.state_from_ckpt(ck.get("state") or {}, w_glob)

    test_images = jnp.asarray(test.images)
    test_labels = jnp.asarray(test.labels)
    acc_fn = jax.jit(lambda p: classifier_accuracy(p, test_images, test_labels, model_cfg))

    # chunked block driver: run to the next eval / checkpoint / stop
    # boundary in ONE algo.run_schedule call (one compiled dispatch under
    # the fused engine), then eval + record + checkpoint. Boundaries are
    # absolute round indices, so a resumed run re-aligns to the same
    # blocks regardless of where its checkpoint landed.
    end = fl.rounds if stop_after is None else min(fl.rounds, stop_after)

    def next_boundary(t: int) -> int:
        stop = min(end, t - t % eval_every + eval_every)
        if checkpoint_dir and checkpoint_every:
            stop = min(stop, t - t % checkpoint_every + checkpoint_every)
        return stop

    def block_lrs(t: int, stop: int) -> np.ndarray:
        return np.asarray([float(lr_fn(i)) for i in range(t, stop)])

    t = start_round
    last_time = time.perf_counter()
    last_round = start_round
    dispatch_t0: Optional[float] = None

    def record_eval(t_now: int, acc_dev, lrs) -> None:
        """Consume a deferred eval: fence the device value BEFORE reading
        the clock (JAX async dispatch would otherwise under-measure the
        block), then record the eval point."""
        nonlocal last_time, last_round, dispatch_t0
        with trace.span("eval"):
            jax.block_until_ready(acc_dev)
        now = time.perf_counter()
        if dispatch_t0 is not None:
            algo.residency.record_dispatch(now - dispatch_t0)
            dispatch_t0 = None
        acc = float(acc_dev)
        history.append(RoundRecord(
            round=t_now, accuracy=acc, comm=meter.snapshot(),
            lr=float(lrs[-1]), seconds=now - last_time,
            rounds=t_now - last_round,
        ))
        last_time, last_round = now, t_now
        if not quiet:
            print(f"  [{fl.algorithm:>12}] round {t_now:>3} "
                  f"acc={acc:.4f} lr={lrs[-1]:.5f} "
                  f"transfers={meter.total_transfers}")

    pipelined = fl.prefetch > 0 and algo.pipelinable
    if not pipelined:
        # the serial driver (prefetch=0, and algorithms that bypass the
        # Schedule IR): plan -> stage -> dispatch -> eval, one block at a
        # time — the pre-pipeline behaviour, bit-for-bit
        while t < end:
            stop = next_boundary(t)
            lrs = block_lrs(t, stop)
            if dispatch_t0 is None:
                dispatch_t0 = time.perf_counter()
            w_glob, state = algo.run_schedule(w_glob, t, lrs, rng, meter,
                                              state)
            t = stop
            # `t == end` (not fl.rounds): a stop_after/rounds not aligned
            # to eval_every still gets its final partial block evaluated,
            # so history always reaches the returned final_model
            if t % eval_every == 0 or t == end:
                record_eval(t, acc_fn(w_glob), lrs)
            if (checkpoint_dir and checkpoint_every
                    and t % checkpoint_every == 0):
                _save_checkpoint(checkpoint_dir, w_glob, t,
                                 rng.bit_generator.state, meter,
                                 history, algo.state_to_ckpt(state))
    else:
        # the pipelined driver (prefetch=1): while block t's dispatch is
        # in flight, plan block t+1 and start staging it. Planning order
        # is the serial driver's exactly (block t fully planned before
        # block t+1), so the RNG stream — and every result — is bit-exact
        # to prefetch=0; only the staging/eval wall overlaps.
        sched = lrs = None
        if t < end:
            stop = next_boundary(t)
            lrs = block_lrs(t, stop)
            sched = algo.plan_schedule(t, len(lrs), rng, state)
        while sched is not None:
            if dispatch_t0 is None:
                dispatch_t0 = time.perf_counter()
            w_glob = algo.dispatch_block(sched, w_glob, lrs, state)
            is_eval = stop % eval_every == 0 or stop == end
            # queue the eval readback without consuming it — the record
            # path syncs only when the value is needed
            acc_dev = acc_fn(w_glob) if is_eval else None
            # snapshot the RNG BETWEEN the two plans: a checkpoint at
            # this boundary resumes by re-planning the lookahead block
            # from this exact state, converging with the serial driver
            rng_snap = copy.deepcopy(rng.bit_generator.state)
            nxt = None
            if stop < end:
                stop2 = next_boundary(stop)
                lrs2 = block_lrs(stop, stop2)
                sched2 = algo.plan_schedule(stop, len(lrs2), rng, state)
                # overlap: data to the store's staging thread, state rows
                # eagerly iff the visited sets are disjoint
                algo.prefetch_block(sched2, sched.visited(), state)
                nxt = (sched2, lrs2, stop2)
            # retire the in-flight block (state write-back = the sync)
            algo.finish_block(sched, state, meter)
            t = stop
            if is_eval:
                record_eval(t, acc_dev, lrs)
            if (checkpoint_dir and checkpoint_every
                    and t % checkpoint_every == 0):
                _save_checkpoint(checkpoint_dir, w_glob, t, rng_snap,
                                 meter, history, algo.state_to_ckpt(state))
            sched, lrs, stop = nxt if nxt is not None else (None, None, None)

    # post-global personalization stage (core.personalize): fine-tune the
    # whole fleet from the final w_glob as a (K, ...) stacked arena, one
    # vmapped dispatch per block, reusing the engine's client store when
    # it has one (the fused engine) so the residency protocol carries
    # over. Runs on its own RNG stream AFTER the round loop — inactive
    # configs execute nothing and stay bit-exact.
    preport = None
    if fl.personalize.active:
        from repro.core.personalize import personalize_fleet, save_personalized
        preport = personalize_fleet(
            model_cfg, fl, clients, w_glob, test,
            store=getattr(algo.engine, "store", None))
        if checkpoint_dir:
            save_personalized(checkpoint_dir, preport.fleet, fl.num_devices)

    # fold the store's staging instrumentation into the run's meter
    stage_s, overlap_s = algo.engine.staging_stats()
    algo.residency.stage_seconds = stage_s
    algo.residency.overlapped_stage_seconds = overlap_s
    store = getattr(algo.engine, "store", None)
    if store is not None:
        store.close()
    eps, delta = ((None, None) if algo.privacy is None
                  else algo.privacy.spent)
    res = algo.residency
    return ExperimentResult(fl.algorithm, task, fl.partition, history,
                            final_model=w_glob,
                            peak_device_bytes=res.peak_bytes,
                            dp_epsilon=eps, dp_delta=delta,
                            stage_seconds=res.stage_seconds,
                            overlapped_stage_seconds=(
                                res.overlapped_stage_seconds),
                            dispatch_seconds=res.dispatch_seconds,
                            personalized_accuracy=(
                                None if preport is None
                                else preport.personalized_accuracy),
                            global_client_accuracy=(
                                None if preport is None
                                else preport.global_client_accuracy),
                            personalized_fleet=(
                                None if preport is None else preport.fleet),
                            dispatches=trainer.dispatches,
                            spans=trace.snapshot())


# ---------------------------------------------------------------------------
# checkpoint / resume (exact: model + round + numpy RNG + comm counters +
# eval history + algorithm state — dropping history would silently change
# rounds_to_accuracy / comm_to_accuracy answers on a resumed run, and
# dropping state would silently reset MOON's prev locals and SCAFFOLD's
# control variates)


def _pack_state(state):
    """Algorithm state as a msgpack-able tree: client-id dict keys (ints)
    become tagged strings so ``checkpoint.io`` round-trips them exactly."""
    if isinstance(state, dict):
        return {(f"i:{k}" if isinstance(k, int) else str(k)): _pack_state(v)
                for k, v in state.items()}
    return state


def _unpack_state(obj):
    """Inverse of ``_pack_state`` over a restored tree."""
    if isinstance(obj, dict):
        return {(int(k[2:]) if isinstance(k, str) and k.startswith("i:")
                 else k): _unpack_state(v)
                for k, v in obj.items()}
    return obj


def _save_checkpoint(ckdir: str, w_glob, round_: int, rng_state: Dict,
                     meter: CommMeter,
                     history: List[RoundRecord] = (), state: Dict = None):
    """``rng_state`` is the numpy bit-generator state dict to persist — the
    pipelined driver passes a snapshot taken BEFORE the lookahead block was
    planned (so a resumed run re-plans it identically), the serial driver
    passes the generator's current state."""
    import json as _json
    import os as _os

    from repro.checkpoint.io import save as _save

    with trace.span("checkpoint"):
        _os.makedirs(ckdir, exist_ok=True)
        _save(f"{ckdir}/model.msgpack", w_glob)
        _save(f"{ckdir}/algo_state.msgpack", _pack_state(state or {}))
        comm = {f: int(getattr(meter, f)) for f in
                ("model_bytes", "cloud_up", "cloud_down", "edge_up",
                 "edge_down", "p2p")}
        comm["sim_seconds"] = float(meter.sim_seconds)
        with open(f"{ckdir}/state.json", "w") as f:
            _json.dump({"round": round_, "rng_state": rng_state,
                        "comm": comm,
                        "history": [dataclasses.asdict(r)
                                    for r in history]}, f)


def _restore_checkpoint(ckdir: str):
    import json as _json
    import os as _os

    from repro.checkpoint.io import restore as _restore

    if not _os.path.exists(f"{ckdir}/state.json"):
        return None
    with open(f"{ckdir}/state.json") as f:
        meta = _json.load(f)
    out = {"w_glob": _restore(f"{ckdir}/model.msgpack"), **meta}
    # absent in pre-PR-4 checkpoints: those resume with empty state
    if _os.path.exists(f"{ckdir}/algo_state.msgpack"):
        out["state"] = _unpack_state(_restore(f"{ckdir}/algo_state.msgpack"))
    return out
