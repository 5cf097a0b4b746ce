"""All FL algorithms compared in the paper (§IV-B) — as *planners*.

Every algorithm is a pure planner over the RoundPlan IR (``core.plan``):
``plan_round(t, rng, state)`` consumes only the host RNG, the config and
the algorithm's host-side state, and emits a declarative plan — visit
groups (a star cohort, or hop-sequenced ring stacks with pre-drawn batch
plans), an extras spec (cohort-shared vs per-lane), an aggregation spec
(eq. 11 weights, per-edge grouping for HierFAVG), and closed-form comm
records (Table III). Execution lives entirely in ``core.engines``; which
engine interprets the plan is ``FLConfig.engine``'s choice and never
changes the math.

Planners draw ALL randomness (participation sampling, ring orders, batch
plans) in the sequential engine's visit order, so every engine consumes a
bit-identical RNG stream by construction — parity is structural, not
per-engine discipline. Algorithms with memory (MOON's previous locals,
SCAFFOLD's control variates) request the final group's per-lane models
(``keep_locals``) and fold them back into ``state`` in ``update_state``.

``run_schedule(w_glob, t0, lrs, rng, meter, state)`` is THE driver: it
pre-plans ``len(lrs)`` rounds into a ``Schedule`` (same RNG order — plans
reference state only through ``StateRef`` sentinels, so round r+1 can be
planned before round r runs) and hands the whole block to the engine;
under the fused engine an eval-to-eval block is ONE compiled dispatch.
``run_round(w_glob, t, lr, rng, meter, state)`` (benchmarks, parity
tests) is just a length-1 block through the same path — there is no
separate per-round driver to keep in sync, and even a lone HierFAVG
round fuses its R per-edge iterations. Plans reference the global model
only through the ``GLOBAL`` sentinel, so ``w_glob`` stays
device-resident across rounds — with the engines' in-jit aggregation
there is no per-round unstack/host/restack of model trees at all.

Algorithm memory (MOON's previous locals, SCAFFOLD's control variates) is
device-resident (``core.state``): a (K + 1, ...) client stack plus a host
``seen`` mask, updated by the same pure function whether the driver steps
round-by-round or the fused engine scans a whole block.

Client virtualization (``FLConfig.store="host"``): the block boundary is
also the residency protocol's boundary. ``run_schedule`` computes the
block's visited set from the pre-drawn plans (``Schedule.visited`` —
participation is planner-drawn, so no device readback), stages the
visited clients' state rows as a ``(V + 1, ...)`` cohort carry plus the
fleet→cohort rowmap engines consume, asks the engine to stage the
cohort's data (``Engine.stage_data`` — the fused engine's per-block
``CohortArena``), records peak residency on ``self.residency``, runs the
block, and scatters the trained rows back into the host arena. Peak
device bytes for data + state therefore scale with the cohort, not K.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import FLConfig
from repro.core.adversary import AdversaryState
from repro.core.comm import CommMeter, ResidencyMeter
from repro.core.engines import make_engine
from repro.core.local import LocalTrainer
from repro.core.privacy import PrivacyLedger, plan_max_client_steps
from repro.core.plan import (
    GLOBAL, AggSpec, Hop, RoundPlan, RoundResult, Schedule, StateRef,
    VisitGroup,
)
from repro.core.ring import ring_lap_hops
from repro.core.scenario import ScenarioState
from repro.core.state import (
    client_stack, host_stack, pack_client_rows, rowmap_for,
    scaffold_step_compiled, scatter_rows, stage_rows, unpack_client_rows,
    unstage_rows,
)
from repro.core.topology import assign_edges, clusters_of, sample_ring
from repro.data.pipeline import ClientData, plan_epoch_indices
from repro.utils import trace
from repro.utils.tree import tree_bytes, tree_stack, tree_zeros_like

Pytree = Any


class _Planner:
    """Shared planner base: sampling/weights helpers + the round driver."""

    variant = "plain"
    keep_locals = False
    pipelinable = True              # False: the algorithm bypasses the
                                    # Schedule IR (Centralized) — the
                                    # executor falls back to the serial
                                    # driver under FLConfig.prefetch=1
    _transfers_per_client = 1       # model each way (SCAFFOLD ships 2)
    _client_fields: Tuple[str, ...] = ()    # per-client state arenas (staged
                                            # per block under store="host")
    _shared_fields: Tuple[str, ...] = ()    # unstacked device trees
                                            # (SCAFFOLD's server variate)

    def __init__(self, trainer: LocalTrainer, clients: List[ClientData],
                 fl: FLConfig):
        self.trainer = trainer
        self.clients = clients
        self.fl = fl
        self.engine = make_engine(trainer, clients, fl)
        self.edges = assign_edges(fl.num_devices, fl.num_edges)
        self.scenario = ScenarioState(fl.scenario, fl.num_devices)
        self.adversary = AdversaryState(fl.adversary, fl.num_devices)
        self.privacy = (PrivacyLedger(fl.dp_noise_mult, fl.dp_delta)
                        if fl.dp_clip > 0 else None)
        self.residency = ResidencyMeter()
        trace.watch_host()
        self._transient_state_bytes = 0     # the in-flight block's staged
                                            # carries while the next block's
                                            # are eagerly staged (pipeline)

    # -- THE execution driver (identical for every algorithm) ------------
    def run_round(self, w_glob, t, lr, rng: np.random.Generator,
                  meter: CommMeter, state: Dict) -> Tuple[Pytree, Dict]:
        """One round = a length-1 schedule block. The single block driver
        serves both cadences (the old separate per-round driver is gone),
        so the RNG stream, meters and state updates are shared by
        construction — and under the fused engine even a lone HierFAVG
        round fuses its R per-edge iterations into one dispatch."""
        return self.run_schedule(w_glob, t, np.asarray([lr], np.float64),
                                 rng, meter, state)

    def run_schedule(self, w_glob, t0, lrs, rng: np.random.Generator,
                     meter: CommMeter, state: Dict) -> Tuple[Pytree, Dict]:
        """The block driver: pre-plan ``len(lrs)`` rounds (consuming the
        RNG stream exactly as ``len(lrs)`` single-round calls would) and
        execute them through the engine's block runner — a python loop of
        rounds everywhere except the fused engine, where the whole block
        is one compiled dispatch. Comm is applied from the block's summed
        closed-form records.

        The block boundary doubles as the residency protocol's boundary
        (``FLConfig.store="host"``): stage the visited clients' state
        rows + cohort data, run, write the trained rows back — peak
        device bytes recorded on ``self.residency``.

        The body is phase-split so the pipelined executor
        (``FLConfig.prefetch=1``) can interleave blocks:
        ``dispatch_block`` (stage + launch — returns under JAX async
        dispatch before the device finishes) and ``finish_block``
        (state write-back + privacy/comm retirement — the block's host
        sync point). This serial composition IS the pre-pipeline driver,
        statement for statement, so ``prefetch=0`` is bit-exact by
        construction."""
        sched = self.plan_schedule(t0, len(lrs), rng, state)
        w_glob = self.dispatch_block(sched, w_glob, lrs, state)
        self.finish_block(sched, state, meter)
        return w_glob, state

    def dispatch_block(self, sched: Schedule, w_glob, lrs,
                       state: Dict) -> Pytree:
        """Stage the block's residency (state rows + cohort data — a
        matching ``prefetch_block`` makes both hand-offs) and launch the
        dispatch. Returns as soon as the work is enqueued; the returned
        ``w_glob`` is a device future under the fused engine."""
        with trace.span("stage"):
            self.ensure_state(state, w_glob)
            visited = sched.visited()
            self._stage_state(state, visited)
            data_bytes = self.engine.stage_data(visited)
            self.residency.record(data_bytes,
                                  self._staged_state_bytes(state))
            # double-buffered high-water mark: both pipeline arenas at the
            # hand-off (``stage_pair_nbytes``) plus the previous block's
            # staged carries if the next block's were eagerly staged while
            # they were still live
            self.residency.record_transient(
                self.engine.stage_pair_nbytes()
                + self._staged_state_bytes(state)
                + self._transient_state_bytes)
            self._transient_state_bytes = 0
        return self.engine.run_schedule(sched, w_glob, lrs, state,
                                        self.update_state)

    def finish_block(self, sched: Schedule, state: Dict,
                     meter: CommMeter) -> None:
        """Retire a dispatched block: write the trained state rows back
        into the host arena (the ONE device readback of the residency
        protocol — the pipeline's sync point) and apply the block's
        closed-form privacy/comm records."""
        with trace.span("finish"):
            self._unstage_state(state)
            if self.privacy is not None:
                # worst-case client: the ledger advances by each round's
                # max per-client executed steps (closed-form on the plans)
                for plan in sched.plans:
                    self.privacy.record(plan_max_client_steps(plan))
            if meter is not None:
                for channel, count in sched.comm:
                    meter.record(channel, count)
                # accumulate round-by-round (NOT a pre-summed block total)
                # so the float stream is block-size invariant bit-exactly
                for plan in sched.plans:
                    meter.record_time(plan.sim_seconds)

    def prefetch_block(self, sched: Schedule,
                       inflight_visited: np.ndarray, state: Dict) -> None:
        """Overlap the NEXT block's staging with the in-flight block's
        dispatch: the cohort data gather/upload goes to the store's
        background thread unconditionally (arenas are immutable — no
        dependency on the running block), while the algorithm-state rows
        carry a true data dependency (the in-flight block's write-back
        may touch them) and are staged eagerly ONLY when the two blocks'
        planner-drawn visited sets are disjoint — detected host-side from
        ``Schedule.visited()``, no device readback. Overlapping sets fall
        back to the post-``finish_block`` sync path in ``_stage_state``.
        """
        visited = sched.visited()
        self.engine.prefetch_data(visited)
        if (not self._staged_store or "_host" not in state
                or not self._client_fields or inflight_visited is None):
            return
        if np.intersect1d(inflight_visited, visited).size:
            return      # rows the running block will write: wait for it
        stash = {f: stage_rows(state["_host"][f], visited)
                 for f in self._client_fields}
        # while the stash and the in-flight block's carries are both live,
        # residency momentarily holds two state buffers — remember the
        # in-flight one for dispatch_block's transient record
        self._transient_state_bytes = self._staged_state_bytes(state)
        state["_stash"] = {"visited": visited, "rows": stash}

    @property
    def _staged_store(self) -> bool:
        """True for the stores that stage per block (host RAM or disk) —
        the residency protocol treats them identically."""
        return self.fl.store in ("host", "stream")

    # -- the residency protocol (client virtualization, core.state) ------
    def _stage_state(self, state: Dict, visited: np.ndarray) -> None:
        """Host/stream store: upload the block's visited state rows as
        ``(V + 1, ...)`` cohort carries and publish the fleet→cohort
        rowmap that engines consume (``_resolve``, the fused engine's
        in-scan scatter ids). A matching ``prefetch_block`` stash (rows
        staged eagerly while the previous block ran — only possible when
        the visited sets were disjoint, so the values are identical to a
        fresh stage) is consumed instead of re-uploading."""
        if not self._staged_store or "_host" not in state:
            return
        stash = state.pop("_stash", None)
        state["_visited"] = visited
        state["_rowmap"] = rowmap_for(visited, self.fl.num_devices)
        if stash is not None and np.array_equal(stash["visited"], visited):
            for f in self._client_fields:
                state[f] = stash["rows"][f]
        else:
            for f in self._client_fields:
                state[f] = stage_rows(state["_host"][f], visited)

    def _unstage_state(self, state: Dict) -> None:
        """Scatter the block's trained cohort rows back into the host
        arena (one readback per field) and drop the staged carries."""
        if "_visited" not in state:
            return
        visited = state.pop("_visited")
        state.pop("_rowmap")
        for f in self._client_fields:
            state["_host"][f] = unstage_rows(state["_host"][f], visited,
                                             state.pop(f))

    def _staged_state_bytes(self, state: Dict) -> int:
        """Device-resident algorithm-state bytes during the current block
        (full (K + 1, ...) stacks under the device store, the staged
        (V + 1, ...) carries under the host store)."""
        return sum(tree_bytes(state[f])
                   for f in self._client_fields + self._shared_fields
                   if f in state)

    def _state_rows(self, state: Dict, ids: np.ndarray,
                    live: np.ndarray) -> np.ndarray:
        """Scatter targets of a round's state update: live lanes write
        their client row, dead lanes (scenario drops) the dump row —
        remapped to cohort-local rows when a host-store block is staged."""
        rows = np.where(live, ids, self.fl.num_devices).astype(np.int32)
        rowmap = state.get("_rowmap")
        if rowmap is not None:
            rows = rowmap[rows]
        return rows

    def plan_schedule(self, t0: int, n: int, rng: np.random.Generator,
                      state: Dict) -> Schedule:
        """``n`` rounds of plans, drawn in the per-round RNG order."""
        with trace.span("plan"):
            plans = tuple(self.plan_round(t0 + k, rng, state)
                          for k in range(n))
            totals: Dict[str, int] = {}
            for plan in plans:
                for channel, count in plan.comm:
                    totals[channel] = totals.get(channel, 0) + count
            return Schedule(plans=plans,
                            comm=tuple(sorted(totals.items())))

    def plan_round(self, t: int, rng: np.random.Generator,
                   state: Dict) -> RoundPlan:
        """Template step: the algorithm's pure plan (``_plan_round``),
        then — only when a scenario is active — the drop/slow/stale
        transform (``core.scenario``) plus rebuilt comm records, and
        finally the simulated-clock stamp. Scenario-off the transform
        never runs and never draws, so plans (and the RNG stream) are
        bit-identical to a scenario-free build.

        The adversary's transforms layer the same way: the config's robust
        reducer is stamped onto every AggSpec (``_mark_agg``) and a
        Byzantine adversary stamps ``lane_scale`` AFTER the scenario drops
        (an attacker that dropped this round uploads nothing). Both draw
        nothing — attack-off plans and RNG stream stay bit-identical."""
        plan = self._mark_agg(self._plan_round(t, rng, state))
        if self.scenario.active:
            plan, dropped = self.scenario.transform(plan, rng)
            plan = dataclasses.replace(
                plan, comm=self._scenario_comm(plan, dropped))
        if self.adversary.byzantine:
            plan = self.adversary.transform(plan)
        return dataclasses.replace(
            plan, sim_seconds=self.scenario.plan_seconds(plan))

    def _mark_agg(self, plan: RoundPlan) -> RoundPlan:
        """Stamp the config's robust reducer onto every AggSpec of the
        plan (the default ``weighted_mean`` touches nothing — bit-exact)."""
        fl = self.fl
        if fl.reducer == "weighted_mean":
            return plan
        groups = tuple(
            dataclasses.replace(
                g, agg=dataclasses.replace(
                    g.agg, reducer=fl.reducer, trim_frac=fl.trim_frac,
                    krum_f=fl.krum_f))
            if g.agg is not None else g
            for g in plan.groups)
        return dataclasses.replace(plan, groups=groups)

    def _plan_round(self, t: int, rng: np.random.Generator,
                    state: Dict) -> RoundPlan:
        raise NotImplementedError

    def _scenario_comm(self, plan: RoundPlan,
                       dropped: set) -> Tuple[Tuple[str, int], ...]:
        """Closed-form comm of the TRANSFORMED plan. Default = star
        semantics: the cloud broadcasts to every sampled client (a drop is
        only discovered when the upload never arrives), survivors upload."""
        if not plan.groups:
            return plan.comm
        grp = plan.groups[0]
        live = sum(1 for p in grp.hops[0].plans if p is not None)
        tpc = self._transfers_per_client
        return (("cloud_down", tpc * grp.lanes), ("cloud_up", tpc * live))

    def update_state(self, plan: RoundPlan, w_before: Pytree,
                     result: RoundResult, lr: float, state: Dict) -> None:
        pass

    # -- device-resident algorithm state (core.state) --------------------
    def ensure_state(self, state: Dict, w_glob: Pytree) -> None:
        """Initialize the algorithm's state carriers (needs the model
        shape, so it cannot happen at construction)."""

    def state_to_ckpt(self, state: Dict) -> Dict:
        """State carry -> the per-client-id dict layout of
        ``algo_state.msgpack`` (stable since PR 4)."""
        return dict(state)

    def state_from_ckpt(self, ck: Dict, w_glob: Pytree) -> Dict:
        """Inverse of ``state_to_ckpt`` over a restored checkpoint."""
        return dict(ck)

    # -- planning helpers ------------------------------------------------
    def _batch_plan(self, i: int, rng: np.random.Generator) -> np.ndarray:
        return plan_epoch_indices(self.clients[i], self.fl.batch_size,
                                  self.fl.local_epochs, rng)

    def _cohort_plans(self, ids: List[int],
                      rng: np.random.Generator) -> Tuple[np.ndarray, ...]:
        """One batch plan per cohort client, in ``ids`` order."""
        plans = tuple(self._batch_plan(i, rng) for i in ids)
        trace.count("plan_draws", len(plans))
        return plans

    def _sample(self, rng: np.random.Generator) -> List[int]:
        k = self.fl.num_devices
        n = max(1, int(round(k * self.fl.participation)))
        return sorted(rng.choice(k, size=n, replace=False).tolist())

    def _weights(self, ids: List[int]) -> np.ndarray:
        sizes = np.asarray([len(self.clients[i]) for i in ids], np.float64)
        return sizes / sizes.sum()

    def _ring_hops(self, rings: List[List[int]],
                   rng: np.random.Generator) -> Tuple[Hop, ...]:
        """The lap sequence of concurrent rings as (R * max-size) hops.

        Plans are drawn ring-by-ring, lap-by-lap — the sequential engine's
        visit order, so the RNG stream is engine-invariant. Hop j past a
        shorter ring's end repeats the ring's first device with a ``None``
        plan (all-invalid — the lane's model is carried unchanged): ONE
        implementation of the ring-tail rule for every engine."""
        fl = self.fl
        plans = {}
        for r, ring in enumerate(rings):
            for lap in range(fl.ring_rounds):
                for j, i in enumerate(ring):
                    plans[r, lap, j] = self._batch_plan(i, rng)
        trace.count("plan_draws", len(plans))
        width = max(len(r) for r in rings)
        return tuple(
            Hop(ids=tuple(ring[j] if j < len(ring) else ring[0]
                          for ring in rings),
                plans=tuple(plans[r, lap, j] if j < len(ring) else None
                            for r, ring in enumerate(rings)))
            for lap in range(fl.ring_rounds) for j in range(width)
        )


class FedAvg(_Planner):
    """McMahan et al. 2017 — the star baseline (paper Fig. 1): one cohort
    visit group, flat |D_i|/|D| aggregation."""

    def _plan_round(self, t, rng, state):
        ids = self._sample(rng)
        plans = self._cohort_plans(ids, rng)
        shared, stacked = self._extra_specs(ids, state)
        group = VisitGroup(
            hops=(Hop(tuple(ids), plans),), variant=self.variant,
            shared_extras=shared, stacked_extras=stacked,
            agg=AggSpec.flat(self._weights(ids)),
            keep_locals=self.keep_locals)
        n = self._transfers_per_client * len(ids)
        return RoundPlan(groups=(group,),
                         comm=(("cloud_down", n), ("cloud_up", n)))

    def _extra_specs(self, ids, state) -> Tuple[Dict, Dict]:
        """(shared, per-lane) extras of one cohort visit; values may use
        the GLOBAL/StateRef sentinels — engines resolve them at run
        time, so a whole Schedule can be planned up front."""
        return {}, {}


class FedProx(FedAvg):
    """Li et al. 2020 — proximal term mu/2 ||w - w_glob||^2."""
    variant = "prox"

    def _extra_specs(self, ids, state):
        return {"anchor": GLOBAL}, {}       # cohort-shared, broadcast in-jit


class Moon(FedAvg):
    """Li et al. 2021 — model-contrastive loss. state["prev"] is the
    (K + 1, ...) stack of previous local models (``core.state``); a client
    that has not trained yet contrasts against the current global model
    (``StateRef.fallback_global`` + the host ``seen`` mask)."""
    variant = "moon"
    keep_locals = True
    _client_fields = ("prev",)

    def _extra_specs(self, ids, state):
        return ({"w_glob": GLOBAL},
                {"w_prev": tuple(StateRef("prev", i, fallback_global=True)
                                 for i in ids)})

    def ensure_state(self, state, w_glob):
        if "seen" in state:
            return
        if self._staged_store:
            state["_host"] = {"prev": host_stack(w_glob,
                                                 self.fl.num_devices)}
        else:
            state["prev"] = client_stack(w_glob, self.fl.num_devices)
        state["seen"] = np.zeros(self.fl.num_devices + 1, bool)

    def update_state(self, plan, w_before, result, lr, state):
        grp = plan.groups[0]
        ids = np.asarray(grp.hops[0].ids, np.int32)
        # a lane that executed 0 steps (scenario drop) scatters to the
        # ghost dump row and stays unseen — its prev memory must not
        # become this round's untouched broadcast
        live = np.asarray(grp.lane_steps()) > 0
        rows = self._state_rows(state, ids, live)
        state["prev"] = scatter_rows(state["prev"], jnp.asarray(rows),
                                     tree_stack(result.locals_))
        state["seen"][ids[live]] = True

    def state_to_ckpt(self, state):
        stack = (state["_host"]["prev"] if "_host" in state
                 else state.get("prev"))
        if stack is None:
            return {}
        return {"prev": pack_client_rows(stack, state["seen"])}

    def state_from_ckpt(self, ck, w_glob):
        state: Dict = {}
        if ck.get("prev"):
            if self._staged_store:
                arena, state["seen"] = unpack_client_rows(
                    ck["prev"], w_glob, self.fl.num_devices, device=False)
                state["_host"] = {"prev": arena}
            else:
                state["prev"], state["seen"] = unpack_client_rows(
                    ck["prev"], w_glob, self.fl.num_devices)
        return state


class Scaffold(_Planner):
    """Karimireddy et al. 2020 — stochastic controlled averaging. The paper
    cites SCAFFOLD [11] as the canonical variance-reduction answer to client
    drift; included as an extra baseline beyond the paper's own table.

    state["c"] = server control variate; state["ci"] = the (K + 1, ...)
    client-variate stack (``core.state``; never-trained rows are the zeros
    the algorithm initializes c_i to). Option II update for c_i:
    c_i+ = c_i - c + (w_glob - w_i)/(K_i * lr).
    """
    variant = "scaffold"
    keep_locals = True
    _transfers_per_client = 2       # model + control variate each way
    _client_fields = ("ci",)
    _shared_fields = ("c",)

    def _plan_round(self, t, rng, state):
        ids = self._sample(rng)
        plans = self._cohort_plans(ids, rng)
        group = VisitGroup(
            hops=(Hop(tuple(ids), plans),), variant="scaffold",
            shared_extras={"c_glob": StateRef("c")},
            stacked_extras={"c_local": tuple(StateRef("ci", i)
                                             for i in ids)},
            agg=AggSpec.flat(self._weights(ids)), keep_locals=True)
        n = 2 * len(ids)                    # model + control variate
        return RoundPlan(groups=(group,),
                         comm=(("cloud_down", n), ("cloud_up", n)))

    def ensure_state(self, state, w_glob):
        if "c" in state:
            return
        state["c"] = tree_zeros_like(w_glob)
        if self._staged_store:
            state["_host"] = {"ci": host_stack(w_glob, self.fl.num_devices)}
        else:
            state["ci"] = client_stack(w_glob, self.fl.num_devices)
        state["seen"] = np.zeros(self.fl.num_devices + 1, bool)

    def update_state(self, plan, w_before, result, lr, state):
        grp = plan.groups[0]
        ids = np.asarray(grp.hops[0].ids, np.int32)
        steps = np.asarray(grp.lane_steps())
        # K_i * lr per lane, f32-rounded on the host — the fused block
        # scan ships the identical precomputed divisors, so chunked and
        # per-round stay bit-exact
        kl = np.asarray([max(k, 1) * lr for k in steps], np.float32)
        # 0-step lanes (scenario drops) scatter to the dump row and are
        # excluded from the server-variate mean and the |S|/K fraction
        live = steps > 0
        rows = self._state_rows(state, ids, live)
        n_live = int(live.sum())
        mw = np.where(live, np.float32(1.0 / n_live), np.float32(0.0))
        frac = np.float32(n_live / self.fl.num_devices)
        state["c"], state["ci"] = scaffold_step_compiled(
            state["c"], state["ci"], jnp.asarray(rows),
            tree_stack(result.locals_), w_before, jnp.asarray(kl),
            jnp.asarray(mw), frac)
        state["seen"][ids[live]] = True

    def state_to_ckpt(self, state):
        if "c" not in state:
            return {}
        stack = state["_host"]["ci"] if "_host" in state else state["ci"]
        return {"c": state["c"],
                "ci": pack_client_rows(stack, state["seen"])}

    def state_from_ckpt(self, ck, w_glob):
        state: Dict = {}
        if "c" in ck:
            state["c"] = jax.tree.map(jnp.asarray, ck["c"])
            if self._staged_store:
                arena, state["seen"] = unpack_client_rows(
                    ck.get("ci") or {}, w_glob, self.fl.num_devices,
                    device=False)
                state["_host"] = {"ci": arena}
            else:
                state["ci"], state["seen"] = unpack_client_rows(
                    ck.get("ci") or {}, w_glob, self.fl.num_devices)
        return state


class HierFAVG(_Planner):
    """Liu et al. 2020 — hierarchical FedAvg: R edge-level FedAvg iterations
    per cloud round (matched compute budget with FedSR: same R). Planned as
    R chained visit groups — iteration r's lanes are the (edge, device)
    pairs, seeded from iteration r-1's per-edge aggregates; only the final
    group collapses edge models into the cloud model."""

    def _plan_round(self, t, rng, state):
        fl = self.fl
        edge_ids, plans = [], {}
        for e, edge_devices in enumerate(self.edges):
            ids = sample_ring(edge_devices, rng,
                              participation=fl.participation, reshuffle=False)
            edge_ids.append(ids)
            for r in range(fl.ring_rounds):
                for i in ids:
                    plans[e, r, i] = self._batch_plan(i, rng)
        trace.count("plan_draws", len(plans))
        pairs = [(e, i) for e, ids in enumerate(edge_ids) for i in ids]
        lane_w, agg_groups, off = [], [], 0
        for ids in edge_ids:
            lane_w += self._weights(ids).tolist()
            agg_groups.append(tuple(range(off, off + len(ids))))
            off += len(ids)
        sizes = [sum(len(self.clients[i]) for i in ids) for ids in edge_ids]
        total = float(sum(sizes))
        groups = tuple(
            VisitGroup(
                hops=(Hop(tuple(i for _, i in pairs),
                          tuple(plans[e, r, i] for e, i in pairs)),),
                seed=None if r == 0 else tuple(e for e, _ in pairs),
                agg=AggSpec(
                    groups=tuple(agg_groups), lane_weights=tuple(lane_w),
                    group_weights=(tuple(s / total for s in sizes)
                                   if r == fl.ring_rounds - 1 else None)))
            for r in range(fl.ring_rounds)
        )
        comm = []
        for ids in edge_ids:
            comm += [("cloud_down", 1),
                     ("edge_down", fl.ring_rounds * len(ids)),
                     ("edge_up", fl.ring_rounds * len(ids)),
                     ("cloud_up", 1)]
        return RoundPlan(groups=groups, comm=tuple(comm))

    def _scenario_comm(self, plan, dropped):
        """Per edge: the cloud still broadcasts, the edge exchanges R
        iterations with its surviving devices, and only edges with any
        survivor upload back."""
        if not plan.groups:
            return plan.comm
        grp = plan.groups[0]
        R = self.fl.ring_rounds
        comm = []
        for lanes in grp.agg.groups:
            live = sum(1 for c in lanes if grp.hops[0].plans[c] is not None)
            comm.append(("cloud_down", 1))
            if live:
                comm += [("edge_down", R * live), ("edge_up", R * live),
                         ("cloud_up", 1)]
        return tuple(comm)


def _ring_scenario_comm(self, plan, dropped):
    """Comm of a transformed ring plan (shared by the FedSR and Ring
    planners — both emit one group whose lanes are rings): each ring still
    receives the broadcast, its survivors pass the model around a ring
    shrunk to them, and only lanes with any survivor upload."""
    if not plan.groups:
        return plan.comm
    grp = plan.groups[0]
    R = self.fl.ring_rounds
    p2p, live_lanes = 0, 0
    for c in range(grp.lanes):
        members = {hop.ids[c] for hop in grp.hops
                   if hop.plans[c] is not None}
        if members:
            live_lanes += 1
            p2p += ring_lap_hops(len(members), R)
    return (("cloud_down", grp.lanes), ("p2p", p2p),
            ("cloud_up", live_lanes))


class RingOptimization(_Planner):
    """Paper §III-B standalone baseline: ONE global ring over all sampled
    devices, R laps per round; no cloud aggregation inside the ring."""

    def _plan_round(self, t, rng, state):
        fl = self.fl
        ring = self._sample(rng)
        if fl.reshuffle_ring:
            rng.shuffle(ring)
        comm = (("cloud_down", 1),          # seed the first device
                ("p2p", ring_lap_hops(len(ring), fl.ring_rounds)),
                ("cloud_up", 1))            # readout
        groups = ()
        if fl.ring_rounds > 0:
            groups = (VisitGroup(hops=self._ring_hops([ring], rng),
                                 agg=AggSpec.flat([1.0])),)
        return RoundPlan(groups=groups, comm=comm)

    _scenario_comm = _ring_scenario_comm


class FedSR(_Planner):
    """Algorithm 1 — semi-decentralized star-ring.

    Each edge server rings its sampled devices (clusters of
    ``devices_per_edge``; with partial participation, clusters of the same
    size are formed from the sampled pool, Table IV style), runs
    ring-optimization for R laps, and the cloud aggregates the M edge models
    weighted by |D_m|/|D| (eq. 11). Planned as ONE visit group whose lanes
    are the rings — under the fused engine the whole round (broadcast,
    H-hop lap scan, weighted cloud reduce) is a single compiled dispatch."""

    def _plan_round(self, t, rng, state):
        fl = self.fl
        if fl.participation >= 1.0:
            rings = [sample_ring(e, rng, reshuffle=fl.reshuffle_ring)
                     for e in self.edges]
        else:
            rings = clusters_of(self._sample(rng), fl.devices_per_edge, rng)
        sizes = [sum(len(self.clients[i]) for i in r) for r in rings]
        total = float(sum(sizes))
        comm = (("cloud_down", len(rings)),  # w_glob -> edges
                ("p2p", sum(ring_lap_hops(len(r), fl.ring_rounds)
                            for r in rings)),
                ("cloud_up", len(rings)))    # edge models -> cloud
        groups = ()
        if fl.ring_rounds > 0:
            groups = (VisitGroup(
                hops=self._ring_hops(rings, rng),
                agg=AggSpec.flat([s / total for s in sizes])),)
        return RoundPlan(groups=groups, comm=comm)

    _scenario_comm = _ring_scenario_comm


class Centralized(_Planner):
    """Upper-bound reference: pooled-data SGD (paper's 'Centralized' rows).
    No schedule to plan — one visit of the pooled shard, no communication —
    so it bypasses the IR and trains directly. With no Schedule there is
    nothing to pre-plan or prefetch: ``pipelinable = False`` makes the
    executor fall back to the serial driver under ``prefetch=1`` (the two
    drivers are bit-identical for pooled SGD anyway)."""

    pipelinable = False

    def __init__(self, trainer, clients, fl):
        super().__init__(trainer, clients, fl)
        if fl.scenario.active or fl.adversary.active:
            raise ValueError(
                "algorithm='centralized' bypasses the RoundPlan IR — "
                "scenario and adversary transforms cannot apply to pooled "
                "SGD; disable them (scenario.frac=0, adversary.frac=0) "
                "for the centralized baseline")
        images = np.concatenate([c.images for c in clients])
        labels = np.concatenate([c.labels for c in clients])
        self.pool = ClientData(-1, images, labels)

    def run_round(self, w_glob, t, lr, rng, meter, state):
        w = self.trainer.train(w_glob, self.pool, lr=lr,
                               epochs=self.fl.local_epochs, rng=rng)
        if self.privacy is not None:
            self.privacy.record(self.trainer.last_steps)
        return w, state

    def run_schedule(self, w_glob, t0, lrs, rng, meter, state):
        # no plan to pre-draw: a block is just the per-round loop
        for k, lr in enumerate(lrs):
            w_glob, state = self.run_round(w_glob, t0 + k, float(lr), rng,
                                           meter, state)
        return w_glob, state


ALGORITHMS = {
    "fedavg": FedAvg,
    "fedprox": FedProx,
    "moon": Moon,
    "hieravg": HierFAVG,
    "ring": RingOptimization,
    "fedsr": FedSR,
    "scaffold": Scaffold,
    "centralized": Centralized,
}


def make_algorithm(name: str, trainer: LocalTrainer,
                   clients: List[ClientData], fl: FLConfig):
    return ALGORITHMS[name](trainer, clients, fl)
