"""Fused engine: a whole visit group — or a whole block of rounds — as ONE
compiled dispatch.

The batched schedule against a device-resident data plane
(``DeviceDataPlane``): client shards upload once per experiment, a visit
group's hops stack along a leading (H, C, S, B) axis of int32 index plans
(``stack_plan_indices``) — the entire per-round H2D payload — and
``LocalTrainer.train_many_fused`` runs broadcast -> H-hop ring scan ->
in-jit weighted reduce as a single compiled call. A FedSR round (M rings,
R laps, cloud aggregation, eq. 11) is therefore literally one dispatch;
star cohorts are the H=1 special case. ``FLConfig.mesh_data_axis``
composes: the plane's flat sample axis and the lane axis both shard over
the sim mesh.

``run_schedule`` lifts the same trick one level up the Schedule IR: the
plans of an eval-to-eval block stack along a leading round axis (ghost
lanes / invalid hops / invalid steps pad rounds whose participation drew
different shapes) and ``LocalTrainer.train_schedule`` scans the block with
``(w_glob, algo_state)`` as the carry — so a block of ``eval_every`` FedSR
rounds, or a HierFAVG round's R chained edge iterations (times n rounds),
is ONE compiled dispatch instead of one per round (or per iteration).
"""
from __future__ import annotations

import dataclasses

import numpy as np

from repro.core.engines.batched import BatchedEngine
from repro.core.plan import Schedule, VisitGroup
from repro.data.pipeline import DeviceDataPlane, stack_plan_indices
from repro.data.store import make_store
from repro.utils import trace


class FusedEngine(BatchedEngine):

    def __init__(self, trainer, clients, fl):
        super().__init__(trainer, clients, fl)
        # where the fleet lives between blocks is the store's policy
        # (FLConfig.store): upload-once fleet plane, or per-block cohort
        # arenas that keep peak device bytes O(cohort) — see data.store
        self.store = make_store(fl.store, clients, mesh=self.mesh,
                                data_axis=self.data_axis)
        self._arena: DeviceDataPlane = None

    @property
    def plane(self) -> DeviceDataPlane:
        """The data plane serving the CURRENT block — staged by
        ``stage_data`` at the block boundary; before any staging (direct
        ``run`` calls in unit tests) the store serves the whole fleet."""
        if self._arena is None:
            self._arena = self.store.arena(None)
        return self._arena

    def stage_data(self, visited) -> int:
        """Block boundary of the residency protocol: ask the store for
        the arena covering ``visited`` and report its resident bytes.
        The device store returns the same fleet plane every block (0
        re-upload); the host/stream stores upload the cohort slice — real
        H2D traffic, so it lands on the trainer's meter (the device
        store's one-time fleet upload stays accounted in ``plane.nbytes``,
        as before). A matching ``prefetch_data`` makes this call consume
        the background-staged arena instead of gathering synchronously."""
        if visited is not None and len(visited) == 0:
            return 0        # ring_rounds=0: the block gathers nothing
        fresh = self.store.arena_nbytes(visited)
        if self.store.kind in ("host", "stream"):
            self.trainer.h2d_bytes += fresh
        self._arena = self.store.arena(visited)
        return self._arena.nbytes

    def prefetch_data(self, visited) -> None:
        """Hand the next block's cohort gather + upload to the store's
        staging thread (``ClientStore.prefetch``) while the current
        block's dispatch is still in flight."""
        if visited is not None and len(visited) == 0:
            return          # ring_rounds=0: nothing to stage
        self.store.prefetch(visited)

    def stage_pair_nbytes(self) -> int:
        return self.store.last_pair_nbytes

    def staging_stats(self):
        return self.store.stage_seconds, self.store.overlapped_stage_seconds

    def _run_group(self, grp: VisitGroup, w_glob, prev, lr, state):
        padded = self._pad(grp.lanes)
        kw = dict(lr=lr, variant=grp.variant, mesh=self.mesh,
                  data_axis=self.data_axis,
                  **self._extras_kwargs(grp, w_glob, padded, state))
        has_agg = grp.agg is not None
        red_kw = grp.agg.reduce_kwargs(padded) if has_agg else {}
        # the whole hop sequence is one dispatch whose params input IS the
        # lane seed, so the Byzantine transform never needs an explicit ref
        red_kw["dscale"] = self._dscale(grp, padded)
        keep = grp.keep_locals
        # every hop pads to the group-global max step count S so the hop
        # axis stacks uniformly (H, C, S, B); B is group-wide too, since a
        # scenario drop can empty a whole hop of real plans
        S = max(p.shape[0] for hop in grp.hops for p in hop.plans
                if p is not None)
        B = next(p.shape[1] for hop in grp.hops for p in hop.plans
                 if p is not None)
        rows, idx, valid = zip(*(
            stack_plan_indices(list(hop.plans), list(hop.ids),
                               pad_to=padded, steps=S, width=B)
            for hop in grp.hops))
        if grp.seed is None:
            params, broadcast = w_glob, True
        else:
            # seeded edge iteration (HierFAVG): a FRESH gathered stack per
            # group — train_many_fused donates the non-broadcast params
            params, broadcast = self._seed_stack(prev, grp.seed, padded), False
        out = self.trainer.train_many_fused(
            params, self.plane, np.stack(rows), np.stack(idx),
            np.stack(valid), broadcast=broadcast,
            keep_locals=keep, **red_kw, **kw)
        return self._unpack(out, has_agg, keep)

    # -- the Schedule block dispatch ------------------------------------
    def run_schedule(self, sched: Schedule, w_glob, lrs, state, update_fn):
        plans = sched.plans
        if not plans or not plans[0].groups:
            return w_glob       # ring_rounds=0: rounds leave w unchanged
        hier = len(plans[0].groups) > 1
        variant = plans[0].groups[0].variant
        with trace.span("pack"):
            xs = (self._stack_hier_schedule(plans, lrs) if hier
                  else self._stack_cohort_schedule(plans, lrs, variant,
                                                   state))
        carry = {}
        if variant == "moon":
            carry = {"prev": state["prev"]}
        elif variant == "scaffold":
            carry = {"c": state["c"], "ci": state["ci"]}
        agg0 = plans[0].groups[-1].agg
        w_glob, carry = self.trainer.train_schedule(
            w_glob, self.plane, xs, carry, variant=variant, hier=hier,
            reducer=agg0.reducer, trim_frac=agg0.trim_frac,
            krum_f=agg0.krum_f, mesh=self.mesh, data_axis=self.data_axis)
        if variant in ("moon", "scaffold"):
            state.update(carry)
            # participation is planner-drawn, so the seen mask advances
            # host-side — no device readback; 0-step lanes (scenario
            # drops) stay unseen, matching the per-round driver
            for plan in plans:
                g = plan.groups[0]
                ids = np.asarray(g.hops[0].ids)
                live = np.asarray(g.lane_steps()) > 0
                state["seen"][ids[live]] = True
        return w_glob

    def _schedule_dims(self, groups):
        """(lane pad, hop pad, step pad, batch width) over a block's
        groups — ghost lanes / all-invalid hops / invalid steps make the
        per-round shapes stack along one uniform round axis."""
        Cp = self._pad(max(g.lanes for g in groups))
        H = max(len(g.hops) for g in groups)
        S = max(p.shape[0] for g in groups for hop in g.hops
                for p in hop.plans if p is not None)
        B = next(p.shape[1] for g in groups for hop in g.hops
                 for p in hop.plans if p is not None)
        return Cp, H, S, B

    @staticmethod
    def _add_dscale(xs, groups, Cp: int) -> None:
        """Stack the adversary's per-lane delta factors as a (n, Cp) xs
        lane when any round of the block is attacked (honest rounds and
        ghost lanes carry 1.0); honest blocks ship nothing and compile
        the dscale-free body."""
        if all(g.lane_scale is None for g in groups):
            return
        ds = np.ones((len(groups), Cp), np.float32)
        for r, g in enumerate(groups):
            if g.lane_scale is not None:
                ds[r, :g.lanes] = g.lane_scale
        xs["dscale"] = ds

    def _stack_cohort_schedule(self, plans, lrs, variant, state):
        """Stack a block of single-group plans along the round axis, plus
        the variant's state-carry lanes (``core.state``): per-lane client
        ids (ghosts -> the dump row K), MOON's host-precomputed
        prev-vs-global masks, SCAFFOLD's f32-rounded K_i*lr divisors and
        masked mean weights."""
        K = self.fl.num_devices
        groups = [p.groups[0] for p in plans]
        n = len(groups)
        Cp, H, S, B = self._schedule_dims(groups)
        robust = groups[0].agg.reducer != "weighted_mean"
        rows = np.zeros((n, H, Cp), np.int32)
        idx = np.zeros((n, H, Cp, S, B), np.int32)
        valid = np.zeros((n, H, Cp, S), bool)
        aggv = np.zeros((n, Cp), np.float32)
        ids = np.full((n, Cp), K, np.int32)
        if robust:
            # robust reduce operands: the UNCOLLAPSED (G, Cp) lane-weight
            # matrix (validity pattern) + (G,) group weights, padded to the
            # block's max group count with zero rows (m=0 lanes contribute
            # a zero row at group weight 0 — see core.robust)
            Gm = max(len(g.agg.groups) for g in groups)
            aggw = np.zeros((n, Gm, Cp), np.float32)
            aggg = np.zeros((n, Gm), np.float32)
        for r, g in enumerate(groups):
            for h, hop in enumerate(g.hops):
                rw, ix, vl = stack_plan_indices(
                    list(hop.plans), list(hop.ids), pad_to=Cp, steps=S,
                    width=B)
                rows[r, h], idx[r, h], valid[r, h] = rw, ix, vl
            # hops past len(g.hops) stay all-invalid: every lane carried
            # unchanged, exactly the ring-tail rule
            if robust:
                G_r = len(g.agg.groups)
                aggw[r, :G_r] = dataclasses.replace(
                    g.agg, group_weights=None).matrix(Cp)
                aggg[r, :G_r] = np.asarray(g.agg.group_weights, np.float32)
            else:
                aggv[r] = g.agg.matrix(Cp)
            # 0-step lanes (scenario drops) point at the dump row K so the
            # in-scan state scatter discards them — same rule as ghosts
            live = np.asarray(g.lane_steps()) > 0
            ids[r, :g.lanes] = np.where(live, np.asarray(g.hops[0].ids), K)
        rowmap = state.get("_rowmap") if isinstance(state, dict) else None
        if rowmap is not None:
            # host store: the state carry is a staged (V + 1, ...) cohort
            # stack — remap fleet ids (and the fleet dump K) through the
            # block's fleet→cohort table so the in-scan gather/scatter
            # lands on cohort rows (dump K -> staged dump V)
            ids = rowmap[ids]
        xs = {"rows": rows, "plans": idx, "valid": valid,
              "lr": np.asarray(lrs, np.float32)}
        if robust:
            xs.update(aggw=aggw, aggg=aggg)
        else:
            xs["aggv"] = aggv
        self._add_dscale(xs, groups, Cp)
        if variant == "moon":
            seen = np.asarray(state["seen"]).copy()
            use_prev = np.zeros((n, Cp), bool)
            for r, g in enumerate(groups):
                lane_ids = np.asarray(g.hops[0].ids)
                live = np.asarray(g.lane_steps()) > 0
                use_prev[r, :g.lanes] = seen[lane_ids]
                seen[lane_ids[live]] = True
            xs.update(ids=ids, use_prev=use_prev)
        elif variant == "scaffold":
            kl = np.ones((n, Cp), np.float32)
            mw = np.zeros((n, Cp), np.float32)
            frac = np.zeros(n, np.float32)
            for r, g in enumerate(groups):
                steps = np.asarray(g.lane_steps())
                live = steps > 0
                n_live = int(live.sum())
                kl[r, :g.lanes] = np.asarray(
                    [max(k, 1) * float(lrs[r]) for k in steps], np.float32)
                mw[r, :g.lanes] = np.where(live, np.float32(1.0 / n_live),
                                           np.float32(0.0))
                frac[r] = np.float32(n_live / K)
            xs.update(ids=ids, kl=kl, mw=mw, frac=frac)
        return xs

    def _stack_hier_schedule(self, plans, lrs):
        """Stack a block of HierFAVG plans: each round's R chained edge
        iterations become an iteration axis inside the round axis. The
        per-iteration (G, C) edge reduce (``wg``) seeds the next
        iteration's lanes inside the scan; the final iteration applies the
        collapsed cloud vector (``aggv``) exactly as the per-round engine
        would."""
        n = len(plans)
        R = len(plans[0].groups)
        groups = [g for p in plans for g in p.groups]
        Cp, _, S, B = self._schedule_dims(groups)
        G = len(plans[0].groups[0].agg.groups)
        robust = plans[0].groups[-1].agg.reducer != "weighted_mean"
        rows = np.zeros((n, R, Cp), np.int32)
        idx = np.zeros((n, R, Cp, S, B), np.int32)
        valid = np.zeros((n, R, Cp, S), bool)
        wg = np.zeros((n, G, Cp), np.float32)
        seed = np.zeros((n, Cp), np.int32)
        aggv = np.zeros((n, Cp), np.float32)
        gwv = np.zeros((n, G), np.float32)
        for r, plan in enumerate(plans):
            for it, g in enumerate(plan.groups):
                (hop,) = g.hops
                rows[r, it], idx[r, it], valid[r, it] = stack_plan_indices(
                    list(hop.plans), list(hop.ids), pad_to=Cp, steps=S,
                    width=B)
            first, last = plan.groups[0], plan.groups[-1]
            # the un-collapsed (G, C) per-edge reduce, applied after every
            # iteration but the last (ghost lanes weigh 0 in every row)
            wg[r] = dataclasses.replace(
                first.agg, group_weights=None).matrix(Cp)
            if robust:
                # robust final reduce reuses wg's validity pattern; only
                # the (G,) cloud weights ship separately
                gwv[r] = np.asarray(last.agg.group_weights, np.float32)
            else:
                aggv[r] = last.agg.matrix(Cp)
            if R > 1:
                seed[r, :last.lanes] = last.seed
            # ghost lanes seed from row 0 (weight 0, never trained) — same
            # rule as _seed_stack
        xs = {"rows": rows, "plans": idx, "valid": valid,
              "lr": np.asarray(lrs, np.float32), "wg": wg, "seed": seed}
        if robust:
            xs["gwv"] = gwv
        else:
            xs["aggv"] = aggv
        self._add_dscale(xs, [p.groups[0] for p in plans], Cp)
        return xs
