"""Benchmark driver — one function per paper table + kernel micro-benches +
the roofline report. Prints ``name,us_per_call,derived`` CSV.

  PYTHONPATH=src python -m benchmarks.run [--rounds N] [--quick] [--full]
  PYTHONPATH=src python -m benchmarks.run --only table1,kernels

FL rows: us_per_call = wall time per FL round; derived = final accuracy (or
transfers-to-target for Table III). Kernel rows: us_per_call = per-call
time of the jitted reference op on this host. Roofline rows: us_per_call =
projected TPU v5e step time from the dry-run; derived = dominant term.
"""
from __future__ import annotations

import argparse
import sys

from repro.utils.compile_cache import use_compile_cache


def _emit(name: str, us: float, derived) -> None:
    print(f"{name},{us:.1f},{derived}")


def run_fl_tables(rounds: int, only: set) -> None:
    from benchmarks import fl_tables

    if "table1" in only:
        for r in fl_tables.table1_ring_vs_fedavg(rounds=rounds):
            _emit(
                f"table1/{r['task']}/{r['partition']}/{r['algorithm']}",
                r["seconds"] / rounds * 1e6,
                f"acc={r['accuracy']:.4f}",
            )
    if "table2" in only:
        for r in fl_tables.table2_accuracy(rounds=rounds):
            _emit(
                f"table2/{r['task']}/{r['partition']}/{r['algorithm']}",
                r["seconds"] / rounds * 1e6,
                f"acc={r['accuracy']:.4f}",
            )
    if "table3" in only:
        for r in fl_tables.table3_comm_cost(rounds=max(rounds, 12)):
            _emit(
                f"table3/comm/{r['algorithm']}",
                r["seconds"] / max(rounds, 12) * 1e6,
                f"transfers_to_{r['target']:.0%}={r['transfers_to_target']}"
                f";cloud={r['cloud_transfers_total']}"
                f";acc={r['final_accuracy']:.4f}",
            )
    if "table4" in only:
        for r in fl_tables.table4_scalability(rounds=max(rounds // 2, 4)):
            _emit(
                f"table4/scale100/frac{r['participation']}/{r['algorithm']}",
                r["seconds"] / max(rounds // 2, 4) * 1e6,
                f"acc={r['accuracy']:.4f}",
            )
    if "attacks" in only:
        # the sign-flip gap needs ~16+ rounds to open (see the grid's
        # docstring); don't let --rounds starve the ordering claim
        atk_rounds = max(rounds, 20)
        for r in fl_tables.attack_defense_grid(rounds=atk_rounds):
            derived = f"acc={r['accuracy']:.4f}"
            if r.get("dp_epsilon") is not None:
                derived += (f";eps={r['dp_epsilon']:.2f}"
                            f";delta={r['dp_delta']:.0e}")
            _emit(
                f"attack/{r['attack']}/{r['defense']}/{r['algorithm']}",
                r["seconds"] / atk_rounds * 1e6,
                derived,
            )
    if "personalize" in only:
        for r in fl_tables.personalize_table(rounds=rounds):
            _emit(
                f"personalize/alpha{r['alpha']}/{r['mode']}/{r['algorithm']}",
                r["seconds"] / rounds * 1e6,
                f"acc_personalized={r['acc_personalized']:.4f}"
                f";acc_global={r['acc_global']:.4f}"
                f";lift={r['lift']:+.4f}",
            )
    if "scenarios" in only:
        for r in fl_tables.scenario_curves(rounds=rounds):
            _emit(
                f"scenario/{r['scenario']}/{r['algorithm']}/r{r['round']}",
                r["seconds"] / rounds * 1e6,
                f"acc={r['accuracy']:.4f}"
                f";transfers={r['total_transfers']}"
                f";sim_s={r['sim_seconds']:.2f}",
            )


def run_kernels() -> None:
    from benchmarks.kernel_bench import ALL

    for bench in ALL:
        name, us, derived = bench()
        _emit(f"kernel/{name}", us, derived)


def run_roofline() -> None:
    from benchmarks.roofline_report import load_records, primary_step

    recs = load_records()
    if not recs:
        print("# roofline: no dry-run records found "
              "(run: python -m repro.launch.dryrun)", file=sys.stderr)
        return
    for rec in recs:
        if rec.get("status") != "ok" or rec["mesh"] != "16x16":
            continue
        ps = primary_step(rec)
        if not ps:
            continue
        name, step = ps
        r = step["roofline"]
        _emit(
            f"roofline/{rec['arch']}/{rec['shape']}/{name}",
            r["step_time_s"] * 1e6,
            f"dominant={r['dominant']};useful={r['useful_ratio']:.2f}",
        )


def run_smoke() -> None:
    """Seconds-fast CI path (--smoke): exercises every entrypoint wiring —
    one kernel micro-bench, the engine A/Bs (batched/sharded/fused, the
    one-dispatch round and the chunked schedule block) at reduced size, and
    one tiny FL round per engine — so the benchmark drivers can't silently
    rot. Invoked from tier-1 (tests/test_benchmarks_smoke.py)."""
    from benchmarks.kernel_bench import (
        bench_attack_fedsr_median, bench_fedsr_onedispatch, bench_fl_engines,
        bench_fl_engines_fused, bench_fl_engines_sharded,
        bench_fl_schedule_chunked, bench_fleet_scale_hoststore,
        bench_fused_sgd, bench_pipeline_fedsr_hoststore,
        bench_ring_round_fedsr, bench_serve_fleet_mlp64,
    )

    name, us, derived = bench_fused_sgd()
    _emit(f"kernel/{name}", us, derived)
    name, us, derived = bench_fl_engines(num_devices=8, iters=1)
    _emit(f"kernel/{name}", us, derived)
    name, us, derived = bench_fl_engines_sharded(num_devices=8, iters=1)
    _emit(f"kernel/{name}", us, derived)
    name, us, derived = bench_fl_engines_fused(num_devices=8, iters=1)
    _emit(f"kernel/{name}", us, derived)
    name, us, derived = bench_ring_round_fedsr(num_devices=8, ring_rounds=2,
                                               num_edges=2, iters=1)
    _emit(f"kernel/{name}", us, derived)
    name, us, derived = bench_fedsr_onedispatch(num_devices=8, ring_rounds=2,
                                                num_edges=2, iters=1)
    _emit(f"kernel/{name}", us, derived)
    name, us, derived = bench_fl_schedule_chunked(num_devices=8,
                                                  ring_rounds=2, num_edges=2,
                                                  block=4, iters=1)
    _emit(f"kernel/{name}", us, derived)
    # the PR-7 acceptance row at reduced K: host-store peak device bytes
    # must stay O(cohort) while the device store's grow with the fleet
    name, us, derived = bench_fleet_scale_hoststore(fleet_sizes=(256, 2048),
                                                    cohort=8, rounds=2)
    _emit(f"kernel/{name}", us, derived)
    # the PR-9 acceptance row at reduced K: prefetch=0 vs 1 on the host
    # store — the pipeline wiring check (overlap fraction and the 2x
    # residency bound already show at this size; headline numbers are the
    # full K=2048 row's)
    name, us, derived = bench_pipeline_fedsr_hoststore(num_devices=256,
                                                       cohort=8, rounds=4)
    _emit(f"kernel/{name}", us, derived)
    # the PR-8 acceptance row at reduced K: weighted_mean vs median under
    # a 20% delta-amplifying fleet — the adversary + robust-reduce wiring
    # check (acc_median > acc_wmean already shows at this size; the
    # headline numbers are the full-size row's)
    name, us, derived = bench_attack_fedsr_median(num_devices=16, rounds=4)
    _emit(f"kernel/{name}", us, derived)
    # the PR-10 acceptance row at reduced K: stacked one-dispatch
    # personalized serving vs the per-model loop over the same fleet
    # arena — the routing + dispatch-collapse wiring check (the >= 5x
    # speedup already shows at this size; headline numbers are the full
    # K=1024 row's)
    name, us, derived = bench_serve_fleet_mlp64(fleet=64, requests=32,
                                                iters=2)
    _emit(f"kernel/{name}", us, derived)

    from repro.configs import get_config
    from repro.configs.base import FLConfig
    from repro.core.executor import run_experiment
    from repro.data.synthetic import make_task

    train, test = make_task("mnist_like", train_per_class=16,
                            test_per_class=4, seed=0)
    for engine in ("sequential", "batched", "sharded", "fused"):
        fl = FLConfig(algorithm="fedavg", num_devices=4, num_edges=2,
                      rounds=1, local_epochs=1, batch_size=16, engine=engine)
        res = run_experiment(task="mnist_like", model_cfg=get_config("fedsr-mlp"),
                             fl=fl, train=train, test=test)
        _emit(f"smoke/fedavg_round/{engine}",
              res.history[-1].seconds * 1e6, f"acc={res.final_accuracy:.3f}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=10,
                    help="FL rounds per benchmark run")
    ap.add_argument("--only",
                    default="table1,table2,table3,table4,scenarios,attacks,"
                            "personalize,kernels,roofline",
                    help="comma-separated subset")
    ap.add_argument("--quick", action="store_true",
                    help="tables 1+3 + kernels + roofline only, fewer rounds")
    ap.add_argument("--smoke", action="store_true",
                    help="seconds-fast wiring check (used by tier-1 tests)")
    args = ap.parse_args()
    use_compile_cache()

    only = set(args.only.split(","))
    rounds = args.rounds
    if args.quick:
        only &= {"table1", "table3", "kernels", "roofline"}
        rounds = min(rounds, 6)

    print("name,us_per_call,derived")
    if args.smoke:
        run_smoke()
        return
    if "kernels" in only:
        run_kernels()
    if "roofline" in only:
        run_roofline()
    if only & {"table1", "table2", "table3", "table4", "scenarios",
               "attacks", "personalize"}:
        run_fl_tables(rounds, only)


if __name__ == "__main__":
    main()
