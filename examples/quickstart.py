"""Quickstart: FedSR vs FedAvg on a non-IID synthetic image task.

    PYTHONPATH=src python examples/quickstart.py [--store host] [--prefetch 1]
    PYTHONPATH=src python examples/quickstart.py --attack sign_flip \\
        --defense median

Runs ~1 minute on CPU. Demonstrates the paper's two claims:
(1) FedSR tolerates pathological label skew far better than FedAvg;
(2) FedSR's cloud only talks to M edge servers, not K devices.

``--store host`` keeps client shards host-resident and stages only each
round's cohort onto the device (bit-identical results; see README
"Client stores & fleet scale") — the peak-device-bytes line shows what
that buys at scale. ``--store stream`` goes further: shards live in
disk-backed memmaps and host RAM is O(cohort) too.

``--prefetch 1`` turns on the block pipeline (README "Pipelined
execution"): the next block's cohort is planned and staged in the
background while the current dispatch is in flight — bit-identical
results, and the overlap line shows how much staging wall it hid.

``--attack`` turns 20% of the fleet malicious (``sign_flip`` /
``label_flip`` / ``scale`` Byzantine lanes, README "Adversaries, robust
aggregation & privacy"); pair with ``--defense median`` (or
``trimmed_mean`` / ``krum``) to watch a robust reducer recover the
accuracy the default weighted mean loses. FedSR runs rings of 2 under
attack so the attacked-lane fraction stays below one half — the regime
the order-statistic reducers defend.

``--personalize full`` (or ``head``) adds the post-global
personalization stage (README "Personalization & fleet serving"): after
the last round every client fine-tunes the final global model on its own
shard — a whole block of clients as ONE vmapped dispatch — and the
per-client accuracy of the personalized fleet is reported next to the
global model's on the same label-matched test draws. ``head`` fine-tunes
only the classifier head (body gradients masked to zero).
"""
import argparse
import sys

sys.path.insert(0, "src")

from repro.configs import get_config
from repro.configs.base import AdversaryConfig, FLConfig, PersonalizeConfig
from repro.core.executor import run_experiment
from repro.utils.compile_cache import use_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--store", default="device",
                    choices=("device", "host", "stream"),
                    help="client shard residency (FLConfig.store)")
    ap.add_argument("--prefetch", default=0, type=int, choices=(0, 1),
                    help="1 = pipeline: stage the next block's cohort "
                         "while the current dispatch is in flight")
    ap.add_argument("--engine", default="sequential",
                    help="round engine: sequential|batched|sharded|fused")
    ap.add_argument("--attack", default="none",
                    choices=("none", "sign_flip", "label_flip", "scale"),
                    help="turn 20%% of the fleet malicious")
    ap.add_argument("--defense", default="weighted_mean",
                    choices=("weighted_mean", "median", "trimmed_mean",
                             "krum"),
                    help="aggregation rule (FLConfig.reducer)")
    ap.add_argument("--personalize", default="none",
                    choices=("none", "full", "head"),
                    help="post-global per-client fine-tune stage "
                         "(FLConfig.personalize.mode)")
    args = ap.parse_args()
    use_compile_cache()
    cfg = get_config("fedsr-mlp")
    adv = (AdversaryConfig() if args.attack == "none"
           else AdversaryConfig(frac=0.2, kind=args.attack))
    pers = (PersonalizeConfig() if args.personalize == "none"
            else PersonalizeConfig(epochs=3, lr=0.02,
                                   mode=args.personalize))
    # rings of 2 under attack: one Byzantine device poisons its whole
    # ring lap, so wide rings would hand the attackers a lane majority
    num_edges = 10 if adv.active else 5
    print("== FedSR quickstart: 20 devices, "
          f"{num_edges} edge servers, pathological non-IID (xi=2), "
          f"store={args.store}, attack={args.attack}, "
          f"defense={args.defense} ==")
    for algo, local_e, ring_r in [("fedavg", 5, 1), ("fedsr", 1, 5)]:
        fl = FLConfig(
            algorithm=algo, num_devices=20, num_edges=num_edges, rounds=10,
            partition="pathological", xi=2,
            local_epochs=local_e, ring_rounds=ring_r,
            engine=args.engine, store=args.store, prefetch=args.prefetch,
            adversary=adv, reducer=args.defense, krum_f=4,
            personalize=pers,
        )
        res = run_experiment(task="mnist_like", model_cfg=cfg, fl=fl,
                             eval_every=5, quiet=False)
        comm = res.history[-1].comm
        peak_acc = max(rec.accuracy for rec in res.history)
        overlap = (f" | staging {res.stage_seconds * 1e3:.0f}ms "
                   f"({res.overlap_fraction:.0%} overlapped)"
                   if res.stage_seconds > 0 else "")
        pers_line = ""
        if res.personalized_accuracy is not None:
            lift = res.personalized_accuracy - res.global_client_accuracy
            pers_line = (f"    personalized fleet: per-client acc "
                         f"{res.personalized_accuracy:.4f} vs global "
                         f"{res.global_client_accuracy:.4f} "
                         f"(lift {lift:+.4f}, mode={args.personalize})\n")
        print(f"--> {algo:8s} final acc {res.final_accuracy:.4f} "
              f"(peak {peak_acc:.4f}) | "
              f"cloud transfers {comm['cloud_transfers']} | "
              f"P2P transfers {comm['p2p_transfers']} | "
              f"peak device bytes {res.peak_device_bytes}{overlap}\n"
              f"{pers_line}")


if __name__ == "__main__":
    main()
