"""MARL edge-association demo (paper Section IV): trains the MADDPG
controller in the DTWN environment and shows the learned policy beating the
random/average baselines on system latency (Eq. 17).

Training runs as ONE jitted lax.scan (repro.core.marl.train) — the whole
rollout-and-update loop is fused on device and only the metrics trace comes
back to the host. Pass --host-loop for the legacy step-by-step Python loop
(the seed behavior; ~10-30x slower, kept for comparison/debugging).

The controller policy is selectable: --policy factorized (default — shared
per-twin scoring head, parameter count independent of the twin count, so
--twins 10000 works) or --policy flat (the seed's O(N) monolithic MLP,
small-N oracle).

    PYTHONPATH=src python examples/marl_allocation.py --steps 200
    PYTHONPATH=src python examples/marl_allocation.py --twins 5000 --steps 300
"""
import argparse

import jax
import numpy as np

from repro.core.marl import (DDPGConfig, TrainConfig, act, actor_param_count,
                             compare_with_baselines, observe, train,
                             train_host_loop)
from repro.core.marl.env import EnvConfig, bs_frequencies
from repro.launch.runtime import device_info, setup_compile_cache


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--twins", type=int, default=30)
    ap.add_argument("--bs", type=int, default=5)
    ap.add_argument("--policy", choices=("factorized", "flat"),
                    default="factorized")
    ap.add_argument("--host-loop", action="store_true",
                    help="legacy un-fused Python training loop")
    ap.add_argument("--migration", type=float, default=0.0,
                    help="per-round twin move probability: trains the "
                         "controller against an association that drifts "
                         "under the Markov mobility + load-aware kernel "
                         "(repro.core.migration)")
    return ap.parse_args(argv)


def configs(args: argparse.Namespace) -> tuple:
    """``(EnvConfig, DDPGConfig, TrainConfig)`` of the run ``args`` ask
    for — the scan trainer's static arguments."""
    from repro.core.migration import MigrationConfig

    cfg = EnvConfig(n_twins=args.twins, n_bs=args.bs,
                    migration=(MigrationConfig(p_move=args.migration)
                               if args.migration > 0 else None))
    dcfg = DDPGConfig(policy=args.policy)
    tcfg = TrainConfig(steps=args.steps, warmup=min(48, args.steps // 2))
    return cfg, dcfg, tcfg


def main(argv=None):
    args = parse_args(argv)
    cache = setup_compile_cache()
    dev = device_info()
    print(f"device {dev['platform']} {dev['kind']} x{dev['count']}  "
          f"compile cache {cache}")
    cfg, dcfg, tcfg = configs(args)
    key = jax.random.PRNGKey(0)

    if args.host_loop:
        costs = []

        def on_step(i, info):
            costs.append(float(info["system_time"]))
            if i % 25 == 0:
                print(f"step {i:4d} system time {costs[-1]:8.2f}s "
                      f"(running mean {np.mean(costs[-25:]):.2f}s)")

        ts = train_host_loop(cfg, dcfg, tcfg, key, on_step=on_step)
    else:
        ts, trace = train(cfg, dcfg, tcfg, key)
        times = np.asarray(trace["system_time"])
        for i in range(0, args.steps, 25):
            print(f"step {i:4d} system time {times[i]:8.2f}s "
                  f"(running mean {times[max(0, i - 24):i + 1].mean():.2f}s)")
    st, agent = ts.env, ts.agent

    n_params = actor_param_count(
        jax.tree_util.tree_map(lambda x: x[0], agent.actor))
    print(f"\npolicy: {args.policy} ({n_params:,} actor params/agent at "
          f"N={args.twins})")

    # final comparison against baselines on the same frozen state
    a = act(cfg, agent, observe(cfg, st), policy=args.policy)
    cmp_ = compare_with_baselines(cfg, st, a)
    print(f"final round latency:  MARL {float(cmp_['marl']):.2f}s | "
          f"average {float(cmp_['average']):.2f}s | "
          f"random {float(cmp_['random']):.2f}s")
    ghz = [round(float(f) / 1e9, 2) for f in bs_frequencies(cfg)]
    print(f"association histogram: "
          f"{np.bincount(np.asarray(cmp_['assoc']), minlength=cfg.n_bs).tolist()} "
          f"(BS freqs {ghz} GHz)")


if __name__ == "__main__":
    main()
