"""Always-on DTWN serving CLI: stream rounds over a live twin population.

Runs the :mod:`repro.core.serve` loop — device-resident donated state,
population churn, pipelined round dispatch — and reports throughput
(rounds/s) plus streamed round metrics. On a multi-device backend the twin
axis is sharded over every device via ``core/sharding.py``; ``--shards``
forces that many CPU host devices for a rehearsal without a chip.

Examples:
  PYTHONPATH=src python -m repro.launch.serve_dtwn --capacity 1000 \
      --rounds 50 --join 0.02 --leave 0.02 --faults --migration
  PYTHONPATH=src python -m repro.launch.serve_dtwn --capacity 100000 \
      --rounds 20 --join 0.01 --leave 0.01 --no-overlap
  PYTHONPATH=src python -m repro.launch.serve_dtwn --capacity 64 \
      --rounds 30 --policy factorized --consensus --shards 8
  PYTHONPATH=src python -m repro.launch.serve_dtwn --capacity 10000 \
      --rounds 20 --fl --fl-model tiny --join 0.01 --leave 0.01
"""
import argparse
import os
import sys
import time
from typing import Any, Callable, NamedTuple, Optional


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--capacity", type=int, default=1000,
                    help="twin-buffer capacity (= EnvConfig.n_twins)")
    ap.add_argument("--rounds", type=int, default=50)
    ap.add_argument("--live", type=int, default=0,
                    help="initial live population (default: capacity)")
    ap.add_argument("--n-bs", type=int, default=10)
    ap.add_argument("--join", type=float, default=0.0,
                    help="per-round per-empty-slot admission probability")
    ap.add_argument("--leave", type=float, default=0.0,
                    help="per-round per-live-twin departure probability")
    ap.add_argument("--migration", action="store_true",
                    help="enable the between-round migration kernel")
    ap.add_argument("--faults", action="store_true",
                    help="enable straggler/outage injection")
    ap.add_argument("--consensus", action="store_true",
                    help="enable the PBFT chain workload")
    ap.add_argument("--policy", default=None,
                    help="MARL policy protocol for association "
                         "(e.g. factorized); default streams round-robin")
    ap.add_argument("--evolve", action="store_true",
                    help="advance channel/frequency dynamics each round")
    ap.add_argument("--fl", action="store_true",
                    help="stream the real FL workload through the round "
                         "step (per-twin model buffers + Eq. 4/5 on device)")
    ap.add_argument("--fl-model", default="tiny",
                    help="model to train: tiny (N=10^4+ scale) or cnn")
    ap.add_argument("--fl-participants", type=int, default=10,
                    help="twins trained per round")
    ap.add_argument("--fl-iters", type=int, default=5,
                    help="local SGD iterations per participant per round")
    ap.add_argument("--fl-batch", type=int, default=8)
    ap.add_argument("--fl-aggregator", default="fedavg",
                    help="fedavg | trimmed_mean | krum")
    ap.add_argument("--fl-shard-size", type=int, default=128,
                    help="per-twin cyclic shard size over the dataset")
    ap.add_argument("--fl-train", type=int, default=4096,
                    help="training samples to load (CIFAR-10 or the "
                         "deterministic synthetic fallback)")
    ap.add_argument("--no-overlap", action="store_true",
                    help="oracle mode: block every round (no pipelining)")
    ap.add_argument("--shards", type=int, default=0,
                    help="force N CPU host devices for twin sharding (a "
                         "rehearsal without a chip; an error on a TPU)")
    ap.add_argument("--seed", type=int, default=0)
    return ap.parse_args(argv)


class Service(NamedTuple):
    """One configured serving stream, as the CLI builds it: the static
    configs, the compiled donated step, the round keys / knob row / FL
    plan it consumes, and ``fresh_state()`` for a new donated state."""
    cfg: Any
    scfg: Any
    ts: Any                     # TwinSharding, or None for one device
    step: Callable
    keys: Any
    warm_keys: Any
    row: Any
    plan: Any
    plan1: Any                  # the one-round plan of the warm-up call
    data: Any
    fresh_state: Callable[[], Any]


def build(args: argparse.Namespace, *, ts=None) -> Service:
    """Build the stream ``args`` describe. ``ts`` is the twin mesh to
    shard over (None: one device)."""
    import jax
    import numpy as np

    from repro.core import scenario, serve
    from repro.core.consensus import ConsensusConfig
    from repro.core.faults import FaultConfig
    from repro.core.marl.env import EnvConfig
    from repro.core.migration import MigrationConfig

    if ts is not None and ts.n_shards == 1:
        ts = None
    cfg = EnvConfig(
        n_twins=args.capacity, n_bs=args.n_bs,
        migration=MigrationConfig() if args.migration else None,
        faults=FaultConfig() if args.faults else None,
        consensus=ConsensusConfig() if args.consensus else None,
    )
    fcfg = None
    if args.fl:
        from repro.fl.stream import FLServeConfig

        fcfg = FLServeConfig(model=args.fl_model,
                             participants=args.fl_participants,
                             local_iters=args.fl_iters,
                             batch_size=args.fl_batch,
                             aggregator=args.fl_aggregator,
                             verify=args.consensus)
    scfg = serve.ServeConfig(capacity=args.capacity, join_rate=args.join,
                             leave_rate=args.leave, policy=args.policy,
                             evolve_channels=args.evolve, fl=fcfg)

    batch = scenario.make_batch(
        jax.random.PRNGKey(args.seed), 1,
        straggler=(0.1, 0.3) if args.faults else None,
        outage=(0.05, 0.2) if args.faults else None,
        byzantine=(0.0, 0.3) if args.consensus else None,
        quorum=(1.0, 2.0) if args.consensus else None)
    knobs = scenario.stream_knobs(batch, fcfg=cfg.faults, ccfg=cfg.consensus,
                                  lat=cfg.lat)
    row = scenario.knob_row(knobs, 0)
    row_key = batch.key[0]

    init = serve.make_serve_init(cfg, scfg, ts=ts, n_live=args.live or None)

    plan = plan1 = data = None
    if args.fl:
        from repro.data import cifar10
        from repro.fl import stream as fl_stream

        data = cifar10.load(max_train=args.fl_train, max_test=512)
        shards = fl_stream.cyclic_shards(data[0][0].shape[0], args.capacity,
                                         args.fl_shard_size)
        plan = fl_stream.stream_fl_plan(fcfg, shards, args.rounds,
                                        seed=args.seed)
        plan1 = jax.tree_util.tree_map(lambda x: x[:1], plan)

    def fresh_state():
        st = init(row_key, row)
        if args.policy is not None:
            st = serve.attach_policy(cfg, st,
                                     jax.random.PRNGKey(args.seed + 1))
        if args.fl:
            fl = fl_stream.fl_init(fcfg, jax.random.PRNGKey(args.seed + 2),
                                   data, np.asarray(st.active, bool), ts=ts)
            st = st._replace(fl=fl)
        return st

    return Service(
        cfg=cfg, scfg=scfg, ts=ts,
        step=serve.make_round_step(cfg, scfg, ts=ts),
        keys=serve.stream_keys(row_key, args.rounds),
        warm_keys=serve.stream_keys(jax.random.fold_in(row_key, 99), 1),
        row=row, plan=plan, plan1=plan1, data=data, fresh_state=fresh_state)


def warm_up(svc: Service) -> float:
    """Compile the donated step off the clock with one throwaway round
    (donation consumes its state); returns the seconds it took."""
    import jax

    from repro.core import serve

    t0 = time.perf_counter()
    warm, m = serve.serve_rounds(svc.cfg, svc.scfg, svc.fresh_state(),
                                 svc.warm_keys, svc.row, step=svc.step,
                                 overlap=False, plan=svc.plan1)
    jax.block_until_ready((warm, m))
    return time.perf_counter() - t0


def run(svc: Service, *, overlap: bool = True):
    """Serve every round of ``svc.keys`` from a fresh state. Returns
    ``(final_state, host_metrics, seconds)``, the seconds running from
    dispatch to the metrics on the host."""
    from repro.core import serve

    state = svc.fresh_state()
    t0 = time.perf_counter()
    state, metrics = serve.serve_rounds(svc.cfg, svc.scfg, state, svc.keys,
                                        svc.row, step=svc.step,
                                        overlap=overlap, plan=svc.plan)
    metrics = serve.stack_metrics(metrics)  # blocks: end of the pipeline
    return state, metrics, time.perf_counter() - t0


def check_metrics(metrics, *, fl: bool) -> Optional[str]:
    """The run's own sanity gate: None when every streamed round time (and,
    with FL, loss and accuracy) is finite, else what failed."""
    import numpy as np

    for k in ("round_time",) + (("fl_loss", "fl_accuracy") if fl else ()):
        if not np.isfinite(metrics[k]).all():
            return f"non-finite {k}"
    return None


def main(argv=None):
    args = parse_args(argv)

    if args.shards:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") +
            f" --xla_force_host_platform_device_count={args.shards}").strip()

    import numpy as np

    from repro.core.sharding import TwinSharding
    from repro.launch.runtime import device_info, setup_compile_cache

    cache = setup_compile_cache()
    dev = device_info()
    if args.shards and dev["platform"] != "cpu":
        print(f"ERROR: --shards forces CPU host devices; this process runs "
              f"on {dev['platform']} ({dev['kind']}), where the twin axis "
              f"already shards over all {dev['count']} devices",
              file=sys.stderr)
        return 2

    svc = build(args, ts=TwinSharding.make())
    n_shards = 1 if svc.ts is None else svc.ts.n_shards
    print(f"device {dev['platform']} {dev['kind']} x{dev['count']}  "
          f"compile cache {cache}")
    print(f"serving capacity={args.capacity} live={args.live or args.capacity}"
          f" bs={args.n_bs} shards={n_shards}"
          f" churn=({args.join},{args.leave}) policy={args.policy or 'static'}"
          f" axes=[{'M' if args.migration else ''}"
          f"{'F' if args.faults else ''}{'C' if args.consensus else ''}"
          f"{'L' if args.fl else ''}]"
          f" overlap={not args.no_overlap}")
    if args.fl:
        print(f"fl model={args.fl_model} participants="
              f"{args.fl_participants} iters={args.fl_iters} "
              f"batch={args.fl_batch} agg={args.fl_aggregator} "
              f"data={svc.data[2]}[{svc.data[0][0].shape[0]}]")

    print(f"warm-up (compile) {warm_up(svc):.2f}s")
    _, metrics, dt = run(svc, overlap=not args.no_overlap)

    rt = metrics["round_time"]
    print(f"{args.rounds} rounds in {dt:.2f}s wall "
          f"({args.rounds / max(dt, 1e-9):.1f} rounds/s)")
    print(f"round_time  mean={rt.mean():.3f}s  p95={np.quantile(rt, .95):.3f}"
          f"s  (simulated)")
    print(f"population  start={int(metrics['n_active'][0])} "
          f"end={int(metrics['n_active'][-1])} "
          f"joined={int(metrics['n_joined'].sum())} "
          f"left={int(metrics['n_left'].sum())}")
    for k in ("straggler_frac", "outage_frac", "migration_rate", "imbalance",
              "accept_frac", "consensus_time", "honest_stake_share"):
        if k in metrics:
            print(f"{k:18s} mean={float(np.mean(metrics[k])):.4f}")
    if args.fl:
        fll, fla = metrics["fl_loss"], metrics["fl_accuracy"]
        print(f"fl_loss     {float(fll[0]):.4f} -> {float(fll[-1]):.4f}   "
              f"fl_accuracy {float(fla[0]):.4f} -> {float(fla[-1]):.4f}")
        print(f"fl_rounds   participants/round mean="
              f"{float(np.mean(metrics['fl_n_participants'])):.1f}  "
              f"accept_frac mean="
              f"{float(np.mean(metrics['fl_accept_frac'])):.3f}")
    err = check_metrics(metrics, fl=args.fl)
    if err is not None:
        print(f"ERROR: {err}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
