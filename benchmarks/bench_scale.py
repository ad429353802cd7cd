"""Scale benchmarks: the segment-reduce backend sweep, the latency core at
large N, the jitted scan trainer, the policy-scaling sweep, and the
twin-sharded vs single-device sweep.

Five measurements:
  * segment-reduce backend sweep — us/call of every backend of
    ``repro.kernels.segment_reduce`` (onehot / sort / segment_sum /
    pallas-tiled / auto) over N x M, the table the auto-dispatch
    heuristics (``resolve_backend``) are calibrated against. This is the
    measured form of the ROADMAP observation that scatter-add loses to the
    dense one-hot below N~10^4 on XLA-CPU;
  * latency core — jitted Eq. 17 ``round_time`` at large N through the
    dispatch, against the dense one-hot reference at the largest N the
    O(N*M) path comfortably fits;
  * MARL training — steps/sec of the fused ``lax.scan``
    rollout-and-update trainer (repro.core.marl.train) vs the host Python
    loop the seed used (examples/marl_allocation.py style), same env and
    update schedule. Acceptance: scan >= 10x loop;
  * policy scaling — actor params/agent, replay row bytes, and scan-trainer
    steps/s vs twin count N for the flat (O(N)-parameter oracle) vs
    factorized (N-independent) policies. The flat column is capped at
    ``_FLAT_MAX_TWINS`` (its first-layer matmul and O(N) action memory make
    larger N infeasible — that cliff is the point of the factorized
    redesign); skips are logged, not silent.
  * sharded scaling (``--sharded``) — the twin-axis mesh path
    (repro.core.sharding): us/call of Eq. 17 ``round_time`` and one env
    observe+step, sharded over 8 forced host devices vs the single-device
    path, N up to 10^6, plus the measured sharded-vs-single parity error.
    Runs in a subprocess (the forced device count must precede jax init)
    and merges ``sharded_scaling`` into ``results/bench/scale.json``.
    HOST-DEVICE CAVEAT: 8 host "devices" share one CPU's cores, so these
    numbers measure dispatch + collective overhead, NOT the memory-scaling
    win — on real multi-chip hardware each shard has its own HBM/compute.
    See docs/SCALING.md.

A fault/adversary sweep (merged into ``scale.json: faults``):
  * ``--faults`` — the accuracy-under-attack grid: a full ``DTWNSystem``
    per cell over poisoner fraction x straggler rate x aggregator
    (plain FedAvg vs coordinate trimmed-mean vs Krum-lite,
    ``repro.core.faults``), model-replacement attackers; headline metric
    is accuracy retention at 30% poisoners (robust rules must hold >= 0.9
    of the clean FedAvg accuracy where plain FedAvg collapses).

A streaming-service sweep (merged into ``scale.json: streaming``):
  * ``--serve`` — the always-on serving loop (``repro.core.serve``) at
    N=10^5: rounds/s of the donated device-resident streaming step
    (pipelined vs block-every-round) against the batch scan runner on the
    same scenario row, plus a churn-rate sweep (>= 20 rounds of live
    join/leave per rate, population accounting recorded).

A consensus sweep (merged into ``scale.json: consensus``):
  * ``--consensus`` — the PBFT grid: byzantine fraction x quorum f x block
    size through ``scenario.run_consensus`` (every cell rides the
    ScenarioBatch axes, so the whole grid shares one jit compilation) —
    mean Eq. 17 round time with the PBFT term priced in, accept fraction
    of the median+tolerance verifier, and the honest stake share after
    the verification rewards — plus a small full-``DTWNSystem`` FL pair
    (byz=0 vs byz=0.3 through ``FLConfig.consensus``) showing the
    view-change factor inflating the round budget without touching
    accuracy.

Two heterogeneity sweeps (merged into ``scale.json: heterogeneity``):
  * ``--alpha`` — population-tail statistics of the ScenarioBatch skew
    axis (p99/median, nonparametric skewness at skew 1/2/4) and the label
    concentration ``scenario_partition`` produces across Dirichlet alphas
    (0.05 .. 5.0 vs IID);
  * ``--migration`` — the between-round twin-migration runner
    (repro.core.migration via scenario.run_migration[_sharded]) at N up to
    10^6: us/round sharded-vs-single, trajectory parity, realized
    migration rate and final load imbalance. Subprocess with 8 forced host
    devices, same caveat as ``--sharded``.

``python -m benchmarks.bench_scale --smoke`` runs a seconds-scale CI gate:
tiny backend sweep + parity of every backend against the one-hot oracle,
plus the policy-protocol gate (flat and factorized actions decode onto the
(18) feasible set from one shared seed; factorized parameter count is
verified N-independent), plus the migration grouping gate (post-migration
per-BS latency through the sort backend's contiguous grouping must equal
the one-hot oracle; bs_segments boundaries must reproduce the occupancy
counts), plus the fault/adversary gate (``fault_gate``: zero-attacker robust
aggregation must equal plain FedAvg within 1e-6, the robust rules must
stay bounded under constant-1e6 replacement attackers plain FedAvg
amplifies, and zero-rate fault injectors must be identities), plus the
consensus gate (``consensus_gate``: producer election and the vectorized
verifier must match the host ledger verdict-for-verdict, and the PBFT
term must collapse to the fixed Eq. 16 constant at zero byzantine
fraction), plus the 8-host-device sharded parity gate (``--sharded-gate``
in a subprocess: latency Eqs. 12-17, env reset/observe/step, a short
scan-train run, the scenario runner, the migration step/env/runner,
the fault-injection draws/round-time/runner, and the consensus chain
runner
must match the single-device path on ragged and empty-shard populations),
plus the streaming-service gate (``--serve-gate`` in the same 8-device
subprocess: K sharded serve rounds at fixed population must match the
batch runners per axis, and churned rounds must keep the mask accounting
and padding convention),
plus the streamed-FL gate (``--serve-fl-gate`` in the same 8-device
subprocess: the serve loop with the FL workload attached — per-twin model
buffers, vmapped local SGD, on-device Eq. 4/5 — must match the
single-device path on a ragged population, and churned FL rounds must
keep evicted model rows zeroed),
exiting nonzero on mismatch — kernel, policy, sharding, or migration
regressions fail fast without waiting for the full bench.
"""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp

from benchmarks.common import Timer, save_result
from repro.core import latency
from repro.core.marl import (DDPGConfig, TrainConfig, act, actor_param_count,
                             policy_init, space_spec, train, train_host_loop,
                             train_init)
from repro.core.marl.env import EnvConfig
from repro.kernels.segment_reduce import resolve_backend, segment_reduce

LP = latency.LatencyParams()

SWEEP_BACKENDS = ("onehot", "sort", "segment_sum", "pallas", "auto")

# beyond this twin count the flat policy's O(N) first/last layers and O(M*N)
# joint-action transients make the sweep cell impractically slow on CPU
_FLAT_MAX_TWINS = 2000


# sections whose sub-keys are owned by DIFFERENT entry points (e.g.
# "heterogeneity" collects --alpha population/partition stats and the
# --migration sweep; "faults" collects the --faults attack grid;
# "consensus" collects the --consensus PBFT grid and FL pair;
# "streaming" collects the --serve throughput/churn sweep;
# "streaming_fl" collects the --streaming-fl streamed-FL sweep) — merged
# one level deep instead of replaced wholesale
_DEEP_MERGE_KEYS = ("heterogeneity", "faults", "consensus", "streaming",
                    "streaming_fl")


def merge_into_scale(sections: dict) -> None:
    """Merge ``sections`` into results/bench/scale.json, preserving every
    key owned by the other entry points (main / --policies / --sharded /
    --alpha / --migration all write disjoint sections of the same file)."""
    import json
    import os

    from benchmarks.common import RESULTS_DIR

    path = os.path.join(RESULTS_DIR, "bench", "scale.json")
    merged = {}
    if os.path.exists(path):
        with open(path) as f:
            merged = json.load(f)
    for k, v in sections.items():
        if (k in _DEEP_MERGE_KEYS and isinstance(v, dict)
                and isinstance(merged.get(k), dict)):
            merged[k].update(v)
        else:
            merged[k] = v
    save_result("scale", merged)


def _time_segment_reduce(n: int, m: int, backend: str,
                         iters: int = 20) -> float:
    """us/call of one (N, M, backend) cell, jitted, excluding compile."""
    ks = jax.random.split(jax.random.PRNGKey(n * 7 + m), 2)
    assoc = jax.random.randint(ks[0], (n,), 0, m)
    vals = jax.random.uniform(ks[1], (n,))
    fn = jax.jit(lambda v, a: segment_reduce(v, a, m, backend=backend))
    fn(vals, assoc).block_until_ready()  # compile
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(vals, assoc)
    out.block_until_ready()
    return (time.perf_counter() - t0) / iters * 1e6


def sweep_segment_reduce(ns, m: int = 8, iters: int = 20) -> dict:
    """The backend-sweep table: {backend: {str(N): us}}. The dense one-hot
    row is skipped once its (N, M) mask would exceed ~256 MB."""
    table = {}
    for be in SWEEP_BACKENDS:
        row = {}
        for n in ns:
            if be == "onehot" and n * m * 4 > 256 * 2**20:
                continue
            row[str(n)] = _time_segment_reduce(n, m, be, iters=iters)
        table[be] = row
    return table


def _print_sweep(table: dict, m: int) -> None:
    ns = sorted({int(k) for row in table.values() for k in row}, key=int)
    print(f"scale: segment_reduce us/call (M={m}, "
          f"platform={jax.default_backend()})")
    hdr = "  backend      " + "".join(f"{f'N=%.0e' % n:>12}" for n in ns)
    print(hdr)
    for be, row in table.items():
        auto = " <- auto" if be == "auto" else ""
        cells = "".join(
            f"{row.get(str(n), float('nan')):>12.0f}" for n in ns)
        picks = ("" if be != "auto" else "  [" + ",".join(
            resolve_backend(n, m) for n in ns) + "]")
        print(f"  {be:<13}{cells}{picks}{auto}")


def _time_round_time(n: int, m: int, fn, iters: int = 20) -> float:
    ks = jax.random.split(jax.random.PRNGKey(n), 3)
    assoc = jax.random.randint(ks[0], (n,), 0, m)
    b = jnp.full((n,), 0.5)
    data = jax.random.uniform(ks[1], (n,), minval=100, maxval=800)
    freqs = jnp.linspace(1e9, 4e9, m)
    up = jnp.full((m,), 1e7)
    down = jnp.full((m,), 1e7)
    jitted = jax.jit(lambda *a: fn(LP, *a))
    jitted(assoc, b, data, freqs, up, down).block_until_ready()  # compile
    t0 = time.perf_counter()
    for _ in range(iters):
        out = jitted(assoc, b, data, freqs, up, down)
    out.block_until_ready()
    return (time.perf_counter() - t0) / iters * 1e6  # us/call


def _loop_steps_per_sec(cfg: EnvConfig, dcfg: DDPGConfig, steps: int,
                        warmup: int) -> float:
    """The seed's host-side training loop, one device round-trip per step
    (the shared reference implementation in repro.core.marl.train)."""
    tcfg = TrainConfig(steps=steps, warmup=warmup, replay_capacity=2048)
    ts = train_host_loop(cfg, dcfg, tcfg, jax.random.PRNGKey(0))  # compile
    jax.block_until_ready(ts.obs)
    t0 = time.perf_counter()
    ts = train_host_loop(cfg, dcfg, tcfg, jax.random.PRNGKey(1))
    jax.block_until_ready(ts.obs)
    return steps / (time.perf_counter() - t0)


def _scan_steps_per_sec(cfg: EnvConfig, dcfg: DDPGConfig, steps: int,
                        warmup: int) -> float:
    tcfg = TrainConfig(steps=steps, warmup=warmup, replay_capacity=2048)
    _, trace = train(cfg, dcfg, tcfg, jax.random.PRNGKey(0))  # compile
    jax.block_until_ready(trace)
    t0 = time.perf_counter()
    _, trace = train(cfg, dcfg, tcfg, jax.random.PRNGKey(1))
    jax.block_until_ready(trace)
    return steps / (time.perf_counter() - t0)


def _learning_check(cfg: EnvConfig, dcfg: DDPGConfig, steps: int) -> dict:
    """The example's endgame: a scan-trained policy vs the random/average
    association baselines on the final env state (shared helper
    repro.core.marl.compare_with_baselines keeps the two in sync)."""
    from repro.core.marl import compare_with_baselines

    tcfg = TrainConfig(steps=steps, warmup=48)
    ts, trace = train(cfg, dcfg, tcfg, jax.random.PRNGKey(0))
    cmp_ = compare_with_baselines(
        cfg, ts.env, act(cfg, ts.agent, ts.obs, policy=dcfg.policy))
    return {"marl": float(cmp_["marl"]), "average": float(cmp_["average"]),
            "early_mean": float(jnp.mean(trace["system_time"][:20])),
            "late_mean": float(jnp.mean(trace["system_time"][-20:]))}


def sweep_policy_scaling(ns=(100, 1000, 10_000), m: int = 5,
                         steps: int = 40, warmup: int = 10) -> dict:
    """Flat-vs-factorized scaling table:
    {policy: {str(N): {actor_params, replay_row_bytes, scan_sps}}}.

    Actor params are per agent; replay row bytes come from the live buffer
    (``replay_row_bytes``); steps/s is the fused scan trainer end-to-end
    (env + replay + MADDPG update). Flat cells above ``_FLAT_MAX_TWINS``
    are skipped with a log line — the factorized rows are the ones that
    must stay flat in N.
    """
    from repro.core.marl import replay_row_bytes

    table = {}
    for pol in ("flat", "factorized"):
        row = {}
        for n in ns:
            if pol == "flat" and n > _FLAT_MAX_TWINS:
                print(f"scale: policy sweep skipping flat at N={n} "
                      f"(> _FLAT_MAX_TWINS={_FLAT_MAX_TWINS}: O(N) layers)")
                continue
            cfg = EnvConfig(n_twins=n, n_bs=m)
            dcfg = DDPGConfig(policy=pol, hidden=(128, 128), batch_size=32)
            params = actor_param_count(
                policy_init(pol, jax.random.PRNGKey(0), cfg, dcfg.hidden))
            tcfg = TrainConfig(steps=steps, warmup=warmup,
                               replay_capacity=256)
            buf = train_init(cfg, dcfg, tcfg, jax.random.PRNGKey(0)).buf
            row[str(n)] = {
                "actor_params": params,
                "replay_row_bytes": replay_row_bytes(buf),
                "scan_sps": _scan_steps_per_sec(cfg, dcfg, steps, warmup),
            }
        table[pol] = row
    return table


def _print_policy_sweep(table: dict) -> None:
    ns = sorted({int(k) for row in table.values() for k in row})
    print("scale: policy scaling (actor params/agent | replay row B | "
          "scan steps/s)")
    for pol, row in table.items():
        cells = []
        for n in ns:
            c = row.get(str(n))
            cells.append("         skipped" if c is None else
                         f"{c['actor_params']:>9,}p/{c['replay_row_bytes']}B/"
                         f"{c['scan_sps']:.0f}sps")
        print(f"  {pol:<12}" + "  ".join(
            f"N={n:<7}{c}" for n, c in zip(ns, cells)))


# ---------------------------------------------------------------------------
# twin-sharded sweep + parity gate (run in a subprocess with 8 host devices:
# --xla_force_host_platform_device_count must be set before jax initializes)
# ---------------------------------------------------------------------------

_SHARDED_DEVICES = 8


def _spawn_sharded(flag: str, extra=()) -> str:
    """Run ``python -m benchmarks.bench_scale <flag>`` under 8 forced host
    devices and return its stdout (the --sharded-child prints JSON). The
    child is pinned to the CPU: it is a host-device rehearsal, and a chip
    held by this (JAX-holding) parent would hang it."""
    import os
    import subprocess
    import sys

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " "
                        "--xla_force_host_platform_device_count="
                        f"{_SHARDED_DEVICES}").strip()
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.bench_scale", flag, *extra],
        capture_output=True, text=True, timeout=1800, env=env,
        cwd=os.path.join(os.path.dirname(__file__), ".."))
    if out.returncode != 0:
        raise RuntimeError(f"bench_scale {flag} subprocess failed:\n"
                           f"{out.stdout[-2000:]}\n{out.stderr[-4000:]}")
    return out.stdout


def sharded_gate() -> None:
    """The 8-host-device parity gate (CI): sharded latency / env / trainer /
    scenario must match the single-device path, including ragged-N padding
    (N % shards != 0) and empty-shard (N < shards) populations. Raises on
    any mismatch."""
    import numpy as np

    from repro.core import latency as lat
    from repro.core import scenario, sharding
    from repro.core.marl import (act, env_reset, env_step, maddpg_init,
                                 observe, sharded_env_reset, sharded_env_step,
                                 sharded_observe, train, train_sharded)
    from repro.core.marl.spaces import Action
    from repro.core.sharding import TwinSharding

    ts = TwinSharding.make()
    assert ts.n_shards == _SHARDED_DEVICES, ts.n_shards
    lp = lat.LatencyParams()

    # latency Eqs. 12-17: divisible / ragged / empty-shard twin counts
    for n, m in [(64, 5), (37, 5), (5, 3)]:
        ks = jax.random.split(jax.random.fold_in(jax.random.PRNGKey(0), n), 5)
        assoc = jax.random.randint(ks[0], (n,), 0, m)
        b = jax.random.uniform(ks[1], (n,), minval=0.05, maxval=1.0)
        data = jax.random.uniform(ks[2], (n,), minval=100, maxval=800)
        freqs = jax.random.uniform(ks[3], (m,), minval=1e9, maxval=4e9)
        up = jax.random.uniform(ks[4], (m,), minval=1e6, maxval=1e8)
        pairs = [
            (sharding.sharded_t_cmp(ts, lp, assoc, b, data, freqs),
             lat.t_cmp(lp, assoc, b, data, freqs)),
            (sharding.sharded_t_local_agg(ts, lp, assoc, freqs),
             lat.t_local_agg(lp, assoc, freqs)),
            (sharding.sharded_t_broadcast(ts, lp, assoc, up, m),
             lat.t_broadcast(lp, assoc, up, m)),
            (sharding.sharded_round_time(ts, lp, assoc, b, data, freqs, up,
                                         up),
             lat.round_time(lp, assoc, b, data, freqs, up, up)),
            (sharding.sharded_round_time_per_bs(ts, lp, assoc, b, data,
                                                freqs, up, up),
             lat.round_time_per_bs(lp, assoc, b, data, freqs, up, up)),
        ]
        for got, ref in pairs:
            np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                       rtol=1e-5, err_msg=f"N={n} M={m}")
    print("sharded-gate: latency Eqs. 12-17 parity ok (incl. ragged/empty)")

    # env reset/observe/step at ragged N
    cfg = EnvConfig(n_twins=37, n_bs=5)
    key = jax.random.PRNGKey(3)
    st_s, st_r = sharded_env_reset(ts, cfg, key), env_reset(cfg, key)
    obs_s, obs_r = sharded_observe(ts, cfg, st_s), observe(cfg, st_r)
    np.testing.assert_allclose(np.asarray(obs_s.bs_feats),
                               np.asarray(obs_r.bs_feats), rtol=1e-5,
                               atol=1e-7)
    agent = maddpg_init(cfg, DDPGConfig(hidden=(32, 32)), key)
    a_r = act(cfg, agent, obs_r)
    a_s = Action(scores=ts.pad_twin(a_r.scores, axis=1), b_ctl=a_r.b_ctl,
                 tau=a_r.tau)
    (st2_s, r_s, info_s) = sharded_env_step(ts, cfg, st_s, a_s, key)
    (st2_r, r_r, info_r) = env_step(cfg, st_r, a_r, key)
    np.testing.assert_allclose(np.asarray(r_s), np.asarray(r_r), rtol=1e-5)
    np.testing.assert_array_equal(
        np.asarray(ts.unpad_twin(info_s["assoc"], cfg.n_twins)),
        np.asarray(info_r["assoc"]))
    print("sharded-gate: env reset/observe/step parity ok")

    # scan trainer (episode resets + MADDPG updates through the mesh)
    cfg = EnvConfig(n_twins=23, n_bs=3, bs_freqs_ghz=(2.6, 1.8, 3.6),
                    episode_len=6)
    dcfg = DDPGConfig(batch_size=8, hidden=(32, 32))
    tcfg = TrainConfig(steps=12, warmup=4, replay_capacity=32)
    st1, tr1 = train(cfg, dcfg, tcfg, jax.random.PRNGKey(1))
    st2, tr2 = train_sharded(ts, cfg, dcfg, tcfg, jax.random.PRNGKey(1))
    for k in tr1:
        np.testing.assert_allclose(np.asarray(tr1[k]), np.asarray(tr2[k]),
                                   rtol=2e-3, atol=1e-5, err_msg=k)
    # host-side per-leaf parity diff, not a cross-twin reduction
    diffs = [float(jnp.max(jnp.abs(x - y)))  # replint: disable=R004
             for x, y in zip(
        jax.tree_util.tree_leaves(st1.agent.actor),
        jax.tree_util.tree_leaves(st2.agent.actor))]
    assert max(diffs) < 1e-4, max(diffs)
    print(f"sharded-gate: scan-trainer parity ok "
          f"(max actor-param diff {max(diffs):.2e})")

    # scenario runner
    cfg = EnvConfig(n_twins=41, n_bs=7)
    batch = scenario.make_batch(jax.random.PRNGKey(2), 5)
    out = scenario.run_baselines_sharded(ts, cfg, batch)
    ref = scenario.run_baselines(cfg, batch)
    for k in ("random", "average"):
        np.testing.assert_allclose(np.asarray(out[k]), np.asarray(ref[k]),
                                   rtol=1e-5, err_msg=k)
    print("sharded-gate: scenario-runner parity ok")

    # migration: raw step, env step with migration dynamics, and the
    # scenario migration runner — bit-parity with the single-device path on
    # divisible / ragged / empty-shard populations
    from repro.core.migration import (MigrationConfig, migration_step,
                                      sharded_migration_step)

    mcfg = MigrationConfig(p_move=0.4, locality=1.5, load_weight=0.8)
    key = jax.random.PRNGKey(11)
    for n, m in [(64, 5), (37, 5), (5, 3)]:
        ks = jax.random.split(jax.random.fold_in(key, n), 2)
        assoc = jax.random.randint(ks[0], (n,), 0, m)
        data = jax.random.uniform(ks[1], (n,), minval=100, maxval=800)
        got = ts.unpad_twin(
            sharded_migration_step(ts, mcfg, key, assoc, data, m), n)
        np.testing.assert_array_equal(
            np.asarray(got),
            np.asarray(migration_step(mcfg, key, assoc, data, m)),
            err_msg=f"N={n} M={m}")
    cfgm = EnvConfig(n_twins=37, n_bs=5, migration=mcfg)
    st_s, st_r = sharded_env_reset(ts, cfgm, key), env_reset(cfgm, key)
    agent = maddpg_init(cfgm, DDPGConfig(hidden=(32, 32)), key)
    a_r = act(cfgm, agent, observe(cfgm, st_r))
    a_s = Action(scores=ts.pad_twin(a_r.scores, axis=1), b_ctl=a_r.b_ctl,
                 tau=a_r.tau)
    _, r_s, info_s = sharded_env_step(ts, cfgm, st_s, a_s, key)
    _, r_r, info_r = env_step(cfgm, st_r, a_r, key)
    np.testing.assert_allclose(np.asarray(r_s), np.asarray(r_r), rtol=1e-5)
    np.testing.assert_allclose(float(info_s["migration_rate"]),
                               float(info_r["migration_rate"]), rtol=1e-6)
    out = scenario.run_migration_sharded(ts, cfg, mcfg, batch, n_rounds=4)
    ref = scenario.run_migration(cfg, mcfg, batch, n_rounds=4)
    for k in ref:
        np.testing.assert_allclose(np.asarray(out[k]), np.asarray(ref[k]),
                                   rtol=1e-5, err_msg=k)
    print("sharded-gate: migration parity ok "
          "(step/env/runner, incl. ragged/empty)")

    # faults: straggler/outage/malicious draws bit-match the single-device
    # path (per-twin streams are global, localized per shard), the faulty
    # round time matches within fp tolerance (psum order), and the fault
    # scenario runner matches — on divisible / ragged / empty-shard N
    from repro.core import faults

    fcfg = faults.FaultConfig(straggler_rate=0.3, outage_rate=0.2,
                              malicious_frac=0.25)
    for n, m in [(64, 5), (37, 5), (5, 3)]:
        kf = jax.random.fold_in(jax.random.PRNGKey(13), n)
        slow_s, mal_s = faults.sharded_fault_draws(ts, fcfg, kf, n)
        slow_r, mal_r = faults.fault_draws(fcfg, kf, n)
        np.testing.assert_array_equal(
            np.asarray(ts.unpad_twin(slow_s, n)), np.asarray(slow_r),
            err_msg=f"straggler N={n}")
        np.testing.assert_array_equal(
            np.asarray(ts.unpad_twin(mal_s, n)), np.asarray(mal_r),
            err_msg=f"malicious N={n}")
        ks = jax.random.split(kf, 5)
        assoc = jax.random.randint(ks[0], (n,), 0, m)
        b = jax.random.uniform(ks[1], (n,), minval=0.05, maxval=1.0)
        data = jax.random.uniform(ks[2], (n,), minval=100, maxval=800)
        freqs = jax.random.uniform(ks[3], (m,), minval=1e9, maxval=4e9)
        up = jax.random.uniform(ks[4], (m,), minval=1e6, maxval=1e8)
        t_s = faults.sharded_faulty_round_time(ts, lp, fcfg, kf, assoc, b,
                                               data, freqs, up, up)
        t_r = faults.faulty_round_time(lp, fcfg, kf, assoc, b, data, freqs,
                                       up, up)
        np.testing.assert_allclose(float(t_s), float(t_r), rtol=1e-5,
                                   err_msg=f"faulty_round_time N={n}")
    cfgf = EnvConfig(n_twins=41, n_bs=7)
    out = scenario.run_faults_sharded(ts, cfgf, fcfg, batch, n_rounds=4)
    ref = scenario.run_faults(cfgf, fcfg, batch, n_rounds=4)
    for k in ref:
        np.testing.assert_allclose(np.asarray(out[k]), np.asarray(ref[k]),
                                   rtol=1e-5, atol=1e-7, err_msg=k)
    print("sharded-gate: fault-injection parity ok "
          "(draws bit-exact, round time/runner fp-exact, incl. "
          "ragged/empty)")

    # consensus: the on-device chain runner sharded over the twin axis must
    # match the single-device path on a batch that exercises all three
    # consensus axes. Integer-derived outputs (verdict fractions, the PBFT
    # and legacy block terms — all (M,)-replicated math) are bit-exact; the
    # psum-crossing floats (stake init from per-shard data sums) may differ
    # by summation order, so round_times/honest_stake_share get rtol=1e-6
    from repro.core.consensus import ConsensusConfig

    cfgc = EnvConfig(n_twins=41, n_bs=7)
    ccfg = ConsensusConfig(quorum_f=1)
    batchc = scenario.make_batch(jax.random.PRNGKey(23), 4,
                                 byzantine=(0.0, 0.4), quorum=(0.0, 2.0),
                                 block_size=(1e6, 8e6))
    out = scenario.run_consensus_sharded(ts, cfgc, ccfg, batchc, n_rounds=4)
    ref = scenario.run_consensus(cfgc, ccfg, batchc, n_rounds=4)
    exact = ("accept_frac", "consensus_time", "legacy_block_time")
    for k in ref:
        a, b = np.asarray(out[k]), np.asarray(ref[k])
        if k in exact:
            np.testing.assert_array_equal(a, b, err_msg=k)
        else:
            np.testing.assert_allclose(a, b, rtol=1e-6, err_msg=k)
    print("sharded-gate: consensus-runner parity ok "
          "(verdicts/PBFT term bit-exact, psum-crossing floats fp-exact)")


def _time_call(fn, *args, iters: int = 10) -> float:
    """us/call of a jitted callable, excluding compile."""
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6


def sharded_sweep() -> dict:
    """The sharded-vs-single sweep body (requires the forced-device-count
    subprocess): Eq. 17 round_time and env-step us/call at each N, both
    paths, plus parity residuals. N tops out at 10^6."""
    import numpy as np

    from repro.core import latency as lat
    from repro.core import sharding
    from repro.core.marl import (env_reset, env_step, sharded_env_reset,
                                 sharded_env_step)
    from repro.core.marl.spaces import Action
    from repro.core.sharding import TwinSharding

    ts = TwinSharding.make()
    lp = lat.LatencyParams()
    m = 8
    ns = (10_000, 100_000, 1_000_000)
    out = {"devices": ts.n_shards, "n_bs": m,
           "round_time_us": {"single": {}, "sharded": {}},
           "env_step_us": {"single": {}, "sharded": {}}, "parity": {}}

    for n in ns:
        ks = jax.random.split(jax.random.PRNGKey(n % 97), 3)
        assoc = jax.random.randint(ks[0], (n,), 0, m)
        b = jnp.full((n,), 0.5)
        data = jax.random.uniform(ks[1], (n,), minval=100, maxval=800)
        freqs = jnp.linspace(1e9, 4e9, m)
        up = jnp.full((m,), 1e7)
        f_single = jax.jit(
            lambda a, bb, d: lat.round_time(lp, a, bb, d, freqs, up, up))
        f_shard = jax.jit(functools.partial(
            sharding.sharded_round_time, ts, lp, freqs=freqs, uplink=up,
            downlink=up))
        r_s = f_shard(assoc, b, data)
        r_1 = f_single(assoc, b, data)
        out["parity"][str(n)] = abs(float(r_s) - float(r_1)) / abs(
            float(r_1))
        out["round_time_us"]["single"][str(n)] = _time_call(
            f_single, assoc, b, data)
        out["round_time_us"]["sharded"][str(n)] = _time_call(
            f_shard, assoc, b, data)

        cfg = EnvConfig(n_twins=n, n_bs=m)
        key = jax.random.fold_in(jax.random.PRNGKey(5), n % 89)
        a0 = Action(
            scores=jax.random.uniform(ks[2], (m, n), minval=-1, maxval=1),
            b_ctl=jnp.zeros((m,)), tau=jnp.zeros((m, cfg.wl.n_subchannels)))

        st8 = sharded_env_reset(ts, cfg, key)
        a8 = Action(scores=ts.pad_twin(a0.scores, axis=1), b_ctl=a0.b_ctl,
                    tau=a0.tau)
        step8 = jax.jit(lambda s, a, k: sharded_env_step(ts, cfg, s, a, k))
        out["env_step_us"]["sharded"][str(n)] = _time_call(
            step8, st8, a8, key)

        st1_ = env_reset(cfg, key)
        step1 = jax.jit(lambda s, a, k: env_step(cfg, s, a, k))
        out["env_step_us"]["single"][str(n)] = _time_call(step1, st1_, a0,
                                                          key)

        _, r8, _ = step8(st8, a8, key)
        _, r1, _ = step1(st1_, a0, key)
        np.testing.assert_allclose(np.asarray(r8), np.asarray(r1), rtol=1e-4)
        print(f"sharded-sweep: N={n:>9,} round_time "
              f"{out['round_time_us']['sharded'][str(n)]:>8.0f}us sharded vs "
              f"{out['round_time_us']['single'][str(n)]:>8.0f}us single | "
              f"env step {out['env_step_us']['sharded'][str(n)]:>8.0f}us vs "
              f"{out['env_step_us']['single'][str(n)]:>8.0f}us | "
              f"rel err {out['parity'][str(n)]:.1e}")
    return out


# ---------------------------------------------------------------------------
# heterogeneity sweeps (scale.json: "heterogeneity")
# ---------------------------------------------------------------------------


def heterogeneity_stats(n_twins: int = 20_000, n_users: int = 100,
                        n_samples: int = 10_000) -> dict:
    """The --alpha sweep: population-tail statistics of the ScenarioBatch
    skew axis (is skew>1 actually heavier-tailed than skew=1?) and label
    concentration of ``scenario_partition`` across alphas. Host-scale,
    seconds; merged into scale.json under ``heterogeneity``."""
    import numpy as np

    from repro.fl.partition import scenario_partition

    key = jax.random.PRNGKey(0)
    dmin, dmax = 100.0, 1500.0
    tail = {}
    for skew in (1.0, 2.0, 4.0):
        u = jax.random.uniform(jax.random.fold_in(key, int(skew)),
                               (n_twins,))
        d = np.asarray(dmin + (dmax - dmin) * u ** skew)
        tail[str(skew)] = {
            "mean": float(d.mean()), "median": float(np.median(d)),
            "p99": float(np.percentile(d, 99)),
            "tail_ratio_p99_median": float(np.percentile(d, 99)
                                           / np.median(d)),
            "nonparametric_skew": float((d.mean() - np.median(d)) / d.std()),
        }

    labels = np.arange(n_samples) % 10
    sizes = np.asarray(dmin + (dmax - dmin)
                       * np.asarray(jax.random.uniform(key, (n_users,)))**3)
    part = {}
    for alpha in (0.05, 0.1, 0.5, 5.0, None):
        shards = scenario_partition(n_samples, sizes, labels=labels,
                                    alpha=alpha, seed=0)
        maxfrac = [np.bincount(labels[s], minlength=10).max() / len(s)
                   for s in shards]
        part["iid" if alpha is None else str(alpha)] = {
            "mean_max_class_frac": float(np.mean(maxfrac)),
            "min_shard": int(min(len(s) for s in shards)),
        }
    return {"population_tail": tail, "alpha_partition": part,
            "n_twins": n_twins, "n_users": n_users}


def migration_sweep(ns=(10_000, 100_000, 1_000_000), n_scenarios: int = 2,
                    n_rounds: int = 5) -> dict:
    """The --migration sweep body (requires the forced-device-count
    subprocess): ``run_migration`` vs ``run_migration_sharded`` us/round at
    each N — association evolving under the Markov mobility + load-aware
    kernel across FL rounds — plus sharded-vs-single parity of the full
    round-time trajectories. N tops out at 10^6 (sharded runs to
    completion there; that cell is the acceptance gate). Parity is
    ENFORCED, not just recorded: any N whose trajectories diverge beyond
    fp32 noise raises — a large-N-only sharding bug (padding, psum) fails
    the sweep instead of landing in scale.json as data."""
    import numpy as np

    from repro.core import scenario
    from repro.core.migration import MigrationConfig
    from repro.core.sharding import TwinSharding

    ts = TwinSharding.make()
    mcfg = MigrationConfig(p_move=0.2, locality=1.0, load_weight=1.0)
    m = 8
    out = {"devices": ts.n_shards, "n_bs": m, "n_scenarios": n_scenarios,
           "n_rounds": n_rounds,
           "mcfg": {"p_move": mcfg.p_move, "locality": mcfg.locality,
                    "load_weight": mcfg.load_weight},
           "round_us": {"single": {}, "sharded": {}},
           "parity": {}, "migration_rate": {}, "final_imbalance": {}}
    for n in ns:
        cfg = EnvConfig(n_twins=n, n_bs=m)
        batch = scenario.make_batch(jax.random.PRNGKey(n % 101), n_scenarios)
        f_sh = lambda: scenario.run_migration_sharded(ts, cfg, mcfg, batch,
                                                      n_rounds=n_rounds)
        us_sh = _time_call(lambda *_: f_sh(), iters=3) / (n_scenarios
                                                          * n_rounds)
        got = f_sh()
        ref = scenario.run_migration(cfg, mcfg, batch, n_rounds=n_rounds)
        f_1 = lambda: scenario.run_migration(cfg, mcfg, batch,
                                             n_rounds=n_rounds)
        us_1 = _time_call(lambda *_: f_1(), iters=3) / (n_scenarios
                                                        * n_rounds)
        err = float(np.max(np.abs(np.asarray(got["round_times"])
                                  - np.asarray(ref["round_times"]))
                           / np.abs(np.asarray(ref["round_times"]))))
        assert err < 1e-4, f"sharded migration parity broke at N={n}: {err}"
        out["round_us"]["sharded"][str(n)] = us_sh
        out["round_us"]["single"][str(n)] = us_1
        out["parity"][str(n)] = err
        out["migration_rate"][str(n)] = float(
            np.mean(np.asarray(ref["migration_rates"])))
        out["final_imbalance"][str(n)] = float(
            np.mean(np.asarray(ref["imbalance"])[:, -1]))
        print(f"migration-sweep: N={n:>9,} {us_sh:>9.0f}us/round sharded vs "
              f"{us_1:>9.0f}us single | rate "
              f"{out['migration_rate'][str(n)]:.3f} | rel err {err:.1e}")
    return out


# ---------------------------------------------------------------------------
# fault/adversary axis (scale.json: "faults")
# ---------------------------------------------------------------------------


def fault_gate() -> None:
    """CI gate for the fault/adversary axis (part of --smoke). Three
    invariants, all raising on violation:

    * zero-attacker parity — ``robust_bs_aggregate_stacked`` with
      ``trim_k=0`` / ``krum_f=0`` must reproduce plain
      ``hierarchy.bs_aggregate_stacked`` (FedAvg Eq. 4) within 1e-6;
    * breakdown — with 2 of 8 clients per BS replaced by 1e6 constants,
      plain FedAvg blows up while both robust rules stay bounded and flag
      every attacker (survivor fraction below the suspect threshold);
    * zero-rate identity — ``scenario.run_faults`` with all fault knobs at
      zero must reproduce the ``run_baselines`` 'average' round times
      exactly (the injectors are identities at rate 0).
    """
    import numpy as np

    from repro.core import faults, hierarchy, scenario

    k, m = 24, 3
    ks = jax.random.split(jax.random.PRNGKey(42), 3)
    stacked = {"w": jax.random.normal(ks[0], (k, 4, 5)),
               "b": jax.random.normal(ks[1], (k, 7))}
    sizes = jax.random.uniform(ks[2], (k,), minval=0.5, maxval=2.0)
    assoc = jnp.asarray(np.arange(k) % m, jnp.int32)
    ref_tree, ref_w = hierarchy.bs_aggregate_stacked(stacked, sizes, assoc, m)
    for aggname, kw in (("trimmed_mean", {"trim_k": 0}),
                        ("krum", {"krum_f": 0})):
        tree, w, surv = faults.robust_bs_aggregate_stacked(
            stacked, sizes, assoc, m, aggregator=aggname, **kw)
        for la, lb in zip(jax.tree_util.tree_leaves(tree),
                          jax.tree_util.tree_leaves(ref_tree)):
            np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                       atol=1e-6, err_msg=aggname)
        np.testing.assert_allclose(np.asarray(w), np.asarray(ref_w),
                                   atol=1e-6, err_msg=aggname)
        assert float(jnp.min(surv)) == 1.0, aggname
    print("scale --smoke: zero-attacker robust == FedAvg parity ok "
          "(trimmed_mean, krum)")

    mal = np.zeros(k, bool)
    mal[:6] = True  # average_association order: 2 attackers per BS of 8
    attacked = {
        kk: jnp.where(jnp.asarray(mal).reshape((k,) + (1,) * (v.ndim - 1)),
                      1e6, v) for kk, v in stacked.items()}
    fed_tree, _ = hierarchy.bs_aggregate_stacked(attacked, sizes, assoc, m)
    fed_max = max(float(jnp.max(jnp.abs(le)))
                  for le in jax.tree_util.tree_leaves(fed_tree))
    assert fed_max > 1e4, f"FedAvg unexpectedly bounded: {fed_max}"
    for aggname, kw in (("trimmed_mean", {"trim_k": 2}),
                        ("krum", {"krum_f": 2})):
        tree, _, surv = faults.robust_bs_aggregate_stacked(
            attacked, sizes, assoc, m, aggregator=aggname, **kw)
        rob_max = max(float(jnp.max(jnp.abs(le)))
                      for le in jax.tree_util.tree_leaves(tree))
        assert rob_max < 100.0, f"{aggname} breakdown: {rob_max}"
        n_cli, n_sus = faults.suspect_counts(surv, assoc, m)
        np.testing.assert_array_equal(np.asarray(n_sus),
                                      np.full(m, 2.0, np.float32),
                                      err_msg=aggname)
    print(f"scale --smoke: breakdown gate ok (FedAvg max |agg| {fed_max:.1e}"
          " vs robust < 1e2; 2 attackers/BS all flagged)")

    cfg = EnvConfig(n_twins=33, n_bs=5)
    batch = scenario.make_batch(jax.random.PRNGKey(7), 3)
    fcfg = faults.FaultConfig(straggler_rate=0.0, outage_rate=0.0,
                              malicious_frac=0.0)
    out = scenario.run_faults(cfg, fcfg, batch, n_rounds=4)
    ref = scenario.run_baselines(cfg, batch)
    rt = np.asarray(out["round_times"])
    np.testing.assert_allclose(
        rt, np.broadcast_to(np.asarray(ref["average"]).reshape(-1, 1),
                            rt.shape), rtol=1e-6)
    assert float(jnp.max(out["straggler_frac"])) == 0.0
    assert float(jnp.max(out["outage_frac"])) == 0.0
    print("scale --smoke: zero-rate fault injectors are identities "
          "(run_faults == run_baselines 'average')")


def fault_attack_grid(rounds: int = 3, n_users: int = 20, n_bs: int = 3,
                      train_n: int = 2000, boost: float = 50.0) -> dict:
    """The --faults sweep: accuracy-under-attack curves, robust vs plain
    FedAvg across poisoner fraction x straggler rate (model-replacement
    attackers, ``boost``x update scaling). Each cell runs a full
    ``DTWNSystem`` for ``rounds`` federated rounds on the deterministic
    cifar10-sim textures and records final test accuracy, holdout loss,
    mean round time (stragglers/outages inflate it through Eqs. 12-17) and
    the chain's suspect count. The headline derived metric is
    ``retention_at_poison``: accuracy at 30% poisoners / clean FedAvg
    accuracy, per aggregator — the robust rules must retain >= 0.9 where
    plain FedAvg collapses. Merged into scale.json under
    ``faults.attack_grid``."""
    import numpy as np

    from repro.core import association as assoc_mod
    from repro.core.faults import FaultConfig
    from repro.data import cifar10
    from repro.fl.server import DTWNSystem, FLConfig

    data = cifar10.load(max_train=train_n, max_test=512)
    assoc = np.asarray(assoc_mod.average_association(n_users, n_bs))
    # stratified attacker placement: exactly round(poison * cohort) per BS —
    # the poisoner-fraction axis should mean the fraction, not a Bernoulli
    # draw that can cluster past the per-cohort breakdown point (a cohort
    # that is majority-malicious is unrecoverable by ANY robust rule; the
    # chain's loss gate handles that regime, measured separately)
    def stratified_malicious(frac: float) -> np.ndarray:
        mal = np.zeros(n_users, bool)
        for j in range(n_bs):
            members = np.where(assoc == j)[0]
            mal[members[: int(round(frac * members.size))]] = True
        return mal

    cells = {}
    for poison in (0.0, 0.3):
        for s_rate in (0.0, 0.5):
            for agg in ("fedavg", "trimmed_mean", "krum"):
                cfg = FLConfig(
                    n_users=n_users, n_bs=n_bs,
                    bs_freqs_ghz=(2.6, 1.8, 3.6), local_iters=2,
                    batch_size=16, aggregator=agg, trim_k=2, krum_f=2,
                    malicious_frac=poison, attack="model_replacement",
                    attack_boost=boost,
                    faults=FaultConfig(straggler_rate=s_rate,
                                       outage_rate=0.1 if s_rate else 0.0))
                sys_ = DTWNSystem(cfg, data, seed=0)
                sys_.malicious = stratified_malicious(poison)
                times, n_sus = [], 0
                for _ in range(rounds):
                    r = sys_.run_round(assoc, participating_users=n_users)
                    times.append(r["round_time_s"])
                    n_sus = r["n_suspect"]
                acc = sys_.test_accuracy(n=512)
                name = f"poison{poison}_straggler{s_rate}_{agg}"
                cells[name] = {
                    "accuracy": acc,
                    "holdout_loss": sys_.holdout_loss(sys_.params),
                    "round_time_mean_s": float(np.mean(times)),
                    "n_suspect_last": int(n_sus),
                    "n_attackers": int(sys_.malicious.sum()),
                }
                print(f"faults: {name:<40} acc {acc:.3f} "
                      f"t {np.mean(times):7.2f}s suspects {n_sus}")
    clean = cells["poison0.0_straggler0.0_fedavg"]["accuracy"]
    retention = {
        agg: cells[f"poison0.3_straggler0.0_{agg}"]["accuracy"] / clean
        for agg in ("fedavg", "trimmed_mean", "krum")}
    for agg, r in retention.items():
        print(f"faults: retention at 30% poisoners [{agg}] {r:.3f}")
    return {"attack_grid": {
        "config": {"rounds": rounds, "n_users": n_users, "n_bs": n_bs,
                   "train_n": train_n, "attack": "model_replacement",
                   "attack_boost": boost, "trim_k": 2, "krum_f": 2,
                   "dataset": "cifar10-sim"},
        "cells": cells,
        "clean_fedavg_accuracy": clean,
        "retention_at_poison": retention,
    }}


def consensus_gate() -> None:
    """CI gate for the consensus axis (part of --smoke). Three invariants,
    all raising on violation:

    * election parity — ``consensus.elect_producers`` (stable argsort of
      ``-stakes``) must reproduce the host ledger's tie rule
      (``sorted(range(M), key=lambda i: (-stakes[i], i))``) on quantized
      stakes that force frequent exact ties;
    * verifier triple parity — the vectorized ``verify_metas`` quality
      gate, an independent numpy re-statement of the predicate
      (loss <= fp32 median + tolerance, cohort not majority-suspect), and
      a fresh host ``DPoSChain.verify_round`` must agree verdict-for-
      verdict on a deterministic fuzz over losses / suspect metas;
    * zero-byzantine identity — at ``quorum_f=0, byzantine_frac=0`` the
      PBFT term collapses to the fixed Eq. 16 constant: ``run_consensus``
      must report ``consensus_time == legacy_block_time`` within 1e-6 per
      scenario, and ``latency.round_time(..., consensus=ccfg)`` must equal
      the legacy path.
    """
    import numpy as np

    from repro.core import blockchain as bc
    from repro.core import consensus, scenario
    from repro.core.consensus import ConsensusConfig

    rng = np.random.RandomState(31)
    for trial in range(40):
        m = rng.randint(2, 10)
        stakes = (rng.randint(0, 4, size=m) * 10.0).astype(np.float32)
        k = rng.randint(1, m + 1)
        got = list(np.asarray(consensus.elect_producers(
            jnp.asarray(stakes), k)))
        ref = sorted(range(m), key=lambda i: (-stakes[i], i))[:k]
        assert got == ref, (trial, stakes, k, got, ref)
    print("scale --smoke: consensus election parity ok "
          "(vectorized top-k stake == host tie rule, 40 tie-heavy draws)")

    for trial in range(25):
        m = rng.randint(1, 9)
        losses = rng.choice([0.1, 0.25, 0.5, 0.5, 0.75, 1.0, 5.0],
                            size=m).astype(np.float32)
        tol = float(rng.choice([0.0, 0.25, 0.5]))
        n_cli = rng.randint(1, 9, size=m)
        n_sus = np.minimum(rng.randint(0, 9, size=m), n_cli)
        med = np.median(losses).astype(np.float32)
        want = {i: bool(losses[i] <= med + np.float32(tol)
                        and not (n_sus[i] * 2 > n_cli[i]))
                for i in range(m)}
        got = consensus.verify_metas(
            jnp.asarray(losses), jnp.ones((m,), bool), tolerance=tol,
            n_clients=jnp.asarray(n_cli, jnp.float32),
            n_suspect=jnp.asarray(n_sus, jnp.float32))
        assert {i: bool(v) for i, v in enumerate(np.asarray(got))} == want, \
            (trial, losses, tol)
        chain = bc.DPoSChain(m, [1.0] * m, tolerance=tol)
        for i in range(m):
            chain.submit_model(i, {"w": jnp.full((2,), float(i))}, round_=0,
                               holdout_loss=float(losses[i]),
                               n_clients=int(n_cli[i]),
                               n_suspect=int(n_sus[i]))
        assert chain.verify_round() == want, (trial, losses, tol)
    print("scale --smoke: consensus verifier triple parity ok "
          "(verify_metas == numpy reference == host verify_round)")

    cfg = EnvConfig(n_twins=33, n_bs=5)
    ccfg = ConsensusConfig(quorum_f=0, byzantine_frac=0.0)
    batch = scenario.make_batch(jax.random.PRNGKey(17), 3)
    out = scenario.run_consensus(cfg, ccfg, batch, n_rounds=4)
    np.testing.assert_allclose(np.asarray(out["consensus_time"]),
                               np.asarray(out["legacy_block_time"]),
                               atol=1e-6)
    ks = jax.random.split(jax.random.PRNGKey(19), 5)
    n, m = 41, 5
    assoc = jax.random.randint(ks[0], (n,), 0, m)
    b = jax.random.uniform(ks[1], (n,), minval=0.05, maxval=1.0)
    data = jax.random.uniform(ks[2], (n,), minval=100, maxval=800)
    freqs = jax.random.uniform(ks[3], (m,), minval=1e9, maxval=4e9)
    up = jax.random.uniform(ks[4], (m,), minval=1e6, maxval=1e8)
    legacy = latency.round_time(LP, assoc, b, data, freqs, up, up)
    cons = latency.round_time(LP, assoc, b, data, freqs, up, up,
                              consensus=ccfg)
    assert abs(float(legacy) - float(cons)) <= 1e-6, (legacy, cons)
    print("scale --smoke: zero-byzantine PBFT == Eq. 16 identity ok "
          "(run_consensus per-scenario and round_time consensus mode)")


def consensus_sweep(n_scenarios: int = 4, n_rounds: int = 8,
                    fl_rounds: int = 2, fl_users: int = 12,
                    fl_train_n: int = 2000) -> dict:
    """The --consensus sweep, merged into ``scale.json: consensus``.

    Two measurements:

    * ``pbft_grid`` — byzantine fraction x quorum f x block size, each
      cell one ``run_consensus`` batch of ``n_scenarios`` scenarios
      advancing the on-device chain ``n_rounds`` blocks: mean Eq. 17 round
      time, the PBFT term, the legacy Eq. 16 constant, mean accept
      fraction, and the honest stake share after the rewards. The knobs
      ride the ScenarioBatch axes (degenerate ``(v, v)`` ranges) so every
      cell shares ONE jit compilation;
    * ``fl_pair`` — a small full-``DTWNSystem`` accuracy pair, consensus
      priced vs legacy: byz=0 vs byz=0.3 through ``FLConfig.consensus``
      on the deterministic cifar10-sim textures — the headline is that the
      view-change factor inflates the round budget while accuracy is
      untouched (consensus prices the block phase; it does not alter
      aggregation).
    """
    import numpy as np

    from repro.core import scenario
    from repro.core.consensus import ConsensusConfig

    cfg = EnvConfig(n_twins=64, n_bs=5)
    ccfg = ConsensusConfig()
    cells = {}
    for byz in (0.0, 0.2, 0.4):
        for qf in (0, 1, 2):
            for sb in (2e6, 8e6):
                batch = scenario.make_batch(
                    jax.random.PRNGKey(29), n_scenarios,
                    byzantine=(byz, byz), quorum=(float(qf), float(qf)),
                    block_size=(sb, sb))
                out = scenario.run_consensus(cfg, ccfg, batch,
                                             n_rounds=n_rounds)
                name = f"byz{byz}_f{qf}_blk{sb:.0e}"
                cells[name] = {
                    "round_time_mean_s": float(jnp.mean(out["round_times"])),
                    "consensus_time_mean_s":
                        float(jnp.mean(out["consensus_time"])),
                    "legacy_block_time_mean_s":
                        float(jnp.mean(out["legacy_block_time"])),
                    "accept_frac_mean": float(jnp.mean(out["accept_frac"])),
                    "honest_stake_share_mean":
                        float(jnp.mean(out["honest_stake_share"])),
                }
                c = cells[name]
                print(f"consensus: {name:<24} t {c['round_time_mean_s']:7.2f}s"
                      f" pbft {c['consensus_time_mean_s']:6.2f}s"
                      f" accept {c['accept_frac_mean']:.3f}"
                      f" honest-stake {c['honest_stake_share_mean']:.3f}")

    from repro.core import association as assoc_mod
    from repro.data import cifar10
    from repro.fl.server import DTWNSystem, FLConfig

    data = cifar10.load(max_train=fl_train_n, max_test=512)
    n_bs = 3
    assoc = np.asarray(assoc_mod.average_association(fl_users, n_bs))
    fl_cells = {}
    for byz in (0.0, 0.3):
        flc = FLConfig(n_users=fl_users, n_bs=n_bs,
                       bs_freqs_ghz=(2.6, 1.8, 3.6), local_iters=2,
                       batch_size=16,
                       consensus=ConsensusConfig(quorum_f=1,
                                                 byzantine_frac=byz))
        sys_ = DTWNSystem(flc, data, seed=0)
        times, cons_times = [], []
        for _ in range(fl_rounds):
            r = sys_.run_round(assoc, participating_users=fl_users)
            times.append(r["round_time_s"])
            cons_times.append(r["consensus_time_s"])
        acc = sys_.test_accuracy(n=512)
        fl_cells[f"byz{byz}"] = {
            "accuracy": acc,
            "round_time_mean_s": float(np.mean(times)),
            "consensus_time_mean_s": float(np.mean(cons_times)),
        }
        print(f"consensus: fl byz={byz} acc {acc:.3f} "
              f"t {np.mean(times):7.2f}s pbft {np.mean(cons_times):6.2f}s")
    return {
        "pbft_grid": {
            "config": {"n_scenarios": n_scenarios, "n_rounds": n_rounds,
                       "n_twins": 64, "n_bs": 5,
                       "byzantine": [0.0, 0.2, 0.4], "quorum_f": [0, 1, 2],
                       "block_size_bits": [2e6, 8e6]},
            "cells": cells,
        },
        "fl_pair": {
            "config": {"rounds": fl_rounds, "n_users": fl_users,
                       "n_bs": n_bs, "train_n": fl_train_n, "quorum_f": 1,
                       "dataset": "cifar10-sim"},
            "cells": fl_cells,
        },
    }


def serve_gate() -> None:
    """The streaming-service parity gate (CI, 8 forced host devices):
    K rounds of the sharded ``repro.core.serve`` loop at a fixed full
    population must match the batch runners on the same scenario row —
    divisible (N=64 migration), ragged (N=37 faults), and empty-shard
    (N=5 consensus) populations — plus quick churn invariants (per-round
    mask accounting and the padding convention on the final state).
    Raises on any mismatch."""
    import numpy as np

    from repro.core import scenario, serve
    from repro.core.consensus import ConsensusConfig
    from repro.core.faults import FaultConfig
    from repro.core.migration import MigrationConfig
    from repro.core.sharding import TwinSharding

    ts = TwinSharding.make()
    batch = scenario.make_batch(jax.random.PRNGKey(0), 2,
                                straggler=(0.1, 0.4), outage=(0.05, 0.3),
                                byzantine=(0.0, 0.4), quorum=(0.0, 2.0),
                                block_size=(1e6, 8e6))
    k_rounds, i = 4, 1
    cases = [
        ("faults", EnvConfig(n_twins=37, n_bs=5,
                             faults=FaultConfig(0.3, 0.2, 0.25))),
        ("migration", EnvConfig(n_twins=64, n_bs=5,
                                migration=MigrationConfig(0.4, 1.5, 0.8))),
        ("consensus", EnvConfig(n_twins=5, n_bs=5,
                                consensus=ConsensusConfig(quorum_f=1))),
    ]
    for name, cfg in cases:
        scfg = serve.ServeConfig(capacity=cfg.n_twins)
        knobs = scenario.stream_knobs(batch, fcfg=cfg.faults,
                                      ccfg=cfg.consensus, lat=cfg.lat)
        row = scenario.knob_row(knobs, i)
        init = serve.make_serve_init(cfg, scfg, ts=ts)
        state = init(batch.key[i], row)
        step = serve.make_round_step(cfg, scfg, ts=ts)
        keys = serve.stream_keys(batch.key[i], k_rounds)
        state, m = serve.serve_rounds(cfg, scfg, state, keys, row,
                                      step=step, overlap=False)
        m = serve.stack_metrics(m)
        if name == "faults":
            ref = scenario.run_faults(cfg, cfg.faults, batch,
                                      n_rounds=k_rounds)
        elif name == "migration":
            ref = scenario.run_migration(cfg, cfg.migration, batch,
                                         n_rounds=k_rounds)
        else:
            ref = scenario.run_consensus(cfg, cfg.consensus, batch,
                                         n_rounds=k_rounds)
        np.testing.assert_allclose(
            m["round_time"], np.asarray(ref["round_times"])[i], rtol=1e-6,
            err_msg=f"serve-vs-batch round_time, axis={name} "
                    f"N={cfg.n_twins} shards={ts.n_shards}")
        assert int(m["n_active"][-1]) == cfg.n_twins, (name, m["n_active"])
    print(f"serve parity ok on {ts.n_shards} shards "
          "(divisible/ragged/empty-shard populations)")

    # --- churn invariants under the sharded step ---
    cfg = EnvConfig(n_twins=64, n_bs=5)
    scfg = serve.ServeConfig(capacity=64, join_rate=0.15, leave_rate=0.15)
    knobs = scenario.stream_knobs(batch)
    row = scenario.knob_row(knobs, 0)
    init = serve.make_serve_init(cfg, scfg, ts=ts, n_live=48)
    state = init(batch.key[0], row)
    step = serve.make_round_step(cfg, scfg, ts=ts)
    keys = serve.stream_keys(batch.key[0], 6)
    pop = 48
    for t in range(6):
        state, m = step(state, serve.round_keys(keys, t), row)
        m = {k: np.asarray(v) for k, v in m.items()}
        pop = pop + int(m["n_joined"]) - int(m["n_left"])
        assert int(m["n_active"]) == pop, (t, m)
        assert np.isfinite(m["round_time"]) and m["round_time"] > 0
    act = np.asarray(state.active)
    assoc = np.asarray(state.env.assoc)
    data = np.asarray(state.env.data_sizes)
    assert (assoc[~act] == 5).all() and (data[~act] == 0.0).all()
    assert (assoc[act] < 5).all()
    print(f"serve churn ok on {ts.n_shards} shards "
          f"(population 48 -> {pop} over 6 rounds)")


def serve_sweep(n: int = 100_000, n_rounds: int = 24,
                churn_rates=(0.0, 0.01, 0.05)) -> dict:
    """Streaming-service throughput at N=10^5: rounds/s of the donated
    streaming step (pipelined and blocking) vs the batch scan runner on
    the same scenario row, plus a churn-rate sweep (>= 20 rounds of live
    join/leave per rate). Merged into ``scale.json: streaming``."""
    import numpy as np

    from repro.core import scenario, serve
    from repro.core.faults import FaultConfig

    cfg = EnvConfig(n_twins=n, n_bs=10, faults=FaultConfig())
    batch = scenario.make_batch(jax.random.PRNGKey(0), 1,
                                straggler=(0.1, 0.3), outage=(0.05, 0.2))
    knobs = scenario.stream_knobs(batch, fcfg=cfg.faults)
    row = scenario.knob_row(knobs, 0)
    row_key = batch.key[0]

    # batch reference: the scan runner, timed post-compile
    ref = scenario.run_faults(cfg, cfg.faults, batch, n_rounds=n_rounds)
    jax.block_until_ready(ref["round_times"])
    t0 = time.time()
    ref = scenario.run_faults(cfg, cfg.faults, batch, n_rounds=n_rounds)
    jax.block_until_ready(ref["round_times"])
    batch_rps = n_rounds / max(time.time() - t0, 1e-9)

    def run(scfg, overlap):
        step = serve.make_round_step(cfg, scfg)
        keys = serve.stream_keys(row_key, n_rounds)
        # warm the compile AND the allocator/thread-pool steady state off
        # the clock (several rounds — the first executions after a compile
        # run well below steady-state throughput on XLA-CPU); donation
        # consumes the state, so warm on a throwaway one
        state = serve.serve_init(cfg, scfg, row_key, row)
        serve.serve_rounds(cfg, scfg, state, serve.stream_keys(
            jax.random.fold_in(row_key, 99), 6), row, step=step,
            overlap=overlap)
        best, m = 0.0, None
        for _ in range(2):  # best-of-2: host/worker thread contention on
            # shared CPUs makes single timings of the async path erratic
            state = serve.serve_init(cfg, scfg, row_key, row)
            t0 = time.time()
            state, m = serve.serve_rounds(cfg, scfg, state, keys, row,
                                          step=step, overlap=overlap)
            m = serve.stack_metrics(m)  # blocks: end of the pipeline
            best = max(best, n_rounds / max(time.time() - t0, 1e-9))
        return best, m

    fixed = serve.ServeConfig(capacity=n)
    stream_rps, m_fixed = run(fixed, overlap=True)
    blocking_rps, _ = run(fixed, overlap=False)
    np.testing.assert_allclose(m_fixed["round_time"],
                               np.asarray(ref["round_times"])[0], rtol=1e-6)

    churn = {}
    for rate in churn_rates:
        scfg = serve.ServeConfig(capacity=n, join_rate=rate,
                                 leave_rate=rate)
        rps, m = run(scfg, overlap=True)
        churn[str(rate)] = {
            "rounds_per_s": rps,
            "final_population": int(m["n_active"][-1]),
            "joined": int(m["n_joined"].sum()),
            "left": int(m["n_left"].sum()),
            "mean_round_time_s": float(np.mean(m["round_time"])),
        }
        assert np.isfinite(m["round_time"]).all()

    out = {
        "n_twins": n, "n_rounds": n_rounds, "n_bs": 10,
        "batch_rounds_per_s": batch_rps,
        "stream_rounds_per_s": stream_rps,
        "stream_blocking_rounds_per_s": blocking_rps,
        "overlap_speedup_vs_blocking": stream_rps / max(blocking_rps, 1e-9),
        "stream_vs_batch": stream_rps / max(batch_rps, 1e-9),
        "churn_sweep": churn,
    }
    print(f"streaming N={n}: batch {batch_rps:.1f} rounds/s, stream "
          f"{stream_rps:.1f} (pipelined) / {blocking_rps:.1f} (blocking)")
    for rate, rowd in churn.items():
        print(f"  churn={rate}: {rowd['rounds_per_s']:.1f} rounds/s, "
              f"population {n} -> {rowd['final_population']} "
              f"(+{rowd['joined']}/-{rowd['left']})")
    return out


def serve_fl_gate() -> None:
    """Streamed-FL parity gate (CI, 8 forced host devices): K rounds of
    the serve loop with the real FL workload attached — per-twin model
    buffers, vmapped local SGD, on-device Eq. 4/5 aggregation, chain
    verify — sharded over 8 devices must match the single-device path:
    bit-equal integer telemetry (participants, accept counts, Eq. 4 BS
    weights) and float-tolerance loss/accuracy/model trees, on a ragged
    population (N=37 pads to 40). Plus churned FL rounds: finite loss and
    evicted rows zeroed in the model buffers. Raises on any mismatch."""
    import numpy as np

    from repro.core import scenario, serve
    from repro.core.sharding import TwinSharding
    from repro.data import cifar10
    from repro.fl import stream as fls
    from repro.fl.partition import iid_partition

    ts = TwinSharding.make()
    n, m, k_rounds = 37, 5, 3
    fcfg = fls.FLServeConfig(model="tiny", participants=6, local_iters=2,
                             batch_size=8, verify=True, tolerance=25.0)
    cfg = EnvConfig(n_twins=n, n_bs=m)
    scfg = serve.ServeConfig(capacity=n, fl=fcfg)
    batch = scenario.make_batch(jax.random.PRNGKey(0), 2)
    row = scenario.knob_row(scenario.stream_knobs(batch), 1)
    data = cifar10.load(max_train=2000, max_test=300)
    plan = fls.stream_fl_plan(fcfg, iid_partition(2000, n, seed=3),
                              k_rounds, seed=0)
    keys = serve.stream_keys(batch.key[1], k_rounds)

    def run(scfg, ts, n_live=None):
        init = serve.make_serve_init(cfg, scfg, ts=ts, n_live=n_live)
        state = init(batch.key[1], row)
        fl = fls.fl_init(fcfg, jax.random.PRNGKey(7), data,
                         np.asarray(state.active, bool))
        state = state._replace(fl=fl)
        step = serve.make_round_step(cfg, scfg, ts=ts)
        state, mtr = serve.serve_rounds(cfg, scfg, state, keys, row,
                                        step=step, overlap=False, plan=plan)
        return state, serve.stack_metrics(mtr)

    s1, m1 = run(scfg, None)
    s8, m8 = run(scfg, ts)
    for k in ("fl_n_participants", "fl_accept_frac", "fl_bs_weight",
              "round_time"):
        np.testing.assert_array_equal(m1[k], m8[k],
                                      err_msg=f"serve-fl parity: {k}")
    for k in ("fl_loss", "fl_accuracy"):
        np.testing.assert_allclose(m1[k], m8[k], rtol=1e-5,
                                   err_msg=f"serve-fl parity: {k}")
    for k in s1.fl.params:
        np.testing.assert_allclose(np.asarray(s1.fl.params[k]),
                                   np.asarray(s8.fl.params[k]), atol=2e-6,
                                   err_msg=f"global model: {k}")
        # sharded twin buffers are capacity-padded — compare the real rows
        np.testing.assert_allclose(np.asarray(s1.fl.twin_params[k]),
                                   np.asarray(s8.fl.twin_params[k])[:n],
                                   atol=2e-6, err_msg=f"twin buffer: {k}")
    print(f"serve fl parity ok on {ts.n_shards} shards "
          f"(ragged N={n}, {k_rounds} rounds, tiny model)")

    # --- churned FL rounds under the sharded step ---
    scfg_c = serve.ServeConfig(capacity=n, join_rate=0.2, leave_rate=0.2,
                               fl=fcfg)
    state, mtr = run(scfg_c, ts, n_live=28)
    assert np.isfinite(mtr["fl_loss"]).all(), mtr["fl_loss"]
    act = np.array(state.active)  # copy: the buffers were donated
    for k, tp in state.fl.twin_params.items():
        dead = np.array(tp)[~act]
        assert (dead == 0.0).all(), f"evicted rows not zeroed in {k}"
    print(f"serve fl churn ok on {ts.n_shards} shards "
          f"(population 28 -> {int(mtr['n_active'][-1])})")


def streaming_fl_sweep(n: int = 10_000, n_rounds: int = 12,
                       churn_rates=(0.0, 0.01, 0.05)) -> dict:
    """Streamed-FL throughput at N=10^4: rounds/s of the donated FL round
    step (vmapped local SGD + on-device Eq. 4/5) with pipelined vs
    blocking dispatch, plus a churn-rate sweep where evicted twins drop
    out of the aggregation and admitted twins warm-start from the live
    global model. Merged into ``scale.json: streaming_fl``."""
    import numpy as np

    from repro.core import scenario, serve
    from repro.data import cifar10
    from repro.fl import stream as fls

    train_n, shard_size = 4096, 128
    fcfg = fls.FLServeConfig(model="tiny", participants=16, local_iters=2,
                             batch_size=8)
    cfg = EnvConfig(n_twins=n, n_bs=10)
    batch = scenario.make_batch(jax.random.PRNGKey(0), 1)
    row = scenario.knob_row(scenario.stream_knobs(batch), 0)
    row_key = batch.key[0]
    data = cifar10.load(max_train=train_n, max_test=512)
    plan = fls.stream_fl_plan(fcfg, fls.cyclic_shards(train_n, n, shard_size),
                              n_rounds, seed=0)
    plan1 = jax.tree_util.tree_map(lambda x: x[:1], plan)

    def run(scfg, overlap):
        step = serve.make_round_step(cfg, scfg)
        keys = serve.stream_keys(row_key, n_rounds)

        def fresh():
            st = serve.serve_init(cfg, scfg, row_key, row)
            fl = fls.fl_init(fcfg, jax.random.PRNGKey(2), data,
                             np.asarray(st.active, bool))
            return st._replace(fl=fl)

        # warm the compile off the clock (donation consumes the state)
        serve.serve_rounds(cfg, scfg, fresh(), serve.stream_keys(
            jax.random.fold_in(row_key, 99), 1), row, step=step,
            overlap=False, plan=plan1)
        best, m = 0.0, None
        for _ in range(2):  # best-of-2: the async path is timing-noisy
            state = fresh()
            t0 = time.time()
            state, m = serve.serve_rounds(cfg, scfg, state, keys, row,
                                          step=step, overlap=overlap,
                                          plan=plan)
            m = serve.stack_metrics(m)  # blocks: end of the pipeline
            best = max(best, n_rounds / max(time.time() - t0, 1e-9))
        assert np.isfinite(m["fl_loss"]).all()
        return best, m

    fixed = serve.ServeConfig(capacity=n, fl=fcfg)
    stream_rps, m_fixed = run(fixed, overlap=True)
    blocking_rps, _ = run(fixed, overlap=False)

    churn = {}
    for rate in churn_rates:
        scfg = serve.ServeConfig(capacity=n, join_rate=rate,
                                 leave_rate=rate, fl=fcfg)
        rps, m = run(scfg, overlap=True)
        churn[str(rate)] = {
            "rounds_per_s": rps,
            "final_population": int(m["n_active"][-1]),
            "joined": int(m["n_joined"].sum()),
            "left": int(m["n_left"].sum()),
            "fl_loss_first": float(m["fl_loss"][0]),
            "fl_loss_last": float(m["fl_loss"][-1]),
            "fl_accuracy_last": float(m["fl_accuracy"][-1]),
            "mean_accept_frac": float(np.mean(m["fl_accept_frac"])),
        }

    out = {
        "n_twins": n, "n_rounds": n_rounds, "n_bs": 10,
        "model": fcfg.model, "participants": fcfg.participants,
        "local_iters": fcfg.local_iters, "batch_size": fcfg.batch_size,
        "train_n": train_n, "shard_size": shard_size,
        "stream_rounds_per_s": stream_rps,
        "stream_blocking_rounds_per_s": blocking_rps,
        "overlap_speedup_vs_blocking": stream_rps / max(blocking_rps, 1e-9),
        "fl_loss_first": float(m_fixed["fl_loss"][0]),
        "fl_loss_last": float(m_fixed["fl_loss"][-1]),
        "fl_accuracy_last": float(m_fixed["fl_accuracy"][-1]),
        "churn_sweep": churn,
    }
    print(f"streaming_fl N={n}: {stream_rps:.1f} rounds/s (pipelined) / "
          f"{blocking_rps:.1f} (blocking), loss "
          f"{out['fl_loss_first']:.3f} -> {out['fl_loss_last']:.3f}")
    for rate, rowd in churn.items():
        print(f"  churn={rate}: {rowd['rounds_per_s']:.1f} rounds/s, "
              f"population {n} -> {rowd['final_population']} "
              f"(+{rowd['joined']}/-{rowd['left']}), loss -> "
              f"{rowd['fl_loss_last']:.3f}")
    return out


def smoke() -> None:
    """CI gate: tiny sweep through every backend + oracle parity. Raises
    (and exits nonzero) on any backend disagreeing with the dense oracle."""
    import numpy as np

    m = 7
    for n in (63, 1024, 4097):
        ks = jax.random.split(jax.random.PRNGKey(n), 2)
        assoc = jax.random.randint(ks[0], (n,), 0, m)
        vals = jax.random.uniform(ks[1], (n,), minval=-1.0, maxval=1.0)
        ref = np.asarray(segment_reduce(vals, assoc, m, backend="onehot"))
        for be in ("sort", "segment_sum", "pallas", "auto"):
            out = np.asarray(segment_reduce(vals, assoc, m, backend=be))
            np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-5,
                                       err_msg=f"backend={be} N={n}")
    table = sweep_segment_reduce((1_000, 10_000), m=8, iters=3)
    _print_sweep(table, m=8)
    print("scale --smoke: all segment_reduce backends match the oracle")

    # --- policy-protocol parity gate (flat vs factorized, shared seed) ---
    from repro.core import association as assoc_mod
    from repro.core.marl import (decode_actions, env_reset, maddpg_init,
                                 observe)

    cfg = EnvConfig(n_twins=48, n_bs=5)
    key = jax.random.PRNGKey(3)
    st = env_reset(cfg, key)
    obs = observe(cfg, st)
    shapes = {}
    for pol in ("flat", "factorized"):
        dcfg = DDPGConfig(policy=pol, hidden=(32, 32))
        agent = maddpg_init(cfg, dcfg, key)
        a = act(cfg, agent, obs, policy=pol)
        assoc, b, tau = decode_actions(cfg, a)
        shapes[pol] = (assoc.shape, b.shape, tau.shape)
        checks = assoc_mod.check_constraints(cfg.lat, assoc, b, tau,
                                             cfg.n_twins, cfg.n_bs)
        assert all(checks.values()), f"policy={pol} violates {checks}"
    assert shapes["flat"] == shapes["factorized"], shapes
    p_small = actor_param_count(
        policy_init("factorized", key, EnvConfig(n_twins=48), (32, 32)))
    p_big = actor_param_count(
        policy_init("factorized", key, EnvConfig(n_twins=4800), (32, 32)))
    assert p_small == p_big, (p_small, p_big)
    print(f"scale --smoke: flat/factorized decode parity ok; factorized "
          f"actor params N-independent ({p_small:,} at N=48 and N=4800)")

    # --- migration parity gate: post-migration per-BS results through the
    # sort backend's contiguous grouping must equal the one-hot oracle, and
    # the bs_segments boundaries must reproduce the occupancy counts ---
    from repro.core import migration as mig
    from repro.kernels.segment_reduce import segment_count

    mcfg = mig.MigrationConfig(p_move=0.5, locality=1.0, load_weight=1.0)
    for n in (63, 1024):
        ks = jax.random.split(jax.random.PRNGKey(n + 1), 3)
        assoc = jax.random.randint(ks[0], (n,), 0, m)
        data = jax.random.uniform(ks[1], (n,), minval=100, maxval=800)
        assoc2 = mig.migration_step(mcfg, ks[2], assoc, data, m)
        freqs = jnp.linspace(1e9, 4e9, m)
        up = jnp.full((m,), 1e7)
        b = jnp.full((n,), 0.5)
        t_sort = latency.round_time(LP, assoc2, b, data, freqs, up, up,
                                    backend="sort")
        t_oracle = latency.round_time_onehot(LP, assoc2, b, data, freqs, up,
                                             up)
        np.testing.assert_allclose(float(t_sort), float(t_oracle), rtol=1e-5,
                                   err_msg=f"migration N={n}")
        _, bounds = mig.bs_segments(assoc2, m)
        np.testing.assert_array_equal(
            np.diff(np.asarray(bounds)),
            np.asarray(segment_count(assoc2, m, backend="onehot"),
                       np.int64), err_msg=f"bs_segments N={n}")
    print("scale --smoke: migration sort-grouping parity vs one-hot oracle "
          "ok")

    # --- fault/adversary axis gate: zero-attacker robust==FedAvg parity,
    # breakdown bound, zero-rate injector identity ---
    fault_gate()

    # --- consensus axis gate: election/verifier host parity, zero-byzantine
    # PBFT == Eq. 16 identity ---
    consensus_gate()

    # --- 8-host-device sharded parity gate (subprocess: the forced device
    # count must be set before jax initializes; includes the migration
    # step/env/runner parity block) ---
    print(_spawn_sharded("--sharded-gate").strip())
    print("scale --smoke: sharded parity gate ok on "
          f"{_SHARDED_DEVICES} host devices")

    # --- streaming-service gate (subprocess, same forced device count):
    # sharded serve loop vs batch runners + churn invariants ---
    print(_spawn_sharded("--serve-gate").strip())
    print("scale --smoke: serve gate ok on "
          f"{_SHARDED_DEVICES} host devices")

    # --- streamed-FL gate (subprocess, same forced device count): the FL
    # workload through the sharded serve loop vs single-device, + churn ---
    print(_spawn_sharded("--serve-fl-gate").strip())
    print("scale --smoke: serve fl gate ok on "
          f"{_SHARDED_DEVICES} host devices")


def main(reduced: bool = True):
    with Timer() as t:
        m = 8
        sweep_ns = ((1_000, 10_000, 100_000) if reduced else
                    (1_000, 10_000, 100_000, 1_000_000))
        sweep = sweep_segment_reduce(sweep_ns, m=m,
                                     iters=20 if reduced else 10)
        n_seg = 100_000 if reduced else 1_000_000
        n_ref = 10_000
        us_seg = _time_round_time(n_seg, m, latency.round_time)
        us_seg_ref_n = _time_round_time(n_ref, m, latency.round_time)
        us_onehot = _time_round_time(n_ref, m, latency.round_time_onehot)

        cfg = EnvConfig(n_twins=30, n_bs=5)
        loop_steps = 40 if reduced else 200
        scan_steps = 400 if reduced else 2000
        # example scale (compute-bound: the 256x256 MADDPG update dominates
        # both paths, fusion only removes the host dispatch overhead)
        dcfg_big = DDPGConfig(batch_size=64)
        loop_big = _loop_steps_per_sec(cfg, dcfg_big, loop_steps, warmup=10)
        scan_big = _scan_steps_per_sec(cfg, dcfg_big, scan_steps, warmup=10)
        # dispatch-bound scale (small nets: the regime the host loop caps —
        # one device round-trip per env step + one per update)
        dcfg_small = DDPGConfig(hidden=(32, 32), batch_size=16)
        loop_small = _loop_steps_per_sec(cfg, dcfg_small, loop_steps,
                                         warmup=10)
        scan_small = _scan_steps_per_sec(cfg, dcfg_small, scan_steps,
                                         warmup=10)
        speedup = scan_small / loop_small
        learn = _learning_check(cfg, dcfg_big, 120 if reduced else 200)
        policy_sweep = sweep_policy_scaling((100, 1_000, 10_000),
                                            steps=30 if reduced else 60)

    out = {
        "segment_reduce_sweep_us": sweep,
        "segment_reduce_sweep_m": m,
        "round_time_segment_us": {str(n_seg): us_seg, str(n_ref): us_seg_ref_n},
        "round_time_onehot_us": {str(n_ref): us_onehot},
        "marl_example_scale": {"loop_sps": loop_big, "scan_sps": scan_big,
                               "speedup": scan_big / loop_big},
        "marl_dispatch_bound": {"loop_sps": loop_small, "scan_sps": scan_small,
                                "speedup": speedup},
        "learning_check": learn,
        "policy_scaling": policy_sweep,
    }
    merge_into_scale(out)
    _print_sweep(sweep, m=m)
    _print_policy_sweep(policy_sweep)
    print(f"scale: round_time N={n_seg} segment {us_seg:.0f}us | "
          f"N={n_ref} segment {us_seg_ref_n:.0f}us vs onehot {us_onehot:.0f}us")
    print(f"scale: MARL 256x256/b64  scan {scan_big:.0f} vs loop "
          f"{loop_big:.0f} steps/s ({scan_big / loop_big:.1f}x)")
    print(f"scale: MARL 32x32/b16    scan {scan_small:.0f} vs loop "
          f"{loop_small:.0f} steps/s ({speedup:.1f}x)")
    print(f"scale: learned policy round time {learn['marl']:.2f}s vs "
          f"average baseline {learn['average']:.2f}s "
          f"(train latency {learn['early_mean']:.2f}s -> "
          f"{learn['late_mean']:.2f}s)")
    return {"name": "scale",
            "us_per_call": t.seconds * 1e6,
            "derived": f"segN{n_seg}/{us_seg:.0f}us"
                       f"|scan_sps/{scan_small:.0f}"
                       f"|loop_sps/{loop_small:.0f}"
                       f"|speedup/{speedup:.1f}x"}


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="seconds-scale backend parity + policy gate + "
                         "sharded parity gate CI run")
    ap.add_argument("--reduced", action="store_true",
                    help="CI-scale run instead of the full N=10^6 sweep")
    ap.add_argument("--policies", action="store_true",
                    help="run only the flat-vs-factorized scaling sweep "
                         "(merged into results/bench/scale.json)")
    ap.add_argument("--sharded", action="store_true",
                    help="run the twin-sharded vs single-device sweep on 8 "
                         "forced host devices (subprocess; merged into "
                         "results/bench/scale.json as 'sharded_scaling')")
    ap.add_argument("--sharded-gate", action="store_true",
                    help="[subprocess child] 8-device sharded parity gate")
    ap.add_argument("--serve", action="store_true",
                    help="streaming-service throughput sweep at N=10^5: "
                         "donated streaming step (pipelined/blocking) vs "
                         "the batch scan runner, plus a churn-rate sweep "
                         "(merged into scale.json: streaming)")
    ap.add_argument("--serve-gate", action="store_true",
                    help="[subprocess child] 8-device streaming-vs-batch "
                         "parity + churn invariant gate")
    ap.add_argument("--serve-fl-gate", action="store_true",
                    help="[subprocess child] 8-device streamed-FL parity "
                         "(sharded vs single-device serve loop with the "
                         "FL workload) + churned-FL invariant gate")
    ap.add_argument("--streaming-fl", action="store_true",
                    help="streamed-FL throughput sweep at N=10^4: the "
                         "donated FL round step pipelined vs blocking, "
                         "plus a churn-rate sweep (merged into "
                         "scale.json: streaming_fl)")
    ap.add_argument("--sharded-child", action="store_true",
                    help="[subprocess child] sharded sweep body; prints "
                         "JSON on the last stdout line")
    ap.add_argument("--alpha", action="store_true",
                    help="heterogeneity stats sweep: ScenarioBatch "
                         "population-tail + scenario_partition label "
                         "concentration across alphas (merged into "
                         "scale.json: heterogeneity)")
    ap.add_argument("--migration", action="store_true",
                    help="migration sweep on 8 forced host devices up to "
                         "N=10^6 (subprocess; merged into scale.json: "
                         "heterogeneity.migration_sweep)")
    ap.add_argument("--migration-child", action="store_true",
                    help="[subprocess child] migration sweep body; prints "
                         "JSON on the last stdout line")
    ap.add_argument("--faults", action="store_true",
                    help="accuracy-under-attack grid: robust vs plain "
                         "FedAvg across poisoner fraction x straggler rate "
                         "(merged into scale.json: faults.attack_grid)")
    ap.add_argument("--consensus", action="store_true",
                    help="PBFT consensus grid: byzantine fraction x quorum "
                         "f x block size through run_consensus, plus a "
                         "small FL pair with the consensus-priced round "
                         "budget (merged into scale.json: consensus)")
    args = ap.parse_args()
    if args.smoke:
        smoke()
    elif args.sharded_gate:
        sharded_gate()
    elif args.serve_gate:
        serve_gate()
    elif args.serve_fl_gate:
        serve_fl_gate()
    elif args.streaming_fl:
        merge_into_scale({"streaming_fl": streaming_fl_sweep()})
        print("streaming_fl sweep merged into results/bench/scale.json")
    elif args.serve:
        merge_into_scale({"streaming": serve_sweep()})
        print("streaming sweep merged into results/bench/scale.json")
    elif args.sharded_child:
        import json

        print(json.dumps(sharded_sweep()))
    elif args.sharded:
        import json

        stdout = _spawn_sharded("--sharded-child")
        lines = [ln for ln in stdout.strip().splitlines() if ln]
        for ln in lines[:-1]:
            print(ln)
        merge_into_scale({"sharded_scaling": json.loads(lines[-1])})
        print("sharded_scaling merged into results/bench/scale.json")
    elif args.migration_child:
        import json

        print(json.dumps(migration_sweep()))
    elif args.migration:
        import json

        stdout = _spawn_sharded("--migration-child")
        lines = [ln for ln in stdout.strip().splitlines() if ln]
        for ln in lines[:-1]:
            print(ln)
        merge_into_scale(
            {"heterogeneity": {"migration_sweep": json.loads(lines[-1])}})
        print("heterogeneity.migration_sweep merged into "
              "results/bench/scale.json")
    elif args.faults:
        merge_into_scale({"faults": fault_attack_grid()})
        print("faults.attack_grid merged into results/bench/scale.json")
    elif args.consensus:
        merge_into_scale({"consensus": consensus_sweep()})
        print("consensus grid merged into results/bench/scale.json")
    elif args.alpha:
        stats = heterogeneity_stats()
        merge_into_scale({"heterogeneity": stats})
        for skew, row in stats["population_tail"].items():
            print(f"heterogeneity: skew={skew} p99/median "
                  f"{row['tail_ratio_p99_median']:.2f} nonparametric skew "
                  f"{row['nonparametric_skew']:+.3f}")
        for a, row in stats["alpha_partition"].items():
            print(f"heterogeneity: alpha={a} mean max-class frac "
                  f"{row['mean_max_class_frac']:.3f} min shard "
                  f"{row['min_shard']}")
        print("heterogeneity stats merged into results/bench/scale.json")
    elif args.policies:
        table = sweep_policy_scaling()
        _print_policy_sweep(table)
        merge_into_scale({"policy_scaling": table})
    else:
        main(reduced=args.reduced)
