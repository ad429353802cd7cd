"""Always-on DTWN service: streaming rounds over a live twin population.

Everything before this module is batch-mode — sweeps and trainers start,
run N rounds, and exit. The paper's premise is *real-time* digital-twin
maintenance ("migrate real-time data processing and computation to the edge
plane"), so this module turns the round pipeline into a long-lived service:

* **Device-resident donated state** — :class:`ServeState` (env realization,
  active mask, fault chain, byzantine mask, optional MADDPG agent + replay)
  lives on device across rounds. The jitted round step donates its state
  argument (``jax.jit(..., donate_argnums=...)``, the ``launch/train.py``
  idiom), so XLA writes round t+1's state into round t's buffers and the
  N-sized twin arrays never round-trip to host — at N=10^6 that is the
  difference between a service and a benchmark.
* **Population churn** — the twin axis is a fixed-capacity padded buffer
  with an ``active`` mask. :func:`admit` / :func:`evict` rewrite rows and
  the mask without reshaping: an evicted row is restamped to the padding
  convention (``data=0``, ``assoc=n_bs``) so it vanishes from every segment
  reduction and Eq. 4 weight by construction — the exact invariant
  ``core/sharding.py`` already enforces for shard-padding rows, so sharded
  serving works unchanged. Churn draws come from a dedicated key fold
  (11) disjoint from every batch-runner stream, so zero-churn streaming is
  bit-identical to the batch runners.
* **Pipelined rounds** — :func:`serve_rounds` dispatches round t+1 without
  blocking on round t (``jax.block_until_ready``-free); host work (metric
  indexing) overlaps device execution. ``overlap=False`` is the oracle
  mode that blocks every round — both produce identical values.
* **Online scenario streaming** — per-round knobs are
  :class:`~repro.core.scenario.StreamKnobs` rows (heterogeneity, fault,
  and consensus axes), consumed one per round.

Parity contract (gated by ``tests/test_serve.py`` and
``bench_scale --serve-gate``): at a fixed full population with churn off,
K streamed rounds are bit-identical to the batch runners on the same
scenario row — per axis, the round body reproduces the exact key
derivations of ``scenario._faults_one`` (fold 5 round keys, fold 4 outage
init), ``scenario._migration_one`` (fold 3), and ``scenario._consensus_one``
(fold 6 byzantine mask, fold 8 submissions), and composes the round time as
the same ``max(t_cmp) + max(t_broadcast) + block-term`` decomposition.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import association as assoc_mod
from repro.core import comms, latency, migration, scenario, sharding
from repro.core import consensus as consensus_mod
from repro.core import faults as faults_mod
from repro.core.marl import env as env_mod
from repro.core.marl.env import EnvConfig, EnvState
from repro.core.scenario import StreamKnobs
from repro.core.sharding import TWIN_AXIS, TwinSharding

__all__ = [
    "ServeConfig", "ServeState", "RoundKeys", "stream_keys", "serve_init",
    "make_serve_init", "attach_policy", "admit", "evict", "churn_step",
    "make_round_step",
    "serve_rounds", "serve_specs", "stack_metrics",
]

# key folds consumed per scenario-row key, shared with the batch runners
# (scenario.py): 1 random assoc, 2 rollout, 3 migration, 4 outage init,
# 5 fault rounds, 6 byzantine mask, 7 malicious mask, 8 chain submissions.
# The serve loop's own streams must stay disjoint:
_CHURN_FOLD = 11    # per-round join/leave draws
_DYNAMICS_FOLD = 12  # per-round channel/frequency evolution (opt-in)

# host spans of serve_rounds, written into the profiler's trace while one
# is recording: a round's input selection, its step's enqueue, and the
# metric stacking at the end of a call
SPAN_INPUTS = "serve.inputs"
SPAN_ENQUEUE = "serve.enqueue"
SPAN_STACK = "serve.stack"


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Static serving knobs (hashable — jit-static next to EnvConfig).

    ``capacity``   — twin-buffer capacity; must equal ``EnvConfig.n_twins``
                     (the buffer IS the twin axis; live population <= it).
    ``join_rate``  — per-round probability an empty slot admits a twin.
    ``leave_rate`` — per-round probability a live twin departs.
    ``policy``     — policy protocol name for MARL-driven association
                     (``ServeState.agent`` required); None streams the
                     paper's round-robin association (+ optional migration).
    ``evolve_channels`` — advance channel/frequency dynamics each round
                     (:func:`repro.core.marl.env.env_evolve`, dedicated
                     fold 12). Off by default: the batch runners hold
                     channels fixed, and parity mode must too.
    ``fl``         — :class:`repro.fl.stream.FLServeConfig` to stream the
                     real FL workload through the round step (per-twin
                     model buffers in ``ServeState.fl``, vmapped local
                     SGD + Eq. 4/5 on device); requires a per-round
                     :class:`~repro.fl.stream.FLPlan`. None streams the
                     latency/env/chain simulation only.
    """
    capacity: int
    join_rate: float = 0.0
    leave_rate: float = 0.0
    policy: Optional[str] = None
    evolve_channels: bool = False
    fl: Optional[Any] = None

    @property
    def churns(self) -> bool:
        return self.join_rate > 0.0 or self.leave_rate > 0.0


class ServeState(NamedTuple):
    """The donated device-resident state of one serving stream.

    Twin-axis leaves (``env.data_sizes``/``env.assoc``/``active``) are
    (capacity,) — shard-local blocks under a twin scope. Inactive rows
    always carry the padding convention ``data=0, assoc=n_bs``.
    """
    env: EnvState            # capacity-padded realization (+ chain view)
    active: jnp.ndarray      # (capacity,) bool — live twins
    bad: jnp.ndarray         # (M,) bool Gilbert-Elliott channel state
    byz: jnp.ndarray         # (M,) bool stationary byzantine mask
    agent: Any = None        # optional MADDPGState (policy mode)
    buf: Any = None          # optional marl.replay.Replay (policy mode)
    fl: Any = None           # optional fl.stream.FLState (streamed FL)
    round: Any = 0           # int32 rounds served (set by serve_init)


class RoundKeys(NamedTuple):
    """One round's PRNG keys, each (2,) uint32 — pre-split on the host for
    the whole stream (:func:`stream_keys`) because ``split(key, n)[i]``
    depends on ``n``: per-round keys must come from the SAME
    ``split(fold_in(key, fold), n_rounds)`` derivation the batch runners
    use, or bit-parity is lost."""
    mig: jnp.ndarray    # fold 3  — scenario._migration_one's round stream
    fault: jnp.ndarray  # fold 5  — scenario._faults_one's round stream
    chain: jnp.ndarray  # fold 8  — scenario._consensus_one's round stream
    churn: jnp.ndarray  # fold 11 — serve-only join/leave stream
    dyn: jnp.ndarray    # fold 12 — serve-only channel-evolution stream


def stream_keys(key, n_rounds: int) -> RoundKeys:
    """Key streams for ``n_rounds`` of serving from one scenario-row key —
    each a (n_rounds, 2) array; index round t with ``round_keys(keys, t)``."""
    def fold_split(fold):
        return jax.random.split(jax.random.fold_in(key, fold), n_rounds)

    return RoundKeys(mig=fold_split(3), fault=fold_split(5),
                     chain=fold_split(8), churn=fold_split(_CHURN_FOLD),
                     dyn=fold_split(_DYNAMICS_FOLD))


def round_keys(keys: RoundKeys, t) -> RoundKeys:
    """Round ``t``'s key tuple out of a :func:`stream_keys` stack."""
    return jax.tree_util.tree_map(lambda k: k[t], keys)


# ---------------------------------------------------------------------------
# churn — admit / evict on capacity-managed padded buffers
# ---------------------------------------------------------------------------


def evict(active, data_sizes, assoc, leave, n_bs: int):
    """Depart ``leave & active`` twins: returns ``(active', data', assoc')``
    with departed rows restamped to the padding convention (``data=0``,
    ``assoc=n_bs``) — out of range for every segment reduction, so an
    evicted twin contributes to no Eq. 4/12-17 quantity from this round on.
    Pure and shape-preserving (no reshape — sharding layouts survive)."""
    leave = jnp.asarray(leave, bool) & active
    return (active & ~leave,
            jnp.where(leave, 0.0, data_sizes),
            jnp.where(leave, n_bs, assoc))


def admit(active, data_sizes, assoc, join, new_data, new_assoc):
    """Admit ``join & ~active`` twins into empty slots: each admitted row
    takes its ``new_data``/``new_assoc`` entry (the association is live
    immediately — an admitted twin is scored by the *next* round's
    latency/association pass). Pure and shape-preserving."""
    join = jnp.asarray(join, bool) & ~active
    return (active | join,
            jnp.where(join, new_data, data_sizes),
            jnp.where(join, new_assoc, assoc))


def churn_step(cfg: EnvConfig, scfg: ServeConfig, key, active, data_sizes,
               assoc, row: StreamKnobs):
    """One round of population churn: Bernoulli departures over live twins,
    Bernoulli admissions into empty slots, admitted populations drawn from
    the round's scenario knobs (``data_min + (data_max-data_min) * U^skew``,
    the :func:`scenario.sample_population` law) with a uniform-random
    initial association. All draws are full-capacity draws localized per
    shard (``sharding.localize``), so sharded serving churns bit-identically
    to single-device. Returns ``(active', data', assoc', n_joined, n_left)``
    — counts are replicated scalars (:func:`sharding.twin_count`)."""
    cap = data_sizes.shape[0] if sharding.in_scope() is None \
        else sharding.in_scope().n_global
    k_leave, k_join, k_data, k_assoc = jax.random.split(key, 4)
    u_leave = sharding.localize(jax.random.uniform(k_leave, (cap,)),
                                fill=1.0)
    u_join = sharding.localize(jax.random.uniform(k_join, (cap,)), fill=1.0)
    leave = active & (u_leave < scfg.leave_rate)
    join = ~active & (u_join < scfg.join_rate)
    u_d = sharding.localize(jax.random.uniform(k_data, (cap,)), fill=0.0)
    new_data = sharding.mask_twins(
        row.data_min + (row.data_max - row.data_min) * u_d ** row.skew, 0.0)
    new_assoc = sharding.localize(
        jax.random.randint(k_assoc, (cap,), 0, cfg.n_bs), fill=cfg.n_bs)
    active2, data2, assoc2 = evict(active, data_sizes, assoc, leave,
                                   cfg.n_bs)
    active2, data2, assoc2 = admit(active2, data2, assoc2, join, new_data,
                                   new_assoc)
    return (active2, sharding.mask_twins(data2, 0.0),
            sharding.mask_twins(assoc2, cfg.n_bs),
            sharding.twin_count(join), sharding.twin_count(leave))


# ---------------------------------------------------------------------------
# init — one scenario row's realization at capacity
# ---------------------------------------------------------------------------


def serve_init(cfg: EnvConfig, scfg: ServeConfig, key, row: StreamKnobs,
               n_live: Optional[int] = None) -> ServeState:
    """Fresh serving state from one scenario-row key: the SAME realization
    ``scenario.scenario_env`` builds for the batch runners (population,
    channels, round-robin association, chain stakes), plus the serve-only
    state — the first ``n_live`` slots active (default: all), the outage
    chain's stationary init (fold 4, matching ``_faults_one``), and the
    stationary byzantine mask (fold 6, matching ``_consensus_one``).
    Attach ``agent``/``buf`` for policy mode via ``._replace``."""
    if scfg.capacity != cfg.n_twins:
        raise ValueError(f"ServeConfig.capacity ({scfg.capacity}) must equal"
                         f" EnvConfig.n_twins ({cfg.n_twins}) — the twin"
                         f" buffer IS the twin axis")
    st = scenario.scenario_env(cfg, key, row.data_min, row.data_max,
                               row.skew)
    n_live = cfg.n_twins if n_live is None else n_live
    active = sharding.localize(
        jnp.arange(cfg.n_twins) < n_live, fill=False)
    if n_live < cfg.n_twins:
        data = jnp.where(active, st.data_sizes, 0.0)
        assoc = jnp.where(active, st.assoc, cfg.n_bs)
        st = st._replace(data_sizes=data, assoc=assoc,
                         chain=env_mod.init_chain(cfg, data, assoc))
    m = cfg.n_bs
    bad = (faults_mod.outage_draw(cfg.faults, jax.random.fold_in(key, 4),
                                  m, rate=row.outage)
           if cfg.faults is not None else jnp.zeros((m,), bool))
    byz = (consensus_mod.draw_byzantine(jax.random.fold_in(key, 6), m,
                                        row.byzantine)
           if cfg.consensus is not None else jnp.zeros((m,), bool))
    if cfg.consensus is not None:
        st = st._replace(chain=sharding.stamp_replicated(st.chain))
    return ServeState(env=st, active=active, bad=bad, byz=byz,
                      round=jnp.int32(0))


def attach_policy(cfg: EnvConfig, state: ServeState, key, *,
                  dcfg=None, replay_capacity: int = 4096) -> ServeState:
    """Attach a fresh MADDPG agent and an empty replay buffer to a serving
    state (policy mode). Both subtrees are M-sized (the PR 3 compact-encoding
    invariant), so they ride replicated next to the sharded twin buffers."""
    from repro.core.marl import replay, spaces
    from repro.core.marl.ddpg import DDPGConfig, maddpg_init

    dcfg = dcfg or DDPGConfig()
    spec = spaces.space_spec(cfg)
    return state._replace(
        agent=maddpg_init(cfg, dcfg, key),
        buf=replay.replay_init(replay_capacity, spec.compact_dim,
                               spec.n_bs, spec.enc_dim))


def make_serve_init(cfg: EnvConfig, scfg: ServeConfig,
                    ts: Optional[TwinSharding] = None,
                    n_live: Optional[int] = None):
    """Jitted (and, with ``ts``, twin-sharded) :func:`serve_init` —
    ``fn(key, row) -> ServeState`` laid out exactly as
    :func:`make_round_step` expects (twin leaves sharded, rest
    replicated)."""
    if ts is None or ts.n_shards == 1:
        return jax.jit(functools.partial(serve_init, cfg, scfg,
                                         n_live=n_live))

    def local(key, row):
        with ts.scope(cfg.n_twins):
            return serve_init(cfg, scfg, key, row, n_live=n_live)

    sm = ts.shard_map(local, in_specs=(P(), P()),
                      out_specs=serve_specs(cfg))
    return jax.jit(sm)


# ---------------------------------------------------------------------------
# the round step — donated, scope-aware, parity-exact per axis
# ---------------------------------------------------------------------------


def _round_step(cfg: EnvConfig, scfg: ServeConfig, state: ServeState,
                keys: RoundKeys, row: StreamKnobs, plan=None):
    """One streamed round. Axis-for-axis this reproduces the batch runners'
    bodies bitwise at a fixed full population (see module docstring):
    migration -> faults -> Eq. 17 scoring -> chain round -> FL round
    (``scfg.fl``; ``plan`` is that round's :class:`~repro.fl.stream.FLPlan`
    row) -> churn -> (optional) dynamics. Each stage runs under a
    ``jax.named_scope`` (``association``, ``migration``, ``faults``,
    ``price``, ``chain``, ``fl_round``, ``churn``, ``dynamics``, ``replay``),
    which names its ops in a profiler trace. Returns ``(state', metrics)``."""
    st = state.env
    m = cfg.n_bs
    active = state.active

    # --- association + controls for this round ---
    with jax.named_scope("association"):
        if scfg.policy is not None:
            from repro.core.marl.ddpg import act

            obs = env_mod.observe(cfg, st)
            a = act(cfg, state.agent, obs, policy=scfg.policy)
            assoc_cmd, b, tau = env_mod.decode_actions(cfg, a)
            assoc_cmd = jnp.where(active, assoc_cmd, m)
            b = jnp.where(active, b, 0.0)
        else:
            obs = a = None
            assoc_cmd = st.assoc
            b = jnp.where(active, 0.5, 0.0)
            tau = jnp.full((m, cfg.wl.n_subchannels), 1.0 / m)
        up = comms.uplink_rate(cfg.wl, tau, st.h_up, st.dist)
        down = comms.downlink_rate(cfg.wl, st.h_down, st.dist)

    # --- migration (fold-3 round key; _migration_one's body) ---
    if cfg.migration is not None:
        with jax.named_scope("migration"):
            assoc = migration.migration_step(cfg.migration, keys.mig,
                                             assoc_cmd, st.data_sizes, m)
            # the kernel migrates every row; re-stamp inactive rows out of
            # range
            assoc = jnp.where(active, assoc, m)
    else:
        assoc = assoc_cmd

    # --- faults (fold-5 round key; _faults_one's body — at rate 0 the
    # slowdowns are exactly 1.0 and the gate is the identity, so one body
    # serves every axis combination bitwise) ---
    if cfg.faults is not None:
        with jax.named_scope("faults"):
            k_slow, k_out = jax.random.split(keys.fault)
            slow = faults_mod.straggler_slowdowns(cfg.faults, k_slow,
                                                  st.data_sizes.shape[0],
                                                  rate=row.straggler)
            bad = faults_mod.outage_step(cfg.faults, k_out, state.bad,
                                         rate=row.outage)
            up_eff = faults_mod.outage_gate(cfg.faults, up, bad)
            b_eff = b * slow
    else:
        slow, bad, up_eff, b_eff = None, state.bad, up, b

    # --- Eq. 17 scoring: the same max+max+block decomposition every batch
    # runner uses (latency.round_time's internal composition) ---
    with jax.named_scope("price"):
        cmp_max = jnp.max(latency.t_cmp(cfg.lat, assoc, b_eff, st.data_sizes,
                                        st.freqs))
        bc_max = jnp.max(latency.t_broadcast(cfg.lat, assoc, up_eff, m))
        if cfg.consensus is not None:
            qf = jnp.round(jnp.asarray(row.quorum,
                                       jnp.float32)).astype(jnp.int32)
            t_block = consensus_mod.consensus_time(
                cfg.lat, cfg.consensus, down, st.freqs, quorum_f=qf,
                byz_frac=row.byzantine, block_size_bits=row.block_size)
        else:
            t_block = latency.t_block_validation(cfg.lat, down, st.freqs)
        t_round = cmp_max + bc_max + t_block

    # --- chain round (fold-8 round key; _consensus_one's body) ---
    chain = st.chain
    accept = None
    if cfg.consensus is not None:
        with jax.named_scope("chain"):
            occ = latency.twin_counts(assoc, m)
            chain, _, accept = consensus_mod.chain_round(
                cfg.consensus, chain, keys.chain, state.byz, occ)

    # --- streamed FL round (``scfg.fl``): vmapped local SGD over the
    # planned participants, Eq. 4/5 + verify gate on device — trains the
    # round's PRE-churn population with the post-migration association,
    # exactly the state the latency terms above priced ---
    fl_state = state.fl
    fl_metrics = {}
    if scfg.fl is not None:
        from repro.fl import stream as fl_stream

        with jax.named_scope("fl_round"):
            fl_state, fl_metrics = fl_stream.fl_round(
                scfg.fl, state.fl, plan, active=active,
                data_sizes=st.data_sizes, assoc=assoc, n_bs=m)

    # --- churn (fold-11 round key — a fresh stream, so churn-off serving
    # consumes exactly the batch runners' draws and nothing else) ---
    pre_active = active
    data = st.data_sizes
    assoc_next = assoc
    n_joined = n_left = jnp.int32(0)
    if scfg.churns:
        with jax.named_scope("churn"):
            active, data, assoc_next, n_joined, n_left = churn_step(
                cfg, scfg, keys.churn, active, data, assoc, row)
            if scfg.fl is not None:
                from repro.fl import stream as fl_stream

                # model-buffer churn contract: admitted rows warm-start
                # from the round's NEW global model, evicted rows go to
                # padding
                fl_state = fl_stream.fl_churn_update(
                    fl_state, active & ~pre_active, pre_active & ~active)

    # --- optional between-round dynamics (fold-12 round key) ---
    env2 = st._replace(data_sizes=data, assoc=assoc_next, chain=chain,
                       t=st.t + 1)
    if scfg.evolve_channels:
        with jax.named_scope("dynamics"):
            env2 = env_mod.env_evolve(cfg, env2, keys.dyn)

    state2 = ServeState(env=env2, active=active, bad=bad, byz=state.byz,
                        agent=state.agent, buf=state.buf, fl=fl_state,
                        round=state.round + 1)

    # --- replay (policy mode): compact encodings flow through masked
    # segment reductions, so departed twins contribute zero to the row ---
    if scfg.policy is not None and state.buf is not None:
        from repro.core.marl import replay, spaces

        with jax.named_scope("replay"):
            reward = jnp.full((m,), -t_round) * cfg.reward_scale
            enc = spaces.encode_action(cfg, a, obs.twin_feats)
            s2 = spaces.compact_obs(env_mod.observe(cfg, env2))
            state2 = state2._replace(buf=replay.replay_add(
                state.buf, spaces.compact_obs(obs), enc, reward, s2))

    metrics = {"round_time": t_round,
               "n_active": sharding.twin_count(state2.active),
               "n_joined": n_joined, "n_left": n_left}
    metrics.update(fl_metrics)
    if cfg.faults is not None:
        with jax.named_scope("faults"):
            metrics["straggler_frac"] = faults_mod.straggler_frac(slow)
            metrics["outage_frac"] = jnp.mean(bad.astype(jnp.float32))
    if cfg.migration is not None:
        with jax.named_scope("migration"):
            load = assoc_mod.bs_loads(assoc, st.data_sizes, m)
            metrics["migration_rate"] = migration.migration_rate(assoc_cmd,
                                                                 assoc)
            metrics["imbalance"] = load["imbalance"]
    if cfg.consensus is not None:
        metrics["accept_frac"] = accept
        metrics["consensus_time"] = t_block
        with jax.named_scope("chain"):
            metrics["honest_stake_share"] = consensus_mod.honest_stake_share(
                chain, state.byz)
    return state2, metrics


# Donated streaming step: round t+1's ServeState is written into round t's
# buffers — the twin-axis arrays never round-trip to host (regression-tested
# by tests/test_serve.py::test_step_donates_state; replint R006 keeps every
# jit of a *round_step* donating).
_round_step_jit = jax.jit(_round_step, static_argnames=("cfg", "scfg"),
                          donate_argnums=(2,))


def serve_specs(cfg: EnvConfig,
                scfg: Optional[ServeConfig] = None) -> ServeState:
    """Partition specs for the ServeState pytree: env per
    :func:`repro.core.marl.env.env_specs`, the active mask twin-sharded,
    everything else (fault chain, byzantine mask, agent params, replay
    rows, round counter) replicated — the PR 3 compact-encoding invariant
    is what keeps the policy-mode subtrees M-sized. With an FL-enabled
    ``scfg`` the model buffers are twin-sharded on their capacity axis
    (``fl.stream.fl_specs``); the global model and datasets replicate."""
    if scfg is not None and scfg.fl is not None:
        from repro.fl.stream import fl_specs

        fl = fl_specs(scfg.fl)
    else:
        fl = P()
    return ServeState(env=env_mod.env_specs(cfg), active=P(TWIN_AXIS),
                      bad=P(), byz=P(), agent=P(), buf=P(), fl=fl,
                      round=P())


def make_round_step(cfg: EnvConfig, scfg: ServeConfig,
                    ts: Optional[TwinSharding] = None):
    """The compiled streaming step ``fn(state, keys, row) -> (state',
    metrics)``, donating ``state``. With a multi-shard ``ts`` the body runs
    under a twin scope inside ``shard_map`` (twin leaves sharded per
    :func:`serve_specs`), still donated at the outer jit. ``fn.lower``
    takes the same arguments and returns the ``jax.stages.Lowered`` step,
    for reading the compiled program."""
    if ts is None or ts.n_shards == 1:
        step = functools.partial(_round_step_jit, cfg, scfg)
        step.lower = functools.partial(_round_step_jit.lower, cfg, scfg)
        return step

    specs = serve_specs(cfg, scfg)

    def local(state, keys, row, plan=None):
        with ts.scope(cfg.n_twins):
            return _round_step(cfg, scfg, state, keys, row, plan)

    sm = ts.shard_map(local, in_specs=(specs, P(), P(), P()),
                      out_specs=(specs, P()))
    jitted = jax.jit(sm, donate_argnums=(0,))

    def step(state, keys, row, plan=None):
        return jitted(state, keys, row, plan)

    step.lower = lambda state, keys, row, plan=None: jitted.lower(
        state, keys, row, plan)
    return step


# ---------------------------------------------------------------------------
# the driver — pipelined host loop
# ---------------------------------------------------------------------------


def _row_t(rows: StreamKnobs, t: int) -> StreamKnobs:
    """Round ``t``'s knob row: rows with a leading stream axis are consumed
    one per round; scalar knobs broadcast to every round."""
    return jax.tree_util.tree_map(
        lambda x: x[t] if jnp.ndim(x) else x, rows)


@jax.jit
def _round_inputs(keys: RoundKeys, rows: StreamKnobs, plan, t):
    """Round ``t``'s step inputs as one compiled program: ``(round_keys,
    _row_t, plan_row)`` of the call's stacks (the plan's row only when a
    plan is given). ``t`` is traced, so the program compiles once per
    input shape, whatever the round; a traced index selects the same rows,
    bit for bit, as the eager ``x[t]``."""
    args = (round_keys(keys, t), _row_t(rows, t))
    if plan is None:
        return args
    from repro.fl.stream import plan_row

    return args + (plan_row(plan, t),)


@jax.jit
def _stack_rounds(out):
    """A call's per-round metric dicts, stacked leaf by leaf into
    ``(n_rounds,)`` arrays as one compiled program (one per ``n_rounds``)."""
    return {k: jnp.stack([m[k] for m in out]) for k in out[0]}


def serve_rounds(cfg: EnvConfig, scfg: ServeConfig, state: ServeState,
                 keys: RoundKeys, rows: StreamKnobs, *, step=None,
                 overlap: bool = True, ts: Optional[TwinSharding] = None,
                 plan=None):
    """Stream ``n_rounds = keys.fault.shape[0]`` rounds from ``state``.

    ``overlap=True`` (the service mode) never blocks between rounds: the
    donated step for round t+1 is dispatched while round t still executes,
    so FL aggregation of round t pipelines with latency scoring /
    association of round t+1 on device and the host only materializes
    metrics at the end. ``overlap=False`` is the oracle that blocks every
    round — bit-identical results, no pipelining. ``plan`` is a stacked
    :class:`~repro.fl.stream.FLPlan` (required when ``scfg.fl`` is set),
    consumed one row per round like ``keys``/``rows``. Returns
    ``(final_state, metrics)`` with metrics stacked (n_rounds,) device
    arrays (see :func:`stack_metrics` for host conversion).

    The host dispatches two programs a round, the input selection
    (:func:`_round_inputs`) and the step, and one more a call, the metric
    stack (:func:`_stack_rounds`); it issues no eager array op."""
    if step is None:
        step = make_round_step(cfg, scfg, ts)
    if scfg.fl is not None and plan is None:
        raise ValueError("ServeConfig.fl is set — serve_rounds needs the "
                         "stream's FLPlan (see fl.stream.stream_fl_plan)")
    n_rounds = keys.fault.shape[0]
    # a traced index clamps where an eager one raises: check the stacks here
    for x in jax.tree_util.tree_leaves((keys, rows, plan)):
        if jnp.ndim(x) and jnp.shape(x)[0] < n_rounds:
            raise ValueError(f"a stacked input holds {jnp.shape(x)[0]} rows "
                             f"for {n_rounds} rounds")

    # scalar knobs, which every round shares, bypass the input program:
    # passing arrays through a jitted call unchanged costs the host more
    # than the selection itself
    stacked = jax.tree_util.tree_map(
        lambda x: x if jnp.ndim(x) else None, rows)

    out = []
    for t in range(n_rounds):
        with jax.profiler.TraceAnnotation(SPAN_INPUTS):
            keys_t, row_t, *plan_t = _round_inputs(keys, stacked, plan,
                                                   np.int32(t))
            row_t = jax.tree_util.tree_map(
                lambda r, x: x if r is None else r, row_t, rows,
                is_leaf=lambda r: r is None)
        with jax.profiler.TraceAnnotation(SPAN_ENQUEUE):
            state, m = step(state, keys_t, row_t, *plan_t)
        if not overlap:
            state = jax.block_until_ready(state)
            m = jax.block_until_ready(m)
        out.append(m)
    with jax.profiler.TraceAnnotation(SPAN_STACK):
        return state, _stack_rounds(out)


def stack_metrics(metrics) -> dict:
    """Materialize a :func:`serve_rounds` metrics dict on the host."""
    return {k: np.asarray(v) for k, v in metrics.items()}
