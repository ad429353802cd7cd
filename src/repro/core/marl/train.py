"""Fully jitted MADDPG training loop for the DTWN environment.

The seed drove training from host Python (see ``examples/marl_allocation.py``):
one device round-trip per env step plus one per update, which caps throughput
at a few hundred steps/s and makes large-N sweeps impractical. Here the whole
rollout-and-update step — OU exploration noise, env transition, replay insert,
and the MADDPG gradient step — is fused into a single ``lax.scan`` body, so a
full training run is ONE jitted call. Metrics come back as a Python-visible
trace of (steps,) arrays.

Everything flows through the structured spaces API: actions are
``spaces.Action`` pytrees (exploration noise shares the structure), the
replay stores ``compact_obs`` rows plus the ``(M, E)`` joint-action
encoding, and the per-twin feature matrix — static across episodes because
``env_soft_reset`` keeps the population — is held once in
``TrainState.obs.twin_feats``. With the (default) factorized policy the
whole trainer state outside the env itself is therefore N-independent,
which is what lets MARL training run at N=10^4+ twins.

Multi-episode training: when ``EnvConfig.episode_len > 0`` the scan body
soft-resets the env (fresh channels/distances, same twin population) every
``episode_len`` steps via ``lax.cond`` — the replay row for the boundary
step still stores the pre-reset next state.

``benchmarks/bench_scale.py`` measures the speedup vs the host loop (>=10x on
CPU at the example's scale; larger once dispatch overhead dominates) and the
flat-vs-factorized policy scaling sweep.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import sharding
from repro.core.marl import env as env_mod
from repro.core.marl import spaces
from repro.core.marl.ddpg import DDPGConfig, MADDPGState, act, maddpg_init, \
    maddpg_update, maddpg_update_impl
from repro.core.marl.env import EnvConfig, EnvState
from repro.core.marl.ou_noise import ou_leaf_step, ou_step
from repro.core.marl.replay import Replay, replay_add, replay_init, \
    replay_sample, replay_sample_prioritized
from repro.core.marl.spaces import Action, Observation
from repro.core.sharding import TWIN_AXIS, TwinSharding


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    steps: int = 200
    warmup: int = 48            # env steps before the first gradient update
    replay_capacity: int = 2048
    sigma0: float = 0.3         # OU noise: linear decay sigma0 -> sigma_min
    sigma_min: float = 0.02
    prioritized: bool = False   # |reward|-proportional replay sampling


class TrainState(NamedTuple):
    env: EnvState
    obs: Observation
    agent: MADDPGState
    buf: Replay
    noise: Action               # OU state, same structure as the action
    key: jnp.ndarray


def _sampler(tcfg: TrainConfig):
    return replay_sample_prioritized if tcfg.prioritized else replay_sample


def _select(pred, on_true, on_false):
    """Branchless pytree select — the sharded trainer's stand-in for
    ``lax.cond`` (see the scope note in ``train_step``). ``pred`` is a
    scalar bool; both sides are already computed."""
    return jax.tree_util.tree_map(lambda a, b: jnp.where(pred, a, b),
                                  on_true, on_false)


def _stamp_carry(ts0: TrainState) -> TrainState:
    """Tag the replicated leaves of the initial scan carry for the
    replication checker (``sharding.stamp_replicated`` — value-preserving
    pmean/pmax): the checker cannot trace zero-initialized replay /
    optimizer / noise state to a collective, but the scan body returns
    those leaves psum-derived, and carry tags must match. Of the four
    twin-sharded leaves, env data_sizes/assoc and obs.twin_feats pass
    through untouched; noise.scores starts as shard-local zeros and is
    tagged varying (``sharding.stamp_varying``) like the body's output."""
    stamp = sharding.stamp_replicated
    return TrainState(
        env=ts0.env._replace(freqs=stamp(ts0.env.freqs),
                             h_up=stamp(ts0.env.h_up),
                             h_down=stamp(ts0.env.h_down),
                             dist=stamp(ts0.env.dist), t=stamp(ts0.env.t)),
        obs=Observation(bs_feats=stamp(ts0.obs.bs_feats),
                        twin_feats=ts0.obs.twin_feats),
        agent=stamp(ts0.agent),
        buf=stamp(ts0.buf),
        noise=Action(scores=sharding.stamp_varying(ts0.noise.scores),
                     b_ctl=stamp(ts0.noise.b_ctl),
                     tau=stamp(ts0.noise.tau)),
        key=stamp(ts0.key),
    )


def _ou_step(cfg: EnvConfig, noise: Action, key, sigma) -> Action:
    """OU step on the structured noise, twin-sharding aware.

    Outside a scope this is exactly ``ou_noise.ou_step``. Inside, the
    carried noise's ``scores`` leaf is shard-local (M, N_local) while the
    single-device trainer draws (M, N): to keep the sharded trainer
    bit-identical, every shard draws the *full* (M, N) normal from the same
    per-leaf key ``ou_step`` would use (Action field order: scores, b_ctl,
    tau) and slices its own columns; the dynamics themselves are the
    shared ``ou_leaf_step``. The O(M*N) draw is transient; padded columns
    get noise too, which is harmless — they are masked at decode.
    """
    if sharding.in_scope() is None:
        return ou_step(noise, key, sigma=sigma)
    k_s, k_b, k_t = jax.random.split(key, 3)
    step = functools.partial(ou_leaf_step, sigma=sigma)
    m = noise.scores.shape[0]
    eps_s = sharding.slice_local(
        jax.random.normal(k_s, (m, cfg.n_twins)), axis=1)
    return Action(
        scores=step(noise.scores, eps_s),
        b_ctl=step(noise.b_ctl, jax.random.normal(k_b, noise.b_ctl.shape)),
        tau=step(noise.tau, jax.random.normal(k_t, noise.tau.shape)))


def train_init(cfg: EnvConfig, dcfg: DDPGConfig, tcfg: TrainConfig,
               key) -> TrainState:
    """Fresh TrainState: reset env (N twins, M BS agents), stacked-agent
    MADDPG params for the configured policy, empty compact replay, OU noise
    as an all-zero Action."""
    k_env, k_agent, k_run = jax.random.split(key, 3)
    st = env_mod.env_reset(cfg, k_env)
    spec = spaces.space_spec(cfg)
    return TrainState(
        env=st,
        obs=env_mod.observe(cfg, st),
        agent=maddpg_init(cfg, dcfg, k_agent),
        buf=replay_init(tcfg.replay_capacity, spec.compact_dim, cfg.n_bs,
                        spec.enc_dim),
        noise=spaces.zeros_action(cfg),
        key=k_run,
    )


def train_step(cfg: EnvConfig, dcfg: DDPGConfig, tcfg: TrainConfig,
               ts: TrainState, i) -> tuple:
    """One fused rollout-and-update step (scan body). ``i`` is the step
    index, used for the noise schedule and the warmup gate."""
    key, k1, k2, k3, k4 = jax.random.split(ts.key, 5)
    frac = i.astype(jnp.float32) / max(tcfg.steps, 1)
    sigma = jnp.maximum(tcfg.sigma0 * (1.0 - frac), tcfg.sigma_min)
    noise = _ou_step(cfg, ts.noise, k1, sigma)
    a = spaces.clip_action(jax.tree_util.tree_map(
        jnp.add, act(cfg, ts.agent, ts.obs, policy=dcfg.policy), noise))
    env2, r, info = env_mod.env_step(cfg, ts.env, a, k2)
    obs2 = env_mod.observe(cfg, env2)
    twin_feats = ts.obs.twin_feats
    buf = replay_add(ts.buf, spaces.compact_obs(ts.obs),
                     spaces.encode_action(cfg, a, twin_feats), r,
                     spaces.compact_obs(obs2))

    def do_update(agent):
        # the un-jitted impl: under the sharded trainer this body must be
        # traced inside the twin scope (the jitted wrapper's cache is
        # blind to it); under the single-device trainer we are inside the
        # train() jit anyway, so the wrapper would only be inlined.
        new, m = maddpg_update_impl(cfg, dcfg, agent,
                                    _sampler(tcfg)(buf, k3, dcfg.batch_size),
                                    twin_feats)
        return new, m["critic_loss"], m["actor_loss"]

    def skip(agent):
        return agent, jnp.float32(0.0), jnp.float32(0.0)

    # Inside a twin scope, lax.cond cannot branch-match a psum-carrying
    # update against the constant skip (the replication checker
    # rejects the pair), so both branches run and a jnp.where selects —
    # value-identical, and the elementwise rep rule accepts mixed tags.
    # Single-device keeps the work-skipping cond.
    if sharding.in_scope() is None:
        agent, closs, aloss = jax.lax.cond(i >= tcfg.warmup, do_update,
                                           skip, ts.agent)
    else:
        agent, closs, aloss = _select(i >= tcfg.warmup, do_update(ts.agent),
                                      skip(ts.agent))

    # episode boundary: soft-reset the dynamics (same twin population) so
    # obs2 stored above is the true pre-reset next state, while the carried
    # state starts the next episode
    if cfg.episode_len > 0:
        def reset(op):
            env_b, k = op
            env_n = env_mod.env_soft_reset(cfg, env_b, k)
            return env_n, env_mod.observe(cfg, env_n)

        if sharding.in_scope() is None:
            env_next, obs_next = jax.lax.cond(
                env2.t >= cfg.episode_len, reset, lambda op: (op[0], obs2),
                (env2, k4))
        else:
            env_next, obs_next = _select(env2.t >= cfg.episode_len,
                                         reset((env2, k4)), (env2, obs2))
    else:
        env_next, obs_next = env2, obs2

    metrics = {
        "system_time": info["system_time"],
        "reward": jnp.mean(r),
        "critic_loss": closs,
        "actor_loss": aloss,
    }
    return TrainState(env=env_next, obs=obs_next, agent=agent, buf=buf,
                      noise=noise, key=key), metrics


@functools.partial(jax.jit, static_argnames=("cfg", "dcfg", "tcfg"))
def train(cfg: EnvConfig, dcfg: DDPGConfig, tcfg: TrainConfig,
          key) -> tuple:
    """Run the full training loop in one jitted lax.scan.

    Returns (final TrainState, trace) where trace is a dict of (steps,)
    arrays: system_time, reward, critic_loss, actor_loss.
    """
    ts = train_init(cfg, dcfg, tcfg, key)
    body = functools.partial(train_step, cfg, dcfg, tcfg)
    return jax.lax.scan(body, ts, jnp.arange(tcfg.steps))


def train_sharded(tsh: TwinSharding, cfg: EnvConfig, dcfg: DDPGConfig,
                  tcfg: TrainConfig, key) -> tuple:
    """:func:`train` with the twin population sharded over a device mesh.

    The whole rollout-and-update scan runs inside ONE ``shard_map`` region:
    per-shard state is the env's twin block ((N_local,) data/assoc, the
    (N_local, F) twin features, the (M, N_local) score noise); the MADDPG
    parameters, optimizer state, replay buffer, and PRNG keys are
    replicated, which the PR 3 compact encoding makes free — replay rows
    are psum'd (M, E) encodings plus compact states, never per-twin data.
    Per step the shards meet only in M-sized collectives (the segment
    reductions, pooled statistics, and gradient stamps).

    Bit-parity with :func:`train` (up to float tolerance): every PRNG draw
    a shard needs is the same *global* draw the single-device trainer makes,
    sliced locally (``sharding.slice_local``), and autodiff through the
    psums is exact under replication checking — ``tests/test_sharding.py``
    asserts trace and final-parameter parity on an 8-host-device mesh.

    Constraints: ``dcfg.policy`` must be ``"factorized"`` (the flat oracle's
    O(N) first layer would have to be gathered, defeating the sharding);
    ``tsh.n_shards == 1`` is the no-op fast path returning ``train(...)``
    unchanged. The returned TrainState carries padded twin-sharded leaves
    (global shape ``tsh.padded_n(cfg.n_twins)``); trace metrics are
    replicated (steps,) arrays exactly like :func:`train`'s.
    """
    if tsh.n_shards == 1:
        return train(cfg, dcfg, tcfg, key)
    if dcfg.policy != "factorized":
        raise ValueError(
            f"train_sharded supports the N-independent 'factorized' policy "
            f"only (got policy={dcfg.policy!r}: its parameters scale with "
            f"the twin count, so shards cannot hold replicas)")
    return _train_sharded_jitted(tsh, cfg, dcfg, tcfg)(key)


@functools.lru_cache(maxsize=None)
def _train_sharded_jitted(tsh: TwinSharding, cfg: EnvConfig,
                          dcfg: DDPGConfig, tcfg: TrainConfig):
    """Compiled sharded-train callable per (mesh, configs) — cached so
    repeated calls (sweeps, reruns with fresh keys) hit one jit program
    instead of retracing a new closure every time. All four keys are
    hashable frozen dataclasses."""

    def local(k):
        with tsh.scope(cfg.n_twins):
            ts0 = _stamp_carry(train_init(cfg, dcfg, tcfg, k))
            body = functools.partial(train_step, cfg, dcfg, tcfg)
            return jax.lax.scan(body, ts0, jnp.arange(tcfg.steps))

    P = jax.sharding.PartitionSpec
    state_specs = TrainState(
        env=env_mod.env_specs(cfg),
        obs=Observation(bs_feats=P(), twin_feats=P(TWIN_AXIS)),
        agent=P(),                       # whole MADDPG subtree replicated
        buf=P(),                         # replay is shard-free
        noise=Action(scores=P(None, TWIN_AXIS), b_ctl=P(), tau=P()),
        key=P(),
    )
    return jax.jit(tsh.shard_map(local, in_specs=(P(),),
                                 out_specs=(state_specs, P())))


def train_host_loop(cfg: EnvConfig, dcfg: DDPGConfig, tcfg: TrainConfig,
                    key, *, on_step=None) -> TrainState:
    """The seed's host-driven loop — same schedule as ``train`` but one
    device round-trip per env step and per update. Kept as the reference
    baseline (``benchmarks/bench_scale.py`` measures the gap) and for
    step-by-step debugging. ``on_step(i, info)`` is called after every env
    transition with the step's info dict."""
    ts = train_init(cfg, dcfg, tcfg, key)
    st, obs, agent, buf, noise, key = ts
    twin_feats = obs.twin_feats
    step_jit = jax.jit(lambda s, a, k: env_mod.env_step(cfg, s, a, k))
    act_jit = jax.jit(lambda ag, o: act(cfg, ag, o, policy=dcfg.policy))
    for i in range(tcfg.steps):
        key, k1, k2, k3, k4 = jax.random.split(key, 5)
        sigma = max(tcfg.sigma0 * (1 - i / max(tcfg.steps, 1)),
                    tcfg.sigma_min)
        noise = ou_step(noise, k1, sigma=sigma)
        a = spaces.clip_action(jax.tree_util.tree_map(
            jnp.add, act_jit(agent, obs), noise))
        st, r, info = step_jit(st, a, k2)
        obs2 = env_mod.observe(cfg, st)
        buf = replay_add(buf, spaces.compact_obs(obs),
                         spaces.encode_action(cfg, a, twin_feats), r,
                         spaces.compact_obs(obs2))
        obs = obs2
        if i >= tcfg.warmup:
            agent, _ = maddpg_update(
                cfg, dcfg, agent, _sampler(tcfg)(buf, k3, dcfg.batch_size),
                twin_feats)
        if cfg.episode_len > 0 and int(st.t) >= cfg.episode_len:
            st = env_mod.env_soft_reset(cfg, st, k4)
            obs = env_mod.observe(cfg, st)
        if on_step is not None:
            on_step(i, info)
    return TrainState(env=st, obs=obs, agent=agent, buf=buf, noise=noise,
                      key=key)
