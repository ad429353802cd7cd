"""Always-on serving tests (repro.core.serve): churn invariants
(hypothesis-fuzzed when installed; a deterministic grid always runs),
streaming-vs-batch bit parity per workload axis, donation regressions
(donated buffers die, live-buffer census stays flat), the FL-substrate
churn bridge (``run_round(active=...)``), and the slow battery — the
8-device ``bench_scale --serve-gate`` subprocess and a >= 20-round churn
soak with per-round mask accounting.
"""
import gc
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import association, scenario, serve, sharding
from repro.core.consensus import ConsensusConfig
from repro.core.faults import FaultConfig
from repro.core.marl import env as env_mod
from repro.core.marl.env import EnvConfig
from repro.core.migration import MigrationConfig

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st

    HAS_HYPOTHESIS = True
    SET = settings(max_examples=25, deadline=None)
except ImportError:  # hypothesis is optional in this environment
    HAS_HYPOTHESIS = False

KEY = jax.random.PRNGKey(0)
ROOT = os.path.join(os.path.dirname(__file__), "..")
SRC = os.path.join(ROOT, "src")


def _batch(n=3, **axes):
    return scenario.make_batch(KEY, n, **axes)


def _stream(cfg, scfg, row_key, row, k, *, n_live=None, overlap=False):
    state = serve.serve_init(cfg, scfg, row_key, row, n_live=n_live)
    keys = serve.stream_keys(row_key, k)
    state, m = serve.serve_rounds(cfg, scfg, state, keys, row,
                                  overlap=overlap)
    return state, serve.stack_metrics(m)


# ---------------------------------------------------------------------------
# churn primitives: admit / evict invariants
# ---------------------------------------------------------------------------


def _rand_churn_case(seed: int, n: int, m: int):
    rng = np.random.default_rng(seed)
    active = rng.random(n) < 0.6
    data = np.where(active, rng.uniform(100.0, 1500.0, n), 0.0)
    data = data.astype(np.float32)
    assoc = np.where(active, rng.integers(0, m, n), m).astype(np.int32)
    leave = rng.random(n) < 0.3
    join = rng.random(n) < 0.3
    new_data = rng.uniform(100.0, 1500.0, n).astype(np.float32)
    new_assoc = rng.integers(0, m, n).astype(np.int32)
    return active, data, assoc, leave, join, new_data, new_assoc


def _check_churn_case(active, data, assoc, leave, join, new_data, new_assoc,
                      m: int):
    a1, d1, s1 = serve.evict(jnp.asarray(active), jnp.asarray(data),
                             jnp.asarray(assoc), jnp.asarray(leave), m)
    left = np.asarray(leave) & np.asarray(active)
    # conservation: evict removes exactly the live departures
    assert int(np.sum(np.asarray(a1))) == int(active.sum() - left.sum())
    # padding convention on departed rows: out of every segment reduction
    np.testing.assert_array_equal(np.asarray(d1)[left], 0.0)
    np.testing.assert_array_equal(np.asarray(s1)[left], m)
    # survivors untouched
    keep = np.asarray(active) & ~left
    np.testing.assert_array_equal(np.asarray(d1)[keep], data[keep])
    np.testing.assert_array_equal(np.asarray(s1)[keep], assoc[keep])

    a2, d2, s2 = serve.admit(a1, d1, s1, jnp.asarray(join),
                             jnp.asarray(new_data), jnp.asarray(new_assoc))
    joined = np.asarray(join) & ~np.asarray(a1)
    assert int(np.sum(np.asarray(a2))) == \
        int(np.sum(np.asarray(a1)) + joined.sum())
    np.testing.assert_array_equal(np.asarray(d2)[joined], new_data[joined])
    np.testing.assert_array_equal(np.asarray(s2)[joined], new_assoc[joined])
    # every live row has an in-range association; every dead row is padded
    a2_np, s2_np, d2_np = map(np.asarray, (a2, s2, d2))
    assert (s2_np[a2_np] < m).all() and (s2_np[a2_np] >= 0).all()
    np.testing.assert_array_equal(s2_np[~a2_np], m)
    np.testing.assert_array_equal(d2_np[~a2_np], 0.0)


def test_admit_evict_invariants_grid():
    for seed in range(8):
        _check_churn_case(*_rand_churn_case(seed, 64, 5), m=5)
    # degenerate cases: everyone leaves / everyone joins / no-ops
    n, m = 16, 3
    active = np.ones(n, bool)
    data = np.full(n, 500.0, np.float32)
    assoc = (np.arange(n) % m).astype(np.int32)
    _check_churn_case(active, data, assoc, np.ones(n, bool),
                      np.zeros(n, bool), data, assoc, m=m)
    _check_churn_case(~active, np.zeros(n, np.float32),
                      np.full(n, m, np.int32),
                      np.zeros(n, bool), np.ones(n, bool), data, assoc, m=m)


if HAS_HYPOTHESIS:

    @SET
    @given(seed=st.integers(0, 2**31 - 1), n=st.integers(1, 200),
           m=st.integers(1, 8))
    def test_admit_evict_invariants_fuzz(seed, n, m):
        _check_churn_case(*_rand_churn_case(seed, n, m), m=m)


def test_evicted_rows_vanish_from_reductions():
    """An evicted row contributes zero to bs_sum / twin_sum / Eq. 4 weight
    denominators — numerically identical to a population that never held
    the twin."""
    active, data, assoc, leave, *_ = _rand_churn_case(3, 128, 5)
    a1, d1, s1 = serve.evict(jnp.asarray(active), jnp.asarray(data),
                             jnp.asarray(assoc), jnp.asarray(leave), 5)
    alive = np.asarray(a1)
    # Eq. 4 weight mass per BS == the sum over surviving twins only
    got = np.asarray(association.bs_loads(s1, d1, 5)["loads"])
    want = np.zeros(5)
    for j, (s, d) in enumerate(zip(np.asarray(s1), np.asarray(d1))):
        if alive[j]:
            want[int(s)] += d
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert float(jnp.sum(d1)) == pytest.approx(float(data[alive].sum()))


def test_churn_step_accounting_and_determinism():
    cfg = EnvConfig(n_twins=64, n_bs=5)
    scfg = serve.ServeConfig(capacity=64, join_rate=0.3, leave_rate=0.3)
    row = scenario.knob_row(scenario.stream_knobs(_batch()), 0)
    rng = np.random.default_rng(0)
    active = jnp.asarray(rng.random(64) < 0.5)
    data = jnp.where(active, 500.0, 0.0)
    assoc = jnp.where(active, jnp.arange(64) % 5, 5)
    out1 = serve.churn_step(cfg, scfg, jax.random.fold_in(KEY, 1), active,
                            data, assoc, row)
    out2 = serve.churn_step(cfg, scfg, jax.random.fold_in(KEY, 1), active,
                            data, assoc, row)
    for x, y in zip(out1, out2):  # same key -> bit-identical churn
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    a2, d2, s2, nj, nl = out1
    assert int(jnp.sum(a2)) == int(jnp.sum(active)) + int(nj) - int(nl)
    # admitted populations follow the round's scenario knobs
    joined = np.asarray(a2) & ~np.asarray(active)
    if joined.any():
        d = np.asarray(d2)[joined]
        assert (d >= float(row.data_min) - 1e-6).all()
        assert (d <= float(row.data_max) + 1e-6).all()


def test_admitted_twins_enter_next_round_association():
    """A twin admitted in round t carries a live in-range association and
    is scored by round t+1's latency pass (n_active reflects it)."""
    cfg = EnvConfig(n_twins=64, n_bs=5)
    scfg = serve.ServeConfig(capacity=64, join_rate=1.0, leave_rate=0.0)
    batch = _batch()
    row = scenario.knob_row(scenario.stream_knobs(batch), 0)
    state = serve.serve_init(cfg, scfg, batch.key[0], row, n_live=16)
    keys = serve.stream_keys(batch.key[0], 2)
    step = serve.make_round_step(cfg, scfg)
    state, m0 = step(state, serve.round_keys(keys, 0), row)
    # join_rate=1 fills every empty slot in one round
    assert int(m0["n_active"]) == 64 and int(m0["n_joined"]) == 48
    assoc = np.asarray(state.env.assoc)
    act = np.asarray(state.active)
    assert act.all() and (assoc >= 0).all() and (assoc < 5).all()
    _, m1 = step(state, serve.round_keys(keys, 1), row)
    assert int(m1["n_active"]) == 64
    assert np.isfinite(float(m1["round_time"]))


def test_departed_twins_vanish_from_observation_and_replay_row():
    """Compact observations (the replay's sampling substrate) flow through
    masked segment reductions, so a post-evict state encodes identically
    to one where the departed twins never existed."""
    cfg = EnvConfig(n_twins=32, n_bs=4)
    batch = _batch()
    row = scenario.knob_row(scenario.stream_knobs(batch), 0)
    scfg = serve.ServeConfig(capacity=32)
    full = serve.serve_init(cfg, scfg, batch.key[0], row)
    # evict the tail [20, 32) from the full state ...
    leave = jnp.arange(32) >= 20
    a1, d1, s1 = serve.evict(full.active, full.env.data_sizes,
                             full.env.assoc, leave, 4)
    evicted_env = full.env._replace(data_sizes=d1, assoc=s1)
    # ... versus a state initialized with the tail never live
    fresh = serve.serve_init(cfg, scfg, batch.key[0], row, n_live=20)
    from repro.core.marl import spaces

    row_evicted = spaces.compact_obs(env_mod.observe(cfg, evicted_env))
    row_fresh = spaces.compact_obs(env_mod.observe(cfg, fresh.env))
    np.testing.assert_allclose(np.asarray(row_evicted),
                               np.asarray(row_fresh), rtol=1e-6)


# ---------------------------------------------------------------------------
# streaming-vs-batch parity (fixed full population, churn off)
# ---------------------------------------------------------------------------

_PARITY_AXES = ["baseline", "faults", "migration", "consensus"]


def _axis_cfg(axis: str, n: int = 64, m: int = 5) -> EnvConfig:
    return EnvConfig(
        n_twins=n, n_bs=m,
        faults=FaultConfig(0.3, 0.2, 0.25) if axis == "faults" else None,
        migration=MigrationConfig(0.4, 1.5, 0.8)
        if axis == "migration" else None,
        consensus=ConsensusConfig(quorum_f=1) if axis == "consensus"
        else None)


@pytest.mark.parametrize("axis", _PARITY_AXES)
def test_streaming_matches_batch_bitwise(axis):
    """K streamed rounds at fixed population == the batch runner on the
    same scenario row, bit for bit (same folds, same composition)."""
    k, i = 5, 1
    batch = _batch(straggler=(0.1, 0.4), outage=(0.05, 0.3),
                   byzantine=(0.0, 0.4), quorum=(0.0, 2.0),
                   block_size=(1e6, 8e6))
    cfg = _axis_cfg(axis)
    knobs = scenario.stream_knobs(batch, fcfg=cfg.faults, ccfg=cfg.consensus,
                                  lat=cfg.lat)
    row = scenario.knob_row(knobs, i)
    _, m = _stream(cfg, serve.ServeConfig(capacity=64), batch.key[i], row, k)

    if axis == "baseline":
        ref = scenario.run_baselines(cfg, batch)
        np.testing.assert_array_equal(
            m["round_time"], np.full(k, np.asarray(ref["average"])[i]))
    elif axis == "faults":
        ref = scenario.run_faults(cfg, cfg.faults, batch, n_rounds=k)
        np.testing.assert_array_equal(m["round_time"],
                                      np.asarray(ref["round_times"])[i])
        np.testing.assert_array_equal(m["straggler_frac"],
                                      np.asarray(ref["straggler_frac"])[i])
        np.testing.assert_array_equal(m["outage_frac"],
                                      np.asarray(ref["outage_frac"])[i])
    elif axis == "migration":
        ref = scenario.run_migration(cfg, cfg.migration, batch, n_rounds=k)
        np.testing.assert_array_equal(m["round_time"],
                                      np.asarray(ref["round_times"])[i])
        np.testing.assert_array_equal(m["migration_rate"],
                                      np.asarray(ref["migration_rates"])[i])
        # imbalance crosses a vmap-vs-streaming segment-reduction boundary
        # (different summation order, same draws) — tight tolerance, not
        # bitwise, matching the repo's cross-program float precedent
        np.testing.assert_allclose(m["imbalance"],
                                   np.asarray(ref["imbalance"])[i],
                                   rtol=1e-6)
    else:
        ref = scenario.run_consensus(cfg, cfg.consensus, batch, n_rounds=k)
        np.testing.assert_array_equal(m["round_time"],
                                      np.asarray(ref["round_times"])[i])
        np.testing.assert_array_equal(m["accept_frac"],
                                      np.asarray(ref["accept_frac"])[i])
        np.testing.assert_array_equal(
            m["consensus_time"],
            np.full(k, np.asarray(ref["consensus_time"])[i]))
        np.testing.assert_array_equal(
            m["honest_stake_share"][-1],
            np.asarray(ref["honest_stake_share"])[i])


def test_overlap_matches_blocking_oracle():
    """Pipelined dispatch (overlap=True) is a scheduling change only —
    values are bit-identical to the block-every-round oracle."""
    batch = _batch(straggler=(0.1, 0.4), outage=(0.05, 0.3))
    cfg = _axis_cfg("faults")
    scfg = serve.ServeConfig(capacity=64, join_rate=0.1, leave_rate=0.1)
    knobs = scenario.stream_knobs(batch, fcfg=cfg.faults)
    row = scenario.knob_row(knobs, 1)
    _, m_pipe = _stream(cfg, scfg, batch.key[1], row, 6, overlap=True)
    _, m_block = _stream(cfg, scfg, batch.key[1], row, 6, overlap=False)
    assert m_pipe.keys() == m_block.keys()
    for key in m_pipe:
        np.testing.assert_array_equal(m_pipe[key], m_block[key])


def test_stream_knobs_match_batch_axes():
    """StreamKnobs are the batch's per-scenario axes verbatim (config
    defaults filled exactly the way the batch runners fill them)."""
    batch = _batch(straggler=(0.1, 0.4), outage=(0.05, 0.3))
    fcfg = FaultConfig(0.3, 0.2, 0.25)
    knobs = scenario.stream_knobs(batch, fcfg=fcfg)
    np.testing.assert_array_equal(np.asarray(knobs.straggler),
                                  np.asarray(batch.straggler))
    np.testing.assert_array_equal(np.asarray(knobs.data_min),
                                  np.asarray(batch.data_min))
    clean = _batch()
    k2 = scenario.stream_knobs(clean, fcfg=fcfg)
    np.testing.assert_array_equal(np.asarray(k2.straggler),
                                  np.full(3, fcfg.straggler_rate,
                                          np.float32))
    k3 = scenario.stream_knobs(clean)
    np.testing.assert_array_equal(np.asarray(k3.straggler), np.zeros(3))


# ---------------------------------------------------------------------------
# donation regressions
# ---------------------------------------------------------------------------


def test_step_donates_state():
    """The compiled round step consumes its state argument: the donated
    buffers are deleted and any host read raises."""
    batch = _batch()
    cfg = _axis_cfg("baseline")
    scfg = serve.ServeConfig(capacity=64)
    row = scenario.knob_row(scenario.stream_knobs(batch), 0)
    state = serve.serve_init(cfg, scfg, batch.key[0], row)
    step = serve.make_round_step(cfg, scfg)
    keys = serve.stream_keys(batch.key[0], 1)
    state2, _ = step(state, serve.round_keys(keys, 0), row)
    assert state.env.h_up.is_deleted()
    assert state.env.data_sizes.is_deleted()
    with pytest.raises(RuntimeError, match="deleted"):
        np.asarray(state.env.h_up)
    assert not state2.env.h_up.is_deleted()


def test_streaming_live_buffer_census_flat():
    """No device-buffer leak across rounds: with metrics materialized each
    round, the live-array census after round 3 equals the census after
    round 12 — the donated state reuses its buffers instead of allocating
    a fresh N-sized set per round."""
    batch = _batch()
    cfg = _axis_cfg("baseline")
    scfg = serve.ServeConfig(capacity=64, join_rate=0.1, leave_rate=0.1)
    row = scenario.knob_row(scenario.stream_knobs(batch), 0)
    state = serve.serve_init(cfg, scfg, batch.key[0], row)
    step = serve.make_round_step(cfg, scfg)
    keys = serve.stream_keys(batch.key[0], 12)

    def census():
        gc.collect()
        return len(jax.live_arrays())

    counts = []
    for t in range(12):
        state, m = step(state, serve.round_keys(keys, t), row)
        _ = {k: np.asarray(v) for k, v in m.items()}  # materialize + drop
        del m
        if t >= 3:
            counts.append(census())
    assert len(set(counts)) == 1, counts


def test_round_step_rejects_reuse_of_donated_state():
    """Feeding an already-donated state back into the step raises — the
    canonical misuse the serve_rounds driver makes impossible."""
    batch = _batch()
    cfg = _axis_cfg("baseline")
    scfg = serve.ServeConfig(capacity=64)
    row = scenario.knob_row(scenario.stream_knobs(batch), 0)
    state = serve.serve_init(cfg, scfg, batch.key[0], row)
    step = serve.make_round_step(cfg, scfg)
    keys = serve.stream_keys(batch.key[0], 1)
    step(state, serve.round_keys(keys, 0), row)
    with pytest.raises((RuntimeError, ValueError), match="delet|donat"):
        jax.block_until_ready(step(state, serve.round_keys(keys, 0), row))


# ---------------------------------------------------------------------------
# FL-substrate churn bridge
# ---------------------------------------------------------------------------


def test_run_round_active_mask_excludes_departed():
    from repro.data import cifar10
    from repro.fl.server import DTWNSystem, FLConfig

    data = cifar10.load(max_train=1000, max_test=256)
    cfg = FLConfig(n_users=12, n_bs=3, bs_freqs_ghz=(2.6, 1.8, 3.6),
                   local_iters=1, batch_size=16)
    system = DTWNSystem(cfg, data, seed=0)
    active = np.ones(12, bool)
    active[7:] = False
    assoc = np.arange(12) % 3
    out = system.run_round(assoc, participating_users=8, active=active)
    # only live twins can be sampled for Eq. 4 training
    assert set(out["chosen"]) <= set(range(7))
    # latency accounting at a reduced population is finite and cheaper
    # than (or equal to) the full-population round with the same draws
    system2 = DTWNSystem(cfg, data, seed=0)
    out_full = system2.run_round(assoc, participating_users=8)
    assert 0.0 < out["round_time_s"] <= out_full["round_time_s"] + 1e-6


# ---------------------------------------------------------------------------
# streamed-FL battery: batch parity, churn contract, donation
# ---------------------------------------------------------------------------


def _fl_batch_system(fcfg, data, n=16, m=3, seed=0):
    """The batch-mode reference: a DTWNSystem whose knobs mirror ``fcfg``
    (chain gate tolerance included — both gates must accept the same
    honest submissions for trajectory parity)."""
    from repro.fl.server import DTWNSystem, FLConfig

    cfg = FLConfig(n_users=n, n_bs=m, bs_freqs_ghz=(2.6, 1.8, 3.6),
                   local_iters=fcfg.local_iters, batch_size=fcfg.batch_size,
                   lr=fcfg.lr, weighted_global=fcfg.weighted_global,
                   consensus=ConsensusConfig(tolerance=fcfg.tolerance))
    return DTWNSystem(cfg, data, seed=seed)


def _fl_streamed(fcfg, system, data, assoc, rounds, *, overlap=False,
                 join=0.0, leave=0.0, n_live=None, seed_key=None):
    """K streamed FL rounds on the SAME realization the batch system
    trains (attach_fl bridges model init, shards, D_j, association)."""
    from repro.fl import stream as fls

    n, m = system.cfg.n_users, system.cfg.n_bs
    cfg = EnvConfig(n_twins=n, n_bs=m)
    scfg = serve.ServeConfig(capacity=n, join_rate=join, leave_rate=leave,
                             fl=fcfg)
    key = KEY if seed_key is None else seed_key
    batch = scenario.make_batch(key, 2)
    row = scenario.knob_row(scenario.stream_knobs(batch), 0)
    state = serve.serve_init(cfg, scfg, batch.key[0], row, n_live=n_live)
    state = fls.attach_fl(scfg, state, system, data, assoc=assoc)
    plan = fls.stream_fl_plan(fcfg, system.shards, rounds, seed=0)
    keys = serve.stream_keys(batch.key[0], rounds)
    state, metrics = serve.serve_rounds(cfg, scfg, state, keys, row,
                                        overlap=overlap, plan=plan)
    return state, serve.stack_metrics(metrics), plan


def test_streamed_fl_matches_batch_rounds():
    """Fixed full population, churn off: the streamed FL rounds ARE the
    batch ``run_round`` trajectory — same participants, bit-identical
    Eq. 4 weights (integer-valued D_j), and the loss/params trajectory
    equal up to vmap conv-batching float error."""
    from repro.data import cifar10
    from repro.fl import stream as fls
    from repro.models import cnn

    n, m, rounds = 16, 3, 3
    data = cifar10.load(max_train=2000, max_test=512)
    fcfg = fls.FLServeConfig(model="cnn", participants=5, local_iters=2,
                             batch_size=8, tolerance=25.0)
    system = _fl_batch_system(fcfg, data, n=n, m=m)
    assoc = np.arange(n) % m
    _, mtr, plan = _fl_streamed(fcfg, system, data, assoc, rounds)

    eval_batch = {"images": jnp.asarray(system.x_test[:fcfg.n_eval]),
                  "labels": jnp.asarray(system.y_test[:fcfg.n_eval])}
    users = np.asarray(plan.users)
    for t in range(rounds):
        info = system.run_round(assoc,
                                participating_users=fcfg.participants)
        # same participants, in the same draw order
        np.testing.assert_array_equal(users[t], np.asarray(info["chosen"]))
        # bit-identical Eq. 4 weights: integer-valued D_j sum exactly
        w_ref = np.zeros(m, np.float32)
        for u in info["chosen"]:
            w_ref[assoc[u]] += np.float32(system.data_sizes[u])
        np.testing.assert_array_equal(mtr["fl_bs_weight"][t], w_ref)
        # both gates accept every honest submission
        assert info["n_verified"] == info["n_submitted"]
        assert mtr["fl_accept_frac"][t] == 1.0
        # loss trajectory on the shared fixed holdout slice (allclose, not
        # bitwise: vmap lowers the P local trainings to grouped convs)
        loss_ref = float(cnn.loss_fn(system.params, eval_batch))
        np.testing.assert_allclose(mtr["fl_loss"][t], loss_ref, rtol=1e-5)
    assert mtr["fl_loss"][-1] < mtr["fl_loss"][0]


def _fl_tiny_setup(fcfg, n, m, rounds, *, join=0.0, leave=0.0, n_live=None,
                   row_i=0, max_train=1000, malicious=None):
    """Streamed-FL fixture on the ``tiny`` model (no batch pairing): serve
    state + warm-started FL state + plan over IID shards."""
    from repro.data import cifar10
    from repro.fl import stream as fls
    from repro.fl.partition import iid_partition

    data = cifar10.load(max_train=max_train, max_test=256)
    cfg = EnvConfig(n_twins=n, n_bs=m)
    scfg = serve.ServeConfig(capacity=n, join_rate=join, leave_rate=leave,
                             fl=fcfg)
    batch = scenario.make_batch(KEY, 2)
    row = scenario.knob_row(scenario.stream_knobs(batch), row_i)
    state = serve.serve_init(cfg, scfg, batch.key[row_i], row,
                             n_live=n_live)
    fl = fls.fl_init(fcfg, jax.random.PRNGKey(7), data,
                     np.asarray(state.active, bool), malicious=malicious)
    state = state._replace(fl=fl)
    plan = fls.stream_fl_plan(fcfg, iid_partition(max_train, n, seed=3),
                              rounds, seed=0)
    keys = serve.stream_keys(batch.key[row_i], rounds)
    return cfg, scfg, state, row, plan, keys


def test_streamed_fl_churn_contract():
    """Churn on: evicted twins' model rows go to the padding convention
    (all-zero, never re-aggregated), admitted twins warm-start from the
    round's new global model with zero momentum, and idle live rows are
    untouched. Overlap mode changes none of it."""
    from repro.fl import stream as fls

    n, m, rounds = 16, 3, 6
    fcfg = fls.FLServeConfig(model="tiny", participants=4, local_iters=1,
                             batch_size=8, verify=False)
    cfg, scfg, state, row, plan, keys = _fl_tiny_setup(
        fcfg, n, m, rounds, join=0.4, leave=0.3, n_live=10, row_i=1)
    step = serve.make_round_step(cfg, scfg)

    prev_active = np.asarray(state.active, bool)
    for t in range(rounds):
        prev_tp = np.array(state.fl.twin_params["w1"])
        state, mtr = step(state, serve.round_keys(keys, t), row,
                          fls.plan_row(plan, t))
        state = jax.block_until_ready(state)
        act = np.asarray(state.active, bool)
        g = np.array(state.fl.params["w1"])
        tp = np.array(state.fl.twin_params["w1"])
        mom = np.array(state.fl.twin_mom["w1"])
        joined = act & ~prev_active
        # padding convention on every dead row (evicted or never-admitted)
        assert (tp[~act] == 0.0).all() and (mom[~act] == 0.0).all()
        # admitted rows warm-start from the round's NEW global model
        np.testing.assert_array_equal(
            tp[joined], np.broadcast_to(g, (int(joined.sum()),) + g.shape))
        assert (mom[joined] == 0.0).all()
        # surviving idle rows untouched
        part = set(np.asarray(fls.plan_row(plan, t).users).tolist())
        idle = act & prev_active & ~np.isin(np.arange(n), list(part))
        np.testing.assert_array_equal(tp[idle], prev_tp[idle])
        assert np.isfinite(float(mtr["fl_loss"]))
        prev_active = act

    # overlap is a scheduling change only, FL metrics included
    def rerun(overlap):
        cfg2, scfg2, st, row2, plan2, keys2 = _fl_tiny_setup(
            fcfg, n, m, 4, join=0.2, leave=0.2, n_live=12, row_i=1)
        _, mtr = serve.serve_rounds(cfg2, scfg2, st, keys2, row2,
                                    overlap=overlap, plan=plan2)
        return serve.stack_metrics(mtr)

    m_pipe, m_block = rerun(True), rerun(False)
    assert m_pipe.keys() == m_block.keys()
    for key in m_pipe:
        np.testing.assert_array_equal(m_pipe[key], m_block[key])


def test_fl_step_donates_model_buffers():
    """The donation census extends to the FL model buffers: per-twin
    params/momentum, the global model, and the datasets all ride the
    donated ServeState."""
    from repro.fl import stream as fls

    fcfg = fls.FLServeConfig(model="tiny", participants=4, local_iters=1,
                             batch_size=8, verify=False)
    cfg, scfg, state, row, plan, keys = _fl_tiny_setup(fcfg, 16, 3, 1,
                                                       max_train=500)
    step = serve.make_round_step(cfg, scfg)
    state2, _ = step(state, serve.round_keys(keys, 0), row,
                     fls.plan_row(plan, 0))
    jax.block_until_ready(state2)
    assert state.fl.twin_params["w1"].is_deleted()
    assert state.fl.twin_mom["w1"].is_deleted()
    assert state.fl.params["w1"].is_deleted()
    assert state.fl.x.is_deleted()
    assert not state2.fl.twin_params["w1"].is_deleted()


def test_fl_streaming_census_flat():
    """No device-buffer leak with the FL workload on: the live-array
    census is flat from round 3 on — model buffers reuse their donated
    storage instead of allocating a fresh capacity-sized set per round."""
    from repro.fl import stream as fls

    rounds = 10
    fcfg = fls.FLServeConfig(model="tiny", participants=4, local_iters=1,
                             batch_size=8, verify=False)
    cfg, scfg, state, row, plan, keys = _fl_tiny_setup(
        fcfg, 16, 3, rounds, join=0.1, leave=0.1, max_train=500)
    step = serve.make_round_step(cfg, scfg)

    def census():
        gc.collect()
        return len(jax.live_arrays())

    counts = []
    for t in range(rounds):
        state, mtr = step(state, serve.round_keys(keys, t), row,
                          fls.plan_row(plan, t))
        _ = {k: np.array(v) for k, v in mtr.items()}
        del mtr
        if t >= 3:
            counts.append(census())
    assert len(set(counts)) == 1, counts


def test_fl_verify_gate_rejects_poisoned_bs():
    """A boosted model-replacement cohort saturating one BS fails the
    on-device loss gate (Eq. 4 verify): its submission is rejected while
    the honest BSs keep aggregating."""
    from repro.fl import stream as fls

    n, m, rounds = 16, 3, 4
    fcfg = fls.FLServeConfig(model="tiny", participants=8, local_iters=2,
                             batch_size=8, attack="model_replacement",
                             attack_boost=50.0, verify=True, tolerance=0.5)
    cfg, scfg, state, row, plan, keys = _fl_tiny_setup(fcfg, n, m, rounds)
    assoc = np.asarray(state.env.assoc)
    mal = assoc == assoc[0]  # one BS's whole cohort is hostile
    assert 0 < mal.sum() < n
    state = state._replace(fl=state.fl._replace(
        malicious=jnp.asarray(mal)))
    _, mtr = serve.serve_rounds(cfg, scfg, state, keys, row, overlap=False,
                                plan=plan)
    mtr = serve.stack_metrics(mtr)
    assert np.isfinite(mtr["fl_loss"]).all()
    # the gate fires: some round rejects a submission
    assert (mtr["fl_accept_frac"] < 1.0).any(), mtr["fl_accept_frac"]
    # and the surviving global model is not the boosted garbage
    assert mtr["fl_loss"][-1] < 10.0


# ---------------------------------------------------------------------------
# the host loop's compiled input selection and metric stack
# ---------------------------------------------------------------------------


def _loop_case(fl: bool, rounds: int):
    """A fresh 16-twin churning stream: ``(cfg, scfg, state, keys, rows,
    plan)``. With ``fl`` the tiny model streams (scalar knob row, a plan);
    without, faults and migration run on a knob stack consumed one row per
    round, and there is no plan."""
    if fl:
        from repro.fl import stream as fls

        fcfg = fls.FLServeConfig(model="tiny", participants=4,
                                 local_iters=1, batch_size=8, verify=False)
        cfg, scfg, state, row, plan, keys = _fl_tiny_setup(
            fcfg, 16, 3, rounds, join=0.2, leave=0.2, n_live=12,
            max_train=500)
        return cfg, scfg, state, keys, row, plan
    cfg = EnvConfig(n_twins=16, n_bs=3, faults=FaultConfig(0.3, 0.2, 0.25),
                    migration=MigrationConfig(0.4, 1.5, 0.8))
    scfg = serve.ServeConfig(capacity=16, join_rate=0.2, leave_rate=0.2)
    batch = _batch(rounds, straggler=(0.1, 0.4), outage=(0.05, 0.3))
    rows = scenario.stream_knobs(batch, fcfg=cfg.faults)
    state = serve.serve_init(cfg, scfg, batch.key[0],
                             scenario.knob_row(rows, 0), n_live=12)
    keys = serve.stream_keys(batch.key[0], rounds)
    return cfg, scfg, state, keys, rows, None


@pytest.mark.parametrize("overlap", [True, False])
@pytest.mark.parametrize("fl", [True, False], ids=["plan", "no_plan"])
def test_serve_rounds_matches_eager_hand_loop(fl, overlap):
    """``serve_rounds``' compiled input selection and metric stack give,
    bit for bit, the final state and metrics of a hand loop that feeds the
    same step eagerly sliced ``round_keys`` / ``_row_t`` / ``plan_row``
    rows."""
    from repro.fl import stream as fls

    rounds = 3
    cfg, scfg, state, keys, rows, plan = _loop_case(fl, rounds)
    step = serve.make_round_step(cfg, scfg)
    got_state, got = serve.serve_rounds(cfg, scfg, state, keys, rows,
                                        step=step, overlap=overlap,
                                        plan=plan)
    got = serve.stack_metrics(got)

    *_, state, keys, rows, plan = _loop_case(fl, rounds)
    out = []
    for t in range(rounds):
        args = (serve.round_keys(keys, t), serve._row_t(rows, t))
        if plan is not None:
            args += (fls.plan_row(plan, t),)
        state, m = step(state, *args)
        out.append({k: np.asarray(v) for k, v in m.items()})

    assert got.keys() == out[0].keys()
    for k in got:
        np.testing.assert_array_equal(got[k], np.stack([m[k] for m in out]))
    got_leaves, got_tree = jax.tree_util.tree_flatten(got_state)
    want_leaves, want_tree = jax.tree_util.tree_flatten(state)
    assert got_tree == want_tree
    for a, b in zip(got_leaves, want_leaves):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_serve_rounds_rejects_short_stacks():
    """A knob stack or plan with fewer rows than the call's rounds is
    refused before any dispatch (a traced index would clamp silently)."""
    cfg, scfg, state, keys, rows, _ = _loop_case(False, 3)
    short = jax.tree_util.tree_map(lambda x: x[:2], rows)
    with pytest.raises(ValueError, match="2 rows for 3 rounds"):
        serve.serve_rounds(cfg, scfg, state, keys, short)
    assert not state.active.is_deleted()


def test_serve_rounds_steady_stream_compiles_nothing():
    """The input program compiles once for a 1-round and once for a
    3-round call (the round index is traced, not static), the metric stack
    once per ``n_rounds``; two more 3-round calls compile nothing."""
    from repro.fl import stream as fls

    cfg, scfg, state, keys, row, plan = _loop_case(True, 3)
    step = serve.make_round_step(cfg, scfg)
    keys1 = jax.tree_util.tree_map(lambda x: x[:1], keys)
    plan1 = fls.FLPlan(*(x[:1] for x in plan))
    jax.block_until_ready((keys1, plan1))
    serve._round_inputs.clear_cache()
    serve._stack_rounds.clear_cache()

    compiles = []

    def listen(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(kw.get("fun_name"))

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        state, m = serve.serve_rounds(cfg, scfg, state, keys1, row,
                                      step=step, plan=plan1)
        state, m = serve.serve_rounds(cfg, scfg, state, keys, row,
                                      step=step, plan=plan)
        jax.block_until_ready((state, m))
        assert compiles.count("jit(_round_inputs)") == 2, compiles
        assert compiles.count("jit(_stack_rounds)") == 2, compiles
        del compiles[:]
        for _ in range(2):
            state, m = serve.serve_rounds(cfg, scfg, state, keys, row,
                                          step=step, plan=plan)
        jax.block_until_ready((state, m))
        assert compiles == []
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)


# ---------------------------------------------------------------------------
# slow battery: 8-device subprocess gate + churn soak
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_serve_gate_8_devices():
    """Streaming-vs-batch parity under a real 8-shard twin scope (ragged
    and empty-shard populations) plus quick churn invariants — the same
    gate CI runs via bench_scale --smoke."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.bench_scale", "--serve-gate"],
        capture_output=True, text=True, timeout=560, env=env, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "serve parity ok" in out.stdout, out.stdout
    assert "serve churn ok" in out.stdout, out.stdout


@pytest.mark.slow
def test_serve_fl_gate_8_devices():
    """Streamed-FL parity under a real 8-shard twin scope: the serve loop
    with the FL workload attached (vmapped local SGD, on-device Eq. 4/5,
    chain verify) must match the single-device path on a ragged N=37
    population, and churned FL rounds must keep evicted model rows zeroed
    — the same gate CI runs via bench_scale --smoke."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.bench_scale", "--serve-fl-gate"],
        capture_output=True, text=True, timeout=560, env=env, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "serve fl parity ok" in out.stdout, out.stdout
    assert "serve fl churn ok" in out.stdout, out.stdout


@pytest.mark.slow
def test_churn_soak_20_rounds():
    """>= 20 streamed rounds with live churn on the full workload stack:
    finite losses/stakes, per-round mask accounting, and the padding
    invariant on every round's final state."""
    n_rounds = 24
    batch = _batch(straggler=(0.05, 0.2), outage=(0.02, 0.1),
                   byzantine=(0.0, 0.3), quorum=(1.0, 2.0))
    cfg = EnvConfig(n_twins=256, n_bs=8,
                    migration=MigrationConfig(0.2, 1.0, 0.5),
                    faults=FaultConfig(0.1, 0.2, 0.1),
                    consensus=ConsensusConfig(quorum_f=1))
    scfg = serve.ServeConfig(capacity=256, join_rate=0.05, leave_rate=0.05)
    knobs = scenario.stream_knobs(batch, fcfg=cfg.faults, ccfg=cfg.consensus,
                                  lat=cfg.lat)
    row = scenario.knob_row(knobs, 0)
    state = serve.serve_init(cfg, scfg, batch.key[0], row, n_live=200)
    step = serve.make_round_step(cfg, scfg)
    keys = serve.stream_keys(batch.key[0], n_rounds)
    pop = 200
    for t in range(n_rounds):
        state, m = step(state, serve.round_keys(keys, t), row)
        m = {k: np.asarray(v) for k, v in m.items()}
        pop = pop + int(m["n_joined"]) - int(m["n_left"])
        assert int(m["n_active"]) == pop  # mask accounting, every round
        assert 0 <= pop <= 256
        assert np.isfinite(m["round_time"]) and m["round_time"] > 0
        assert np.isfinite(m["honest_stake_share"])
        assert 0.0 <= m["accept_frac"] <= 1.0
        act = np.asarray(state.active)
        assoc = np.asarray(state.env.assoc)
        data = np.asarray(state.env.data_sizes)
        assert (assoc[~act] == 8).all() and (data[~act] == 0.0).all()
        assert (assoc[act] < 8).all()
        assert int(act.sum()) == pop
    assert pop != 200 or n_rounds < 5  # churn actually churned
