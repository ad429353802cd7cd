"""Twin-axis mesh sharding tests (repro.core.sharding).

Fast tests run on the single CPU device and pin the no-op guarantees: a
1-shard mesh must reproduce the plain path bit-for-bit, and every scope
helper must degrade to its plain-jnp equivalent outside a scope. The
multi-device parity suite (latency Eqs. 12-17, env reset/observe/step, the
scan trainer, the scenario runner — on divisible, ragged, and empty-shard
populations) lives in ``benchmarks.bench_scale.sharded_gate`` and runs here
as a slow subprocess with 8 forced host devices (the same gate CI runs via
``bench_scale --smoke``).
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import latency, scenario, sharding
from repro.core.marl import (DDPGConfig, EnvConfig, TrainConfig, train,
                             train_sharded)
from repro.core.sharding import TwinSharding
from repro.kernels.segment_reduce import BACKENDS, resolve_backend

KEY = jax.random.PRNGKey(0)
LP = latency.LatencyParams()
SRC = os.path.join(os.path.dirname(__file__), "..", "src")
ROOT = os.path.join(os.path.dirname(__file__), "..")


# ---------------------------------------------------------------------------
# single-device no-op fast path
# ---------------------------------------------------------------------------


def _latency_inputs(n, m, seed=0):
    ks = jax.random.split(jax.random.fold_in(KEY, seed), 5)
    return (jax.random.randint(ks[0], (n,), 0, m),
            jax.random.uniform(ks[1], (n,), minval=0.05, maxval=1.0),
            jax.random.uniform(ks[2], (n,), minval=100, maxval=800),
            jax.random.uniform(ks[3], (m,), minval=1e9, maxval=4e9),
            jax.random.uniform(ks[4], (m,), minval=1e6, maxval=1e8))


def test_single_shard_latency_is_identity():
    ts = TwinSharding.make(1)
    assoc, b, data, freqs, up = _latency_inputs(100, 5)
    got = sharding.sharded_round_time(ts, LP, assoc, b, data, freqs, up, up)
    ref = latency.round_time(LP, assoc, b, data, freqs, up, up)
    assert float(got) == float(ref)
    np.testing.assert_array_equal(
        np.asarray(sharding.sharded_t_cmp(ts, LP, assoc, b, data, freqs)),
        np.asarray(latency.t_cmp(LP, assoc, b, data, freqs)))


def test_single_shard_train_is_identity():
    ts = TwinSharding.make(1)
    cfg = EnvConfig(n_twins=12, n_bs=3, bs_freqs_ghz=(2.6, 1.8, 3.6),
                    episode_len=5)
    dcfg = DDPGConfig(batch_size=8, hidden=(32, 32))
    tcfg = TrainConfig(steps=10, warmup=4, replay_capacity=32)
    st1, tr1 = train(cfg, dcfg, tcfg, jax.random.PRNGKey(1))
    st2, tr2 = train_sharded(ts, cfg, dcfg, tcfg, jax.random.PRNGKey(1))
    for k in tr1:
        np.testing.assert_array_equal(np.asarray(tr1[k]), np.asarray(tr2[k]))
    for a, b in zip(jax.tree_util.tree_leaves(st1.agent),
                    jax.tree_util.tree_leaves(st2.agent)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_single_shard_scenario_runner_matches_full():
    ts = TwinSharding.make(1)
    cfg = EnvConfig(n_twins=30, n_bs=4)
    batch = scenario.make_batch(jax.random.fold_in(KEY, 2), 4)
    lite = scenario.run_baselines_sharded(ts, cfg, batch)
    full = scenario.run_baselines(cfg, batch)
    for k in ("random", "average"):
        np.testing.assert_allclose(np.asarray(lite[k]), np.asarray(full[k]),
                                   rtol=1e-6)
    np.testing.assert_allclose(np.asarray(lite["total_data"]),
                               np.asarray(full["total_data"]), rtol=1e-6)


# ---------------------------------------------------------------------------
# padding / spec helpers
# ---------------------------------------------------------------------------


class _FakeMesh:
    """Mesh stand-in so shape arithmetic is testable without 8 devices."""
    axis_names = ("twin",)
    shape = {"twin": 8}


def test_padding_arithmetic():
    ts = TwinSharding(mesh=_FakeMesh())
    assert ts.n_shards == 8
    assert ts.local_n(64) == 8 and ts.padded_n(64) == 64
    assert ts.local_n(37) == 5 and ts.padded_n(37) == 40
    assert ts.local_n(5) == 1 and ts.padded_n(5) == 8  # empty shards exist
    x = jnp.arange(37)
    xp = ts.pad_twin(x, fill=99)
    assert xp.shape == (40,)
    np.testing.assert_array_equal(np.asarray(xp[37:]), [99, 99, 99])
    np.testing.assert_array_equal(np.asarray(ts.unpad_twin(xp, 37)),
                                  np.asarray(x))
    s2 = ts.pad_twin(jnp.zeros((3, 37)), axis=1)
    assert s2.shape == (3, 40)


def test_twin_spec_layout():
    ts = TwinSharding(mesh=_FakeMesh())
    assert tuple(ts.twin_spec()) == ("twin",)
    assert tuple(ts.twin_spec(axis=1, ndim=2)) == (None, "twin")


def test_mesh_axis_name_is_validated():
    class BadMesh:
        axis_names = ("data", "model")
        shape = {"data": 2, "model": 2}

    with pytest.raises(ValueError, match="twin"):
        TwinSharding(mesh=BadMesh())


def test_train_sharded_rejects_flat_policy():
    ts = TwinSharding(mesh=_FakeMesh())
    cfg = EnvConfig(n_twins=16, n_bs=3, bs_freqs_ghz=(2.6, 1.8, 3.6))
    with pytest.raises(ValueError, match="factorized"):
        train_sharded(ts, cfg, DDPGConfig(policy="flat"), TrainConfig(),
                      KEY)


# ---------------------------------------------------------------------------
# scope helpers degrade to plain jnp outside any scope
# ---------------------------------------------------------------------------


def test_helpers_are_plain_jnp_outside_scope():
    x = jax.random.normal(KEY, (13, 4))
    assert sharding.in_scope() is None
    np.testing.assert_array_equal(np.asarray(sharding.twin_sum(x)),
                                  np.asarray(jnp.sum(x, axis=0)))
    np.testing.assert_array_equal(np.asarray(sharding.twin_mean(x)),
                                  np.asarray(jnp.mean(x, axis=0)))
    np.testing.assert_array_equal(np.asarray(sharding.twin_max(x)),
                                  np.asarray(jnp.max(x, axis=0)))
    np.testing.assert_array_equal(np.asarray(sharding.twin_min(x)),
                                  np.asarray(jnp.min(x, axis=0)))
    np.testing.assert_array_equal(np.asarray(sharding.twin_std(x)),
                                  np.asarray(jnp.std(x, axis=0)))
    logits = jax.random.normal(jax.random.fold_in(KEY, 1), (13,))
    np.testing.assert_allclose(
        np.asarray(sharding.twin_softmax_pool(logits, x)),
        np.asarray(jax.nn.softmax(logits) @ x), rtol=1e-6)
    # identity transforms
    np.testing.assert_array_equal(np.asarray(sharding.mask_twins(x, 0.0)),
                                  np.asarray(x))
    np.testing.assert_array_equal(np.asarray(sharding.localize(x)),
                                  np.asarray(x))
    assert sharding.local_twin_count(7) == 7
    assert sharding.global_twin_count(7) == 7
    tree = {"a": jnp.ones(3)}
    assert sharding.pmean_in_scope(tree) is tree
    assert sharding.stamp_replicated(tree) is tree


def test_sharded_backend_listed_but_never_auto_resolved():
    assert "sharded" in BACKENDS
    for n in (1, 1000, 10_000_000):
        for m in (1, 8, 64):
            for platform in ("cpu", "tpu", "gpu"):
                assert resolve_backend(n, m, platform=platform) != "sharded"


def test_scope_requires_region_helpers_raise_outside():
    with pytest.raises(RuntimeError, match="twin_scope"):
        sharding.slice_local(jnp.arange(8))
    with pytest.raises(RuntimeError, match="twin_scope"):
        sharding.twin_indices()


# ---------------------------------------------------------------------------
# 8-host-device parity suite (subprocess so the device count applies)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_sharded_parity_gate_8_devices():
    """The full parity gate — latency Eqs. 12-17, env reset/observe/step,
    scan trainer, scenario runner; divisible/ragged/empty-shard populations
    — on 8 forced host devices. Shared with CI via bench_scale --smoke."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.bench_scale", "--sharded-gate"],
        capture_output=True, text=True, timeout=560, env=env, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "scan-trainer parity ok" in out.stdout, out.stdout
    assert "migration parity ok" in out.stdout, out.stdout


@pytest.mark.slow
def test_sharded_segment_reduce_direct_8_devices():
    """backend="sharded" through the raw segment_reduce API inside a manual
    shard_map region (no helper wrappers): local-reduce + psum must equal
    the one-hot oracle, and "auto" must resolve identically inside a
    scope."""
    code = """
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.core.sharding import TwinSharding
        from repro.kernels.segment_reduce import segment_reduce

        ts = TwinSharding.make()
        n, m = 96, 7
        ks = jax.random.split(jax.random.PRNGKey(0), 2)
        assoc = jax.random.randint(ks[0], (n,), 0, m)
        vals = jax.random.uniform(ks[1], (n, 3), minval=-1, maxval=1)
        ref = segment_reduce(vals, assoc, m, backend="onehot")

        def local(v, a):
            with ts.scope(n):
                explicit = segment_reduce(v, a, m, backend="sharded")
                auto = segment_reduce(v, a, m)   # scope flips auto
            return explicit, auto

        f = ts.shard_map(local, in_specs=(P("twin"), P("twin")),
                         out_specs=(P(), P()))
        explicit, auto = jax.jit(f)(vals, assoc)
        np.testing.assert_allclose(np.asarray(explicit), np.asarray(ref),
                                   rtol=1e-5)
        np.testing.assert_allclose(np.asarray(auto), np.asarray(ref),
                                   rtol=1e-5)
        print("SHARDED_SEGMENT_REDUCE_OK")
    """
    import textwrap

    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, timeout=560,
                         env=env, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "SHARDED_SEGMENT_REDUCE_OK" in out.stdout
