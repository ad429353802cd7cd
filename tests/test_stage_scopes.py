"""The served round's trace names: the named scope of every stage of
``serve._round_step`` and ``fl_round`` in the compiled step's ``op_name``
metadata (the segment-reduce kernel's name too), and ``serve_rounds``' host
spans in a profiler trace, once per round or once per call; and the
benchmark's readers of those names against traces recorded on the chip.
"""
import glob
import importlib
import os
import re
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.core import scenario, serve
from repro.core.consensus import ConsensusConfig
from repro.core.faults import FaultConfig
from repro.core.marl.env import EnvConfig
from repro.core.migration import MigrationConfig
from repro.fl import stream as fls

sr = importlib.import_module("repro.kernels.segment_reduce")

STEP_STAGES = ("association", "migration", "faults", "price", "chain",
               "fl_round", "churn", "dynamics", "replay")
FL_STAGES = ("gather", "local_sgd", "scatter", "eq4", "verify", "eq5", "eval")


def _everything_on(n=24, m=3, rounds=2):
    """A step with the policy, migration, faults, chain, churn, channel
    dynamics and FL all on, at a small capacity."""
    from repro.data import cifar10
    from repro.fl.partition import iid_partition

    fcfg = fls.FLServeConfig(model="tiny", participants=3, local_iters=2,
                             batch_size=4, n_eval=16)
    cfg = EnvConfig(n_twins=n, n_bs=m,
                    migration=MigrationConfig(0.2, 1.0, 0.5),
                    faults=FaultConfig(0.1, 0.2, 0.1),
                    consensus=ConsensusConfig(quorum_f=1))
    scfg = serve.ServeConfig(capacity=n, join_rate=0.05, leave_rate=0.05,
                             policy="factorized", evolve_channels=True,
                             fl=fcfg)
    batch = scenario.make_batch(jax.random.PRNGKey(0), 2)
    row = scenario.knob_row(scenario.stream_knobs(
        batch, fcfg=cfg.faults, ccfg=cfg.consensus, lat=cfg.lat), 0)
    state = serve.serve_init(cfg, scfg, batch.key[0], row)
    state = serve.attach_policy(cfg, state, jax.random.PRNGKey(1),
                                replay_capacity=8)
    data = cifar10.load(max_train=256, max_test=32)
    state = state._replace(fl=fls.fl_init(
        fcfg, jax.random.PRNGKey(7), data, np.asarray(state.active, bool)))
    plan = fls.stream_fl_plan(fcfg, iid_partition(256, n, seed=3), rounds)
    return cfg, scfg, state, row, plan, serve.stream_keys(batch.key[0], rounds)


def _scopes(hlo_text):
    """Every name-stack component of the compiled step's ``op_name``s (the
    last component of a path is the primitive)."""
    found = set()
    for op in re.findall(r'op_name="([^"]*)"', hlo_text):
        for path in op.split(";"):
            found.update(path.split("/")[:-1])
    return found


def test_round_step_carries_every_stage_scope(monkeypatch):
    # the Pallas segment-reduce (interpreted off the chip) at every
    # reduction, so the kernel's name is in the step as on the chip
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    monkeypatch.setattr(sr, "resolve_backend",
                        lambda n, m, platform=None: "pallas")
    cfg, scfg, state, row, plan, keys = _everything_on()
    step = serve.make_round_step(cfg, scfg)
    hlo = step.lower(state, serve.round_keys(keys, 0), row,
                     fls.plan_row(plan, 0)).compile().as_text()
    scopes = _scopes(hlo)
    missing = [s for s in STEP_STAGES + FL_STAGES + ("segment_reduce",)
               if s not in scopes]
    assert not missing, f"scopes missing from the compiled step: {missing}"
    # each fl_round stage sits inside fl_round
    paths = re.findall(r'op_name="([^"]*)"', hlo)
    for s in FL_STAGES:
        assert any(f"/fl_round/{s}/" in p for p in paths), s


def _host_events(trace_dir):
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    names = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                names.extend(ev.name for ev in line.events)
    return names


def test_serve_rounds_host_spans_per_round_and_call(tmp_path):
    cfg = EnvConfig(n_twins=16, n_bs=3)
    scfg = serve.ServeConfig(capacity=16, join_rate=0.1, leave_rate=0.1)
    batch = scenario.make_batch(jax.random.PRNGKey(3), 1)
    row = scenario.knob_row(scenario.stream_knobs(batch), 0)
    state = serve.serve_init(cfg, scfg, batch.key[0], row)
    step = serve.make_round_step(cfg, scfg)
    keys = serve.stream_keys(batch.key[0], 3)
    state, m = serve.serve_rounds(cfg, scfg, state, keys, row, step=step)
    jax.block_until_ready(m)    # every program compiled before the trace

    calls, rounds = 2, 3
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(calls):
            state, m = serve.serve_rounds(cfg, scfg, state, keys, row,
                                          step=step)
        jax.block_until_ready(m)
    finally:
        jax.profiler.stop_trace()
    names = _host_events(str(tmp_path))
    assert names.count(serve.SPAN_INPUTS) == calls * rounds
    assert names.count(serve.SPAN_ENQUEUE) == calls * rounds
    assert names.count(serve.SPAN_STACK) == calls


@pytest.mark.parametrize("script", ["check_trace.py", "check_stages.py"])
def test_trace_readers_match_recorded_fixtures(script):
    """The benchmark's trace reduction and stage attribution give, on the
    fixtures cut from chip traces, what an occupancy mask gave for them."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, os.path.join(root, "bench", script)],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    assert "FAIL" not in out.stdout
