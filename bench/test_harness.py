"""CPU tests of the harness's model seam, at small sizes.

    JAX_PLATFORMS=cpu python3 -m pytest bench/test_harness.py -q

* The FL cells' worlds and their checked reference rounds, at capacity 12,
  hash to the digests recorded on the commit before the model seam: the
  seam changed what reads the model, not what is drawn or answered.
* A frozen-base + LoRA language model given only as files
  (``toy/models/toy_lm.py``, joined to the ``models`` namespace through the
  path) runs through ``world.py``, ``reference.py`` and ``flops.py``: its
  frozen tree is neither trained nor changed, its adapters train, its FLOPs
  per sequence are a hand count, and the bfloat16 control differs.
* The service hands ``fl_init`` a frozen tree exactly when the world has
  one.

* A configuration with FL off (``"model": null``; ``serve_dtwn``'s first
  documented example at capacity 12) draws no model and no plan, and its
  runs are judged on ``round_time`` and ``mismatches``: a sound run is
  ``correct``; the control, a state left unchanged and an altered answer
  are not.
"""
import hashlib
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import compare  # noqa: E402
import flops  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402
import traffic as traffic_mod  # noqa: E402
import world  # noqa: E402

SEED = 2 ** 33 + 12345
# sha256 of every leaf (path, dtype, shape, bytes) of make_world and of
# run.reference_answers at capacity 12, recorded on the parent commit of the
# model seam (CPU, jax 0.9.0)
DIGESTS = {
    "paper_s5.fl": ("c3ce915cd79dc278392c7ba4ef54035b5d0e2d9ca935bd43aaad8498088b9760",
                    "90548e8a5e2cb158645a07c2006928b8b461aca4db30fb3efc207ce4ed5de2a8"),
    "fleet_1e4.fl_churn": ("a182e8f04e37de25765175eb323067b658d64bf1bfc686d332971af9bbd53a0e",
                           "5c251bf2ba78cc7aa371632ae18a8ab06077308cf19b910bd749447a5253bc88"),
}
# model FLOPs a round of each FL cell, as PERF.md gives them (190.3 GFLOP, 9.0 MFLOP)
ROUND_FLOPS = {"paper_s5.fl": 190319788032, "fleet_1e4.fl_churn": 8969216}

TOY = {"model": "toy_lm", "n_layers": 2, "d_model": 64, "d_ff": 256, "vocab": 256,
       "seq_len": 32, "lora_rank": 4, "n_train": 96, "n_test": 64, "n_eval": 32,
       "shard_size": 32, "batch_size": 4, "local_iters": 2, "verify": True}
# by hand, per sequence (T 32, d 64, f 256, r 4, V 256, 2 blocks):
# a block 8Td^2 + 8Tdr + 4T^2d + 4Tdf = 1048576 + 65536 + 262144 + 2097152,
# twice, and the head 2TdV = 1048576
TOY_FORWARD = 2 * (1048576 + 65536 + 262144 + 2097152) + 1048576
# the backward: the head's input gradient 2TdV; the upper block 8Td^2 +
# 8T^2d + 4Tdf + 16Tdr; the first block 2Td^2 + 6T^2d + 4Tdf + 12Tdr
TOY_TRAIN = (TOY_FORWARD + 1048576 + (1048576 + 524288 + 2097152 + 131072)
             + (262144 + 393216 + 2097152 + 98304))


def digest(tree):
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(a.dtype).encode() + str(a.shape).encode())
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("name", sorted(DIGESTS))
def test_fl_cells_draw_and_answer_as_before(name):
    _, cfg, traffic, limits, _ = run.load_cell(name)
    cfg = dict(cfg, capacity=12)
    want_world, want_ref = DIGESTS[name]
    assert digest(world.make_world(cfg, traffic, SEED)) == want_world
    assert digest(run.reference_answers(cfg, traffic, SEED, limits["margins"])) == want_ref


@pytest.mark.parametrize("name", sorted(ROUND_FLOPS))
def test_fl_cells_round_flops(name):
    _, cfg, traffic, _, _ = run.load_cell(name)
    assert flops.round_model_flops(cfg, traffic) == ROUND_FLOPS[name]


@pytest.fixture(scope="module")
def toy():
    """The toy model's configuration, on the fleet's deployment at capacity
    12, with its directory joined to the ``models`` namespace."""
    sys.path.insert(0, os.path.join(HERE, "toy"))
    try:
        _, base, traffic, limits, _ = run.load_cell("fleet_1e4.fl_churn")
        cfg = dict(base, capacity=12, **TOY)
        cfg.pop("param_shapes")
        yield cfg, traffic, limits["margins"], world.model_module(cfg)
    finally:
        sys.path.remove(os.path.join(HERE, "toy"))


def _dot_flops(jaxpr):
    """2 x MACs of every ``dot_general`` of a jaxpr and its sub-jaxprs."""
    total = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            (contract, _), _ = eqn.params["dimension_numbers"]
            lhs = eqn.invars[0].aval.shape
            total += 2 * int(np.prod(eqn.outvars[0].aval.shape)) * int(
                np.prod([lhs[i] for i in contract]))
        for p in eqn.params.values():
            for sub in p if isinstance(p, (list, tuple)) else [p]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    total += _dot_flops(inner)
    return total


def test_toy_flops_are_a_hand_count(toy):
    cfg, traffic, _, mod = toy
    assert mod.forward_flops(cfg) == TOY_FORWARD == 7995392
    assert mod.train_flops(cfg) == TOY_TRAIN == 15695872
    # the same counts are the products JAX builds for the loss and its
    # gradient with respect to the adapters
    key = jax.random.PRNGKey(0)
    params, frozen = mod.init(key, cfg), mod.init_frozen(key, cfg)
    x, y, _, _ = mod.make_data(key, key, dict(cfg, n_train=4, n_test=4))
    loss, _ = reference.model_fns(mod, frozen, jnp.float32, jax.lax.Precision.HIGHEST)
    assert _dot_flops(jax.make_jaxpr(loss)(params, x, y).jaxpr) == 4 * TOY_FORWARD
    assert _dot_flops(jax.make_jaxpr(jax.grad(loss))(params, x, y).jaxpr) == 4 * TOY_TRAIN
    p, it, b = traffic["participants"], cfg["local_iters"], cfg["batch_size"]
    evals = (cfg["n_bs"] + 1) * cfg["n_eval"] * TOY_FORWARD
    assert flops.round_model_flops(cfg, traffic) == p * it * b * TOY_TRAIN + evals


def test_toy_runs_through_world_and_reference(toy):
    cfg, traffic, margins, mod = toy
    w = world.make_world(cfg, traffic, SEED)
    assert w["x"].shape == (cfg["n_train"], cfg["seq_len"]) and w["x"].dtype == jnp.int32
    np.testing.assert_array_equal(w["x"][:, 1:], w["y"][:, :-1])
    frozen0 = jax.tree_util.tree_map(np.asarray, w["frozen"])
    rounds = traffic_mod.rounds(cfg, traffic, SEED, 0, 3)
    answers, globals_ = reference.run(cfg, traffic, w, rounds, 3, margins)
    ctl, ctl_globals = reference.run(cfg, traffic, w, rounds, 3, margins, control=True)

    # the frozen tree is a constant: not in the trained tree, unchanged,
    # and the one the last round's loss was read with
    assert set(globals_[-1]) == set(w["params"])
    assert not set(globals_[-1]) & set(w["frozen"])
    for k, v in w["frozen"].items():
        np.testing.assert_array_equal(np.asarray(v), frozen0[k])
    again = world.make_world(cfg, traffic, SEED)["frozen"]
    assert digest(again) == digest(frozen0)
    last = jax.tree_util.tree_map(jnp.asarray, globals_[-1])
    with jax.default_matmul_precision(cfg["matmul_precision"]):
        loss = mod.loss(last, w["frozen"], w["x_test"][:cfg["n_eval"]],
                        w["y_test"][:cfg["n_eval"]], precision=jax.lax.Precision.DEFAULT,
                        dtype=jnp.float32)
    np.testing.assert_allclose(float(loss), answers["fl_loss"][-1], rtol=1e-5)

    # the adapters train
    for k, v in w["params"].items():
        assert not np.array_equal(globals_[-1][k], np.asarray(v)), k
    assert np.all(np.isfinite(answers["fl_loss"]))

    # the control trains otherwise: the change of its adapters departs from
    # the reference's (compare.change_gap, change_r1's measure)
    start = jax.tree_util.tree_map(lambda v: np.asarray(v, np.float64), w["params"])
    assert compare.change_gap(ctl_globals[0], globals_[0], start) > 0.1
    assert not np.array_equal(ctl["fl_loss"], answers["fl_loss"])


@pytest.mark.parametrize("with_frozen", [False, True])
def test_fl_init_gets_frozen_exactly_when_the_world_has_one(monkeypatch, with_frozen):
    from repro.fl import stream

    from service import Service

    _, cfg, traffic, _, _ = run.load_cell("fleet_1e4.fl_churn")
    cfg = dict(cfg, capacity=12)
    w = dict(world.make_world(cfg, traffic, SEED))
    if with_frozen:
        w["frozen"] = {"base": jnp.ones((3,), jnp.float32)}
    seen = []

    def stub(fcfg, key, data, active, **kw):
        seen.append((data[2], kw))
        return "fl-state"

    monkeypatch.setattr(stream, "fl_init", stub)
    state = Service(cfg, traffic).fresh_state(w)
    assert state.fl == "fl-state"
    (data_set, kw), = seen
    assert data_set == "cifar10-sim"
    assert ("frozen" in kw) == with_frozen
    if with_frozen:
        assert kw["frozen"] is w["frozen"]
    assert kw["params"] is w["params"]


def _fl_off():
    """An FL-off deployment at capacity 12, as ``serve_dtwn``'s first example
    runs it (``--join 0.02 --leave 0.02 --faults --migration``, 16 rounds a
    call, the CLI's defaults), with its limits."""
    _, base, traffic, _, _ = run.load_cell("fleet_1e4.fl_churn")
    cfg = dict(base, capacity=12, model=None,
               migration={"p_move": 0.1, "locality": 1.0, "load_weight": 1.0},
               faults={"straggler_rate": 0.1, "straggler_slowdown": 4.0, "outage_rate": 0.1,
                       "burst_len": 3.0, "outage_floor": 0.05, "malicious_frac": 0.0})
    traffic = dict(traffic, join_rate=0.02, leave_rate=0.02, rounds_per_call=16)
    limits = {"numbers": {"round_time": 5e-5, "mismatches": 0},
              "margins": {"migration": 1e-4}}
    return cfg, traffic, limits


def test_fl_off_world_has_no_model():
    cfg, traffic, _ = _fl_off()
    w = world.make_world(cfg, traffic, SEED)
    assert not {"params", "frozen", "x", "y", "x_test", "y_test"} & set(w)
    keys, plan = traffic_mod.Traffic(cfg, traffic, SEED).next()
    assert plan is None
    assert {v.shape for v in keys.values()} == {(traffic["rounds_per_call"], 2)}


@pytest.mark.parametrize("kind", [None, "stale_state", "altered"])
def test_fl_off_run_is_judged(kind):
    from faults import faulty, kinds

    cfg, traffic, limits = _fl_off()
    assert kinds(cfg) == ("stale_state", "altered")
    cell = {"name": "fl_off", "chips": 1}
    res = run.run_cell(cell, cfg, traffic, limits, {"end_to_end": [], "per_layer": []},
                       seed=SEED, seconds=0.5, trace=False,
                       service_factory=None if kind is None else faulty(kind))
    assert set(res["checks"]) == {"round_time", "mismatches"}
    assert res["correct"] == (kind is None), res["checks"]


def test_fl_off_control_fails():
    cfg, traffic, limits = _fl_off()
    ctl, ref = run.control_answers(cfg, traffic, SEED, limits["margins"])
    ok, rows = compare.judge(compare.numbers(ctl, ref, None), limits)
    assert not ok, rows
