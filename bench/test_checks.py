"""The check of the checks, on the CPU at a small capacity.

    JAX_PLATFORMS=cpu python3 -m pytest bench/test_checks.py -q

For each cell, at its own widths but a capacity a CPU test can hold:

* a sound run of the program is ``correct``;
* the control - the plain reference computed in bfloat16 at default matmul
  precision - fails the cell's limits;
* a run with each planted fault of ``faults.py`` (state left unchanged,
  half of the participants left out, local SGD on half of each minibatch,
  an answer altered) is not ``correct``.

Each drives the rest of a run (``run.run_cell``) without the harness's look
for a chip. The readings at the cells' own sizes are taken on the chip with
``calibrate.py``.
"""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import compare  # noqa: E402
import run  # noqa: E402
from faults import KINDS, faulty  # noqa: E402

SMALL = {"paper_s5.fl": 12, "fleet_1e4.fl_churn": 300}
SEED = 2 ** 33 + 12345


def _cell(name):
    cell, cfg, traffic, limits, specs = run.load_cell(name)
    return cell, dict(cfg, capacity=SMALL[name]), traffic, limits, specs


def _run(name, factory=None):
    cell, cfg, traffic, limits, specs = _cell(name)
    return run.run_cell(cell, cfg, traffic, limits, specs, seed=SEED, seconds=0.5,
                        trace=False, service_factory=factory)


@pytest.mark.parametrize("name", sorted(SMALL))
def test_sound_run_is_correct(name):
    res = _run(name)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0


@pytest.mark.parametrize("name", sorted(SMALL))
def test_control_fails(name):
    _, cfg, traffic, limits, _ = _cell(name)
    ctl, ref = run.control_answers(cfg, traffic, SEED, limits["margins"])
    import jax
    import numpy as np

    import world
    start = jax.tree_util.tree_map(lambda v: np.asarray(v, np.float64),
                                   world.make_world(cfg, traffic, SEED)["params"])
    ok, rows = compare.judge(compare.numbers(ctl, ref, start), limits)
    assert not ok, rows


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("name", sorted(SMALL))
def test_fault_fails(name, kind):
    res = _run(name, faulty(kind))
    assert not res["correct"], res["checks"]
