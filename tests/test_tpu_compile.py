"""Ahead-of-time compiles of the main-path kernels for a TPU v5e.

A v5e:2x2 topology is *described* (no chip attached) and the segment-reduce
kernel — every per-BS sum of Eqs. 12-17 and the Eq. 4 aggregation — is
compiled for one of its chips at the widths the service runs: the latency
path at N=10^5, Eq. 4 at the paper CNN's widest leaf (fc1, K = 2^21) and
at the tiny model's width at N=10^4, plus ``grad`` and ``vmap`` through the
TPU dispatch (the MADDPG update differentiates and vmaps through it). Each
compile must hold the Pallas kernel (``tpu_custom_call``): the chip's own
compiler refuses what interpret mode accepts (VMEM overflow, block
tiling), so these guard the chip path at no chip time.

The topology is described inside a module fixture, never at import: only
one process may hold the TPU library, and several test workers import
this file.
"""
import importlib
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core import hierarchy
from repro.core.marl.env import EnvConfig
from repro.core.marl.spaces import Action, encode_action, space_spec
from repro.models import cnn, tiny

sr = importlib.import_module("repro.kernels.segment_reduce")


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU lib"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def tpu_dispatch(monkeypatch):
    """Trace the dispatch as it resolves on a TPU: the code asks
    ``jax.default_backend()``, which here still answers "cpu"."""
    monkeypatch.setattr(sr.jax, "default_backend", lambda: "tpu")


def _compile_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _stacked(sharding, model, n):
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    return jax.tree_util.tree_map(
        lambda x: _sds(sharding, (n,) + x.shape, x.dtype), shapes)


@pytest.mark.parametrize("n,k,m", [(100_000, 1, 10), (1000, 4, 10),
                                   (100, 2**21, 5)])
def test_segment_reduce_compiles_for_v5e(one_chip, n, k, m):
    assert sr.resolve_backend(n, m, platform="tpu") == "pallas"
    txt = _compile_text(
        lambda v, a: sr.segment_reduce(v, a, m, backend="pallas",
                                       interpret=False),
        _sds(one_chip, (n, k)), _sds(one_chip, (n,), jnp.int32))
    assert "tpu_custom_call" in txt


def test_segment_reduce_kernel_is_named_on_v5e(one_chip):
    """The kernel's custom call is named ``segment_reduce`` in the compiled
    program, so its events in a chip trace carry that name."""
    txt = _compile_text(
        lambda v, a: sr.segment_reduce(v, a, 5, backend="pallas",
                                       interpret=False),
        _sds(one_chip, (1000, 4)), _sds(one_chip, (1000,), jnp.int32))
    calls = re.findall(r"%([\w.\-]+) = \S+ custom-call\(.*tpu_custom_call", txt)
    assert calls and all(c.startswith("segment_reduce") for c in calls), calls


@pytest.mark.parametrize("model,n", [(cnn, 100), (tiny, 10_000)],
                         ids=["cnn_n100", "tiny_n10k"])
def test_eq4_aggregation_compiles_for_v5e(one_chip, tpu_dispatch, model, n):
    """Eq. 4 over a stacked model tree as the served FL round calls it:
    every leaf (the CNN's fc1 is K = 4096*512) goes through the kernel."""
    m = 5
    tree = _stacked(one_chip, model, n)
    txt = _compile_text(
        lambda t, d, a: hierarchy.bs_aggregate_stacked(t, d, a, m),
        tree, _sds(one_chip, (n,)), _sds(one_chip, (n,), jnp.int32))
    n_leaves = len(jax.tree_util.tree_leaves(tree))
    # one kernel per leaf plus the per-BS weight sum
    assert txt.count("tpu_custom_call") >= n_leaves + 1


def test_grad_through_tpu_dispatch_compiles(one_chip):
    m = 5
    loss = lambda v, a: jnp.sum(
        sr.segment_reduce(v, a, m, backend="pallas", interpret=False) ** 2)
    txt = _compile_text(jax.grad(loss), _sds(one_chip, (4096, 4)),
                        _sds(one_chip, (4096,), jnp.int32))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("assoc_batched", [True, False],
                         ids=["assoc_batched", "assoc_shared"])
def test_vmap_through_tpu_dispatch_compiles(one_chip, assoc_batched):
    m, b, n = 5, 3, 4096
    fn = jax.vmap(
        lambda v, a: sr.segment_reduce(v, a, m, backend="pallas",
                                       interpret=False),
        in_axes=(0, 0 if assoc_batched else None))
    a_shape = (b, n) if assoc_batched else (n,)
    txt = _compile_text(fn, _sds(one_chip, (b, n, 4)),
                        _sds(one_chip, a_shape, jnp.int32))
    assert "tpu_custom_call" in txt


def test_maddpg_action_encoding_vmap_grad_compiles(one_chip, tpu_dispatch):
    """The MADDPG update's pattern: grad of the critic input (the compact
    action encoding) per agent, vmapped over agents, at 5000 twins."""
    cfg = EnvConfig(n_twins=5000, n_bs=5)
    spec = space_spec(cfg)
    m, n = spec.n_bs, spec.n_twins

    def enc_sum(scores, b_ctl, tau, feats):
        return jnp.sum(encode_action(cfg, Action(scores, b_ctl, tau), feats))

    fn = jax.vmap(jax.grad(enc_sum), in_axes=(0, 0, 0, None))
    feats = _sds(one_chip, (n, 4))
    txt = _compile_text(fn, _sds(one_chip, (m, m, n)),
                        _sds(one_chip, (m, m)),
                        _sds(one_chip, (m, m, spec.n_subchannels)), feats)
    assert "tpu_custom_call" in txt
