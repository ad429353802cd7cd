"""Expert-parallel all-to-all MoE dispatch (shard_map path) — correctness
against the dense oracle on a real multi-device mesh."""
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


@pytest.mark.slow
def test_ep_a2a_matches_dense_oracle_and_grads():
    code = """
        import jax, jax.numpy as jnp, dataclasses, numpy as np
        from repro.configs import get_smoke_config
        from repro.models import build_model
        from repro.sharding import param_pspecs, to_shardings, batch_pspec
        from repro.sharding.act import activation_mesh

        cfg = get_smoke_config("deepseek-v2-236b")  # 4 experts, EP over 4
        m_cap = build_model(dataclasses.replace(
            cfg, router_mode="capacity", capacity_factor=8.0))
        m_dense = build_model(dataclasses.replace(cfg, router_mode="dense"))
        params = m_cap.init(jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0,
                                  cfg.vocab_size)
        ref, _ = m_dense.forward(params, {"tokens": toks})
        from repro.launch.mesh import make_debug_mesh

        mesh = make_debug_mesh(8)  # (4, 2) ("data", "model"), Auto axes
        params_s = jax.device_put(
            params, to_shardings(param_pspecs(params, mesh), mesh))
        toks_s = jax.device_put(
            toks, jax.NamedSharding(mesh, batch_pspec(mesh, 2)))
        with activation_mesh(mesh, layout="2d"):
            out, _ = jax.jit(lambda p, b: m_cap.forward(p, b))(
                params_s, {"tokens": toks_s})
            g = jax.jit(jax.grad(m_cap.loss))(params_s, {"tokens": toks_s})
        err = float(jnp.max(jnp.abs(out - ref)))
        gn = sum(float(jnp.abs(x).sum())
                 for x in jax.tree_util.tree_leaves(g))
        assert err < 5e-4, err
        assert np.isfinite(gn)
        print("OK", err)
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, timeout=560, env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout
