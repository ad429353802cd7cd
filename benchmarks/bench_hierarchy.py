"""Beyond-paper benchmark: cross-pod collective-byte reduction from the
paper's two-tier aggregation mapped onto the mesh (DESIGN.md §3).

Lowers (in a subprocess with a 2x2x2 debug multi-pod mesh):
  flat     — one synced train step (grads all-reduced over pod+data)
  hier     — the pod-local inner step (no pod-axis collectives) plus the
             cross-pod parameter sync, amortized over H inner steps
and compares collective bytes per step parsed from the compiled HLO.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

from benchmarks.common import Timer, save_result

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

_CODE = """
import json
import jax, jax.numpy as jnp
from repro.configs import get_smoke_config
from repro.models import build_model
from repro.optim import make_optimizer
from repro.launch.steps import (make_train_step, make_pod_local_train_step,
                                make_cross_pod_sync)
from repro.launch.mesh import make_debug_mesh
from repro.sharding import param_pspecs, to_shardings, batch_pspec
from repro.sharding.act import activation_mesh
from repro.utils.hlo_cost import hlo_cost
from jax.sharding import NamedSharding, PartitionSpec as P

cfg = get_smoke_config("h2o-danube-1.8b")
model = build_model(cfg)
mesh = make_debug_mesh(8, multi_pod=True)   # (2 pods, 2 data, 2 model)
opt = make_optimizer("sgd", lr=0.1)

params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
p_specs = param_pspecs(params, mesh)
p_sh = to_shardings(p_specs, mesh)
opt_sds = jax.eval_shape(opt.init, params)
o_sh = to_shardings(param_pspecs(opt_sds, mesh), mesh)
B, S = 8, 64
toks = jax.ShapeDtypeStruct((B, S), jnp.int32,
                            sharding=NamedSharding(mesh, batch_pspec(mesh, 2)))
sds = lambda tree, sh: jax.tree_util.tree_map(
    lambda s, h: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=h), tree, sh)

def coll_bytes(lowered):
    return hlo_cost(lowered.compile().as_text()).collectives

# ---- flat synced step ----
with activation_mesh(mesh):
    flat = jax.jit(make_train_step(model, opt),
                   in_shardings=(p_sh, o_sh, {"tokens": toks.sharding}),
                   out_shardings=(p_sh, o_sh, None)).lower(
        sds(params, p_sh), sds(opt_sds, o_sh), {"tokens": toks})
flat_c = coll_bytes(flat)

# ---- hierarchical: pod-local inner + cross-pod sync ----
n_pods = mesh.shape["pod"]
stackp = lambda tree: jax.tree_util.tree_map(
    lambda x: jax.ShapeDtypeStruct((n_pods,) + x.shape, x.dtype), tree)
ps, os_ = stackp(params), stackp(opt_sds)
pod_first = lambda spec: P("pod", *tuple(spec))
ps_sh = jax.tree_util.tree_map(
    lambda s: NamedSharding(mesh, pod_first(s)), param_pspecs(params, jax.make_mesh((2,2),("data","model"))),
    is_leaf=lambda x: isinstance(x, P))
os_sh = jax.tree_util.tree_map(
    lambda s: NamedSharding(mesh, pod_first(s)), param_pspecs(opt_sds, jax.make_mesh((2,2),("data","model"))),
    is_leaf=lambda x: isinstance(x, P))
btoks = jax.ShapeDtypeStruct(
    (n_pods, B // n_pods, S), jnp.int32,
    sharding=NamedSharding(mesh, P("pod", "data", None)))
inner = jax.jit(make_pod_local_train_step(model, opt, n_pods),
                in_shardings=(ps_sh, os_sh, {"tokens": btoks.sharding}),
                out_shardings=(ps_sh, os_sh, None)).lower(
    sds(ps, ps_sh), sds(os_, os_sh), {"tokens": btoks})
inner_c = coll_bytes(inner)
sync = jax.jit(make_cross_pod_sync(n_pods), in_shardings=(ps_sh,),
               out_shardings=ps_sh).lower(sds(ps, ps_sh))
sync_c = coll_bytes(sync)

print(json.dumps({"flat": flat_c, "inner": inner_c, "sync": sync_c}))
"""


def run() -> dict:
    env = dict(os.environ)
    # a rehearsal on 8 CPU host devices: pinned to the CPU so the child
    # never reaches for a chip this (JAX-holding) parent may own
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(_CODE)],
                         capture_output=True, text=True, timeout=560, env=env)
    assert out.returncode == 0, out.stderr[-4000:]
    data = json.loads(out.stdout.strip().splitlines()[-1])
    tot = lambda c: sum(v["bytes"] for v in c.values())
    flat_b, inner_b, sync_b = tot(data["flat"]), tot(data["inner"]), tot(
        data["sync"])
    res = {"flat_bytes": flat_b, "inner_bytes": inner_b, "sync_bytes": sync_b}
    for H in (1, 4, 16, 64):
        res[f"hier_bytes_H{H}"] = inner_b + sync_b / H
    res["collectives"] = data
    save_result("hierarchy_collectives", res)
    return res


def main(reduced: bool = True):
    with Timer() as t:
        res = run()
    h16 = res["hier_bytes_H16"]
    ratio = res["flat_bytes"] / max(h16, 1)
    print(f"hierarchy: flat={res['flat_bytes']/1e6:.1f}MB/step "
          f"inner={res['inner_bytes']/1e6:.1f}MB "
          f"sync={res['sync_bytes']/1e6:.1f}MB "
          f"-> H=16 total {h16/1e6:.1f}MB ({ratio:.2f}x less)")
    return {"name": "hierarchy_collectives",
            "us_per_call": t.seconds * 1e6,
            "derived": f"flat/{res['flat_bytes']/1e6:.1f}MB"
                       f"|H16/{h16/1e6:.1f}MB|x{ratio:.2f}"}


if __name__ == "__main__":
    main(reduced=False)
