import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512").strip()
# NOTE: the two lines above MUST run before any jax import (jax locks the
# device count at first init). Everything else follows.

"""Multi-pod dry-run: prove the distribution config is coherent.

For every (architecture x input shape) combination this lowers + compiles the
appropriate step (train_step for train_4k, forward for prefill_32k,
serve_step for decode shapes) against the production mesh — 16x16
("data","model") single pod and 2x16x16 ("pod","data","model") multi-pod —
using ShapeDtypeStruct inputs (no allocation), then records:

  - memory_analysis()        (bytes per device — proves it fits)
  - cost_analysis()          (HLO FLOPs / bytes for the roofline)
  - collective breakdown     (parsed from compiled HLO: all-gather /
                              all-reduce / reduce-scatter / all-to-all /
                              collective-permute operand bytes)
  - derived roofline terms   (compute / memory / collective seconds,
                              dominant term, MODEL_FLOPS/HLO_FLOPs ratio)

Results land in results/dryrun/<arch>__<shape>__<mesh>.json; EXPERIMENTS.md
§Dry-run/§Roofline and benchmarks/bench_roofline.py read them.

Usage:
  python -m repro.launch.dryrun --arch mixtral-8x22b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--skip-existing]
"""
import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import (ARCH_NAMES, SHAPES, get_arch_config,
                           supports_shape)
from repro.launch.mesh import PRODUCTION_DEVICE_KIND, make_production_mesh
from repro.launch.roofline import analytic_memory_bytes, roofline_terms
from repro.utils.hlo_cost import hlo_cost
from repro.launch.specs import (cache_shapes, decode_inputs, params_shapes,
                                train_inputs)
from repro.launch.steps import make_forward_step, make_serve_step, make_train_step
from repro.models import build_model
from repro.optim import make_optimizer
from repro.sharding import (batch_pspec, cache_pspecs, param_pspecs,
                            state_pspecs, to_shardings)
from repro.sharding.act import activation_mesh
from repro.utils.hlo_parse import collective_breakdown

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")


def _sds_with(shardings, tree):
    return jax.tree_util.tree_map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        tree, shardings)


def _on_chip() -> bool:
    """On a TPU a failed compile or analysis fails the run: the CPU-only
    leniency below exists because XLA-CPU lacks some analyses."""
    return jax.default_backend() == "tpu"


def _mem_analysis(compiled):
    try:
        ma = compiled.memory_analysis()
        if ma is None:
            return {}
        out = {}
        for field in ("temp_size_in_bytes", "argument_size_in_bytes",
                      "output_size_in_bytes", "alias_size_in_bytes",
                      "generated_code_size_in_bytes"):
            if hasattr(ma, field):
                out[field] = int(getattr(ma, field))
        return out
    except Exception as e:  # CPU backend may not implement it
        if _on_chip():
            raise
        return {"error": str(e)}


def _cost_analysis(compiled):
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        return {k: float(v) for k, v in ca.items()
                if isinstance(v, (int, float))}
    except Exception as e:
        if _on_chip():
            raise
        return {"error": str(e)}


def _tree_bytes(tree) -> int:
    import numpy as np

    return int(sum(np.prod(x.shape) * jnp.dtype(x.dtype).itemsize
                   for x in jax.tree_util.tree_leaves(tree)))


def lower_one(arch: str, shape_name: str, *, multi_pod: bool = False,
              mesh=None, hlo_dir: str | None = None,
              config_overrides: dict | None = None,
              layout: str = "2d") -> dict:
    """Lower + compile one combination; returns the result record."""
    shape = SHAPES[shape_name]
    cfg = get_arch_config(arch)
    if config_overrides:
        cfg = dataclasses.replace(cfg, **config_overrides)
    mesh = mesh if mesh is not None else make_production_mesh(
        multi_pod=multi_pod)
    n_chips = mesh.devices.size
    model = build_model(cfg)
    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": "x".join(str(s) for s in mesh.devices.shape),
        "axes": list(mesh.axis_names), "n_chips": int(n_chips),
        "mode": shape.mode, "param_count": cfg.param_count(),
        "param_count_active": cfg.param_count(active_only=True),
        "optimizer": cfg.optimizer, "layout": layout,
    }
    t0 = time.time()

    params_sds = params_shapes(model)
    p_specs = param_pspecs(params_sds, mesh, layout=layout)
    p_shard = to_shardings(p_specs, mesh)

    opt_sds = cache_sds = None
    if shape.mode == "train":
        opt = make_optimizer(cfg.optimizer)
        opt_sds = jax.eval_shape(opt.init, params_sds)
        o_specs = state_pspecs(opt_sds, params_sds, p_specs, mesh)
        o_shard = to_shardings(o_specs, mesh)
        batch = train_inputs(cfg, shape)
        b_shard = jax.tree_util.tree_map(
            lambda s: jax.NamedSharding(
                mesh, batch_pspec(mesh, len(s.shape), layout=layout)),
            batch)
        step = make_train_step(model, opt)
        jitted = jax.jit(step,
                         in_shardings=(p_shard, o_shard, b_shard),
                         out_shardings=(p_shard, o_shard, None),
                         donate_argnums=(0, 1))
        args = (_sds_with(p_shard, params_sds), _sds_with(o_shard, opt_sds),
                _sds_with(b_shard, batch))
    elif shape.mode == "prefill":
        batch = train_inputs(cfg, shape)
        if "labels" in batch:
            del batch["labels"]
        b_shard = jax.tree_util.tree_map(
            lambda s: jax.NamedSharding(
                mesh, batch_pspec(mesh, len(s.shape), layout=layout)),
            batch)
        step = make_forward_step(model)
        jitted = jax.jit(step, in_shardings=(p_shard, b_shard))
        args = (_sds_with(p_shard, params_sds), _sds_with(b_shard, batch))
    else:  # decode
        cache_sds = cache_shapes(model, cfg, shape)
        c_specs = cache_pspecs(cache_sds, mesh, shape.global_batch)
        c_shard = to_shardings(c_specs, mesh)
        batch = decode_inputs(cfg, shape)
        fsdp_size = mesh.shape["data"] * mesh.shape.get("pod", 1)
        b_div = shape.global_batch % fsdp_size == 0
        b_shard = jax.tree_util.tree_map(
            lambda s: jax.NamedSharding(
                mesh, batch_pspec(mesh, len(s.shape), batch_divisible=b_div,
                                  layout=layout)),
            batch)
        step = make_serve_step(model)
        jitted = jax.jit(step,
                         in_shardings=(p_shard, c_shard, b_shard, None),
                         out_shardings=(None, c_shard),
                         donate_argnums=(1,))
        args = (_sds_with(p_shard, params_sds), _sds_with(c_shard, cache_sds),
                _sds_with(b_shard, batch),
                jax.ShapeDtypeStruct((), jnp.int32))
        # decode position: last cache slot (seq_len-1)

    with activation_mesh(mesh, layout=layout):
        lowered = jitted.lower(*args)
    rec["lower_s"] = round(time.time() - t0, 2)
    t1 = time.time()
    compiled = lowered.compile()
    rec["compile_s"] = round(time.time() - t1, 2)

    rec["memory_analysis"] = _mem_analysis(compiled)
    rec["cost_analysis_raw"] = _cost_analysis(compiled)  # scan bodies x1!
    hlo = compiled.as_text()
    cost = hlo_cost(hlo)  # trip-count-aware per-device costs
    rec["hlo_cost"] = {
        "dot_flops_per_device": cost.dot_flops,
        "dot_bytes_per_device": cost.dot_bytes,
        "collective_bytes_per_device": cost.collective_bytes,
        "collectives": cost.collectives,
    }
    if hlo_dir:
        os.makedirs(hlo_dir, exist_ok=True)
        with open(os.path.join(
                hlo_dir, f"{arch}__{shape_name}__{rec['mesh']}.hlo"),
                "w") as f:
            f.write(hlo)

    # ---- roofline (GLOBAL = per-device HLO cost x chips; memory term from
    # the analytic traffic model in launch/roofline.py) ----
    tokens = shape.global_batch * (shape.seq_len if shape.mode != "decode"
                                   else 1)
    p_bytes = _tree_bytes(params_sds)
    opt_bytes = (_tree_bytes(opt_sds) if shape.mode == "train" else 0.0)
    cache_bytes = (_tree_bytes(cache_sds) if shape.mode == "decode" else 0.0)
    n_layers_eff = cfg.n_layers + (cfg.n_enc_layers
                                   if cfg.is_encoder_decoder else 0)
    mem_global = analytic_memory_bytes(
        shape.mode, params_bytes=p_bytes, opt_bytes=opt_bytes,
        cache_bytes=cache_bytes, tokens=tokens, d_model=cfg.d_model,
        n_layers=n_layers_eff,
        act_bytes=jnp.dtype(cfg.param_dtype).itemsize)
    rec["bytes"] = {"params": p_bytes, "opt_state": opt_bytes,
                    "kv_cache": cache_bytes, "memory_traffic_global": mem_global,
                    "params_per_device": p_bytes / n_chips,
                    "hbm_per_device": (p_bytes + opt_bytes + cache_bytes)
                    / n_chips}
    flops_global = cost.dot_flops * n_chips
    coll_global = cost.collective_bytes * n_chips
    rec["roofline"] = roofline_terms(n_chips, flops_global, mem_global,
                                     coll_global,
                                     device_kind=PRODUCTION_DEVICE_KIND)
    # MODEL_FLOPS = 6*N_active*tokens (train) / 2*N_active*tokens (fwd)
    mult = 6.0 if shape.mode == "train" else 2.0
    model_flops = mult * cfg.param_count(active_only=True) * tokens
    rec["model_flops"] = model_flops
    rec["useful_flops_ratio"] = (model_flops / flops_global
                                 if flops_global else None)
    rec["ok"] = True
    return rec


def choose_layout(arch: str, shape_name: str, n_chips: int) -> str:
    """Auto layout: pure-DP for small models on train_4k (TP activation
    all-reduces dominate otherwise — §Perf iteration 2: 7x collective-term
    win on h2o-danube), 2-D FSDP x TP everywhere else."""
    cfg = get_arch_config(arch)
    shape = SHAPES[shape_name]
    if shape.mode == "decode":
        # weights stay resident (no per-token FSDP gathers) — §Perf iter. 3
        return "decode"
    if (shape.mode == "train" and cfg.param_count() < 12e9
            and shape.global_batch % n_chips == 0):
        return "dp"
    return "2d"


def result_path(arch: str, shape_name: str, mesh_tag: str) -> str:
    os.makedirs(RESULTS_DIR, exist_ok=True)
    return os.path.join(RESULTS_DIR, f"{arch}__{shape_name}__{mesh_tag}.json")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_NAMES)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="every supported (arch x shape) on this mesh")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--hlo-dir", default=None)
    ap.add_argument("--layout", choices=("auto", "2d", "dp", "decode"), default="auto")
    args = ap.parse_args()

    mesh = make_production_mesh(multi_pod=args.multi_pod)
    mesh_tag = "x".join(str(s) for s in mesh.devices.shape)
    combos = []
    if args.all:
        for a in ARCH_NAMES:
            for s in SHAPES:
                if supports_shape(a, s):
                    combos.append((a, s))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        combos = [(args.arch, args.shape)]

    failures = 0
    for arch, shape_name in combos:
        out = result_path(arch, shape_name, mesh_tag)
        if args.skip_existing and os.path.exists(out):
            print(f"[skip] {arch} x {shape_name} ({mesh_tag})")
            continue
        layout = (choose_layout(arch, shape_name, mesh.devices.size)
                  if args.layout == "auto" else args.layout)
        print(f"[dryrun] {arch} x {shape_name} on {mesh_tag} "
              f"(layout={layout}) ...", flush=True)
        try:
            rec = lower_one(arch, shape_name, mesh=mesh,
                            hlo_dir=args.hlo_dir, layout=layout)
            print(f"  lower {rec['lower_s']}s compile {rec['compile_s']}s "
                  f"dominant={rec['roofline']['dominant']} "
                  f"step={rec['roofline']['roofline_step_s']:.4f}s "
                  f"useful={rec['useful_flops_ratio'] and round(rec['useful_flops_ratio'],3)}")
            print(f"  memory_analysis: {rec['memory_analysis']}")
            print(f"  hbm/device={rec['bytes']['hbm_per_device']/1e9:.2f}GB "
                  f"collective/dev={rec['hlo_cost']['collective_bytes_per_device']/1e9:.3f}GB")
        except Exception as e:
            if _on_chip():
                raise
            failures += 1
            rec = {"arch": arch, "shape": shape_name, "mesh": mesh_tag,
                   "ok": False, "error": str(e),
                   "traceback": traceback.format_exc()}
            print(f"  FAILED: {e}")
        with open(out, "w") as f:
            json.dump(rec, f, indent=2, default=str)
    if failures:
        raise SystemExit(f"{failures} dry-run combination(s) failed")


if __name__ == "__main__":
    main()
