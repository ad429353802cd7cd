#!/usr/bin/env python3
"""CPU check of the model FLOPs functions against XLA's own count.

    JAX_PLATFORMS=cpu python3 bench/check_flops.py

For each model of ``bench/configs``, compiles one forward pass, and one
gradient of the loss, at batch 4 on the CPU and compares
``models/<model>.forward_flops`` and ``train_flops`` with the FLOPs of XLA's
``cost_analysis()``. XLA also counts bias adds, ReLUs, pooling and the
softmax, so its count sits a little above ours; the script prints both and
fails when they differ by more than 2%.
"""
import glob
import json
import os
import sys

TOLERANCE = 0.02
# a batch above 1, so that a weight gradient is a contraction over the batch
# and not an outer product, which XLA counts as one multiply per element
BATCH = 4
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def main():
    import jax
    import jax.numpy as jnp

    from world import model_module

    bad = 0
    seen = set()
    for path in sorted(glob.glob(os.path.join(HERE, "configs", "*.json"))):
        with open(path) as f:
            cfg = json.load(f)
        if cfg["model"] in seen:
            continue
        seen.add(cfg["model"])
        mod = model_module(cfg)
        params = mod.init(jax.random.PRNGKey(0), cfg["param_shapes"])
        x = jnp.zeros((BATCH,) + mod.IMAGE, jnp.float32)
        fwd = jax.jit(lambda p, x: mod.forward(p, x, precision=jax.lax.Precision.HIGHEST,
                                               dtype=jnp.float32))
        y = jnp.zeros((BATCH,), jnp.int32)

        def loss(p, x, y):
            logits = mod.forward(p, x, precision=jax.lax.Precision.HIGHEST, dtype=jnp.float32)
            return -jnp.mean(jnp.take_along_axis(jax.nn.log_softmax(logits), y[:, None], 1))

        for what, fn, args, ours in (
                ("forward", fwd, (params, x), mod.forward_flops(cfg["param_shapes"])),
                ("train", jax.jit(jax.grad(loss)), (params, x, y),
                 mod.train_flops(cfg["param_shapes"]))):
            cost = fn.lower(*args).compile().cost_analysis()
            cost = cost[0] if isinstance(cost, (list, tuple)) else cost
            xla = float(cost["flops"]) / BATCH
            rel = (xla - ours) / ours
            print(f"{cfg['model']} {what} per image: ours {ours:.6g}, XLA cost_analysis {xla:.6g}, "
                  f"XLA above ours by {100 * rel:.3f}%")
            bad += abs(rel) > TOLERANCE
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
