#!/usr/bin/env python3
"""CPU check of the model FLOPs functions against XLA's own count.

    JAX_PLATFORMS=cpu python3 bench/check_flops.py

For each model of ``bench/configs``, compiles one forward pass (the loss,
for a model that gives no ``forward``), and one gradient of the loss with
respect to the trained tree, at batch 4 on the CPU and compares
``models/<model>.forward_flops`` and ``train_flops`` (per example) with the
FLOPs of XLA's ``cost_analysis()``. XLA also counts bias adds, ReLUs,
pooling and the softmax, so its count sits a little above ours; the script
prints both and fails when they differ by more than 2%.
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
    from world import model_module

    bad = 0
    seen = {None}
    for path in sorted(glob.glob(os.path.join(HERE, "configs", "*.json"))):
        with open(path) as f:
            cfg = json.load(f)
        if cfg["model"] in seen:
            continue
        seen.add(cfg["model"])
        bad += check(model_module(cfg), cfg)
    return 1 if bad else 0


def check(mod, cfg) -> int:
    """1 where ``mod``'s FLOPs per example miss XLA's count by more than
    ``TOLERANCE``, else 0; prints both."""
    import jax
    import jax.numpy as jnp

    from reference import model_fns

    highest = jax.lax.Precision.HIGHEST
    key = jax.random.PRNGKey(0)
    params = mod.init(key, cfg)
    frozen = mod.init_frozen(key, cfg) if hasattr(mod, "init_frozen") else None
    x, y, _, _ = mod.make_data(key, key, dict(cfg, n_train=BATCH, n_test=BATCH))
    loss, _ = model_fns(mod, frozen, jnp.float32, highest)
    if hasattr(mod, "forward"):
        fwd = (lambda p, x, y: mod.forward(p, x, precision=highest, dtype=jnp.float32))
    else:
        fwd = loss
    bad = 0
    for what, fn, ours in (("forward", jax.jit(fwd), mod.forward_flops(cfg)),
                           ("train", jax.jit(jax.grad(loss)), mod.train_flops(cfg))):
        cost = fn.lower(params, x, y).compile().cost_analysis()
        cost = cost[0] if isinstance(cost, (list, tuple)) else cost
        xla = float(cost["flops"]) / BATCH
        rel = (xla - ours) / ours
        print(f"{cfg['model']} {what} per example: ours {ours:.6g}, XLA cost_analysis "
              f"{xla:.6g}, XLA above ours by {100 * rel:.3f}%")
        bad += abs(rel) > TOLERANCE
    return int(bad > 0)


if __name__ == "__main__":
    sys.exit(main())
