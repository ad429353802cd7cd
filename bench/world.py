"""Everything a run starts from, made on the device from ``--seed``.

One jitted call draws the scenario knobs, the twin population, the radio
realization, the outage and byzantine masks, the association policy's actor
weights (where the configuration runs one) and, where the configuration
trains an FL model, the model's weights and data set. The program receives
these as its initial state; the plain reference starts from the same call.
Nothing here imports the program.
"""
import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int) -> jnp.ndarray:
    """A raw (2,) uint32 key holding all 64 bits of ``seed``."""
    seed = int(seed) % 2 ** 64
    return jnp.asarray(np.array([seed >> 32, seed & 0xFFFFFFFF], np.uint32))


def model_module(cfg):
    """The FL model's module ``models/<model>.py``, or None where the
    configuration runs no FL (``"model": null``). The harness reads a model
    only through these names of it:

    * ``make_data(key_train, key_test, cfg) -> (x, y, x_test, y_test)``;
    * ``init(key, cfg)`` - the trained tree; optionally ``init_frozen(key,
      cfg)`` - a tree that is neither trained nor aggregated;
    * ``forward(params, xs, *, precision, dtype)``, or ``loss(params,
      frozen, xs, ys, *, precision, dtype)`` and ``accuracy(...)`` alike
      (without them, softmax cross-entropy over ``forward``'s logits and one
      label per example);
    * ``forward_flops(cfg)``, ``train_flops(cfg)`` - FLOPs per example;
    * ``DATA_SET`` - the data set's name, as the program's FL state takes it.
    """
    if cfg["model"] is None:
        return None
    return importlib.import_module(f"models.{cfg['model']}")


def freqs_hz(cfg):
    """(M,) nominal BS frequencies: the config's table cycled over the BSs."""
    table = np.asarray(cfg["bs_freqs_ghz"], np.float32)
    return jnp.asarray(table[np.arange(cfg["n_bs"]) % table.size] * 1e9)


def actor_dims(cfg):
    """(per-twin features, per-BS features, compact state, hidden, head) of
    the association policy's actor."""
    pol = cfg["policy"]
    g = 4 + cfg["wireless"]["n_subchannels"] + (2 if cfg["consensus"] else 0)
    return 2, g, cfg["n_bs"] * g + 4 * 2, tuple(pol["hidden"]), pol["head"]


def _actor(key, cfg):
    """One actor per BS, stacked on a leading BS axis: He-normal weights,
    zero biases."""
    f, _, compact, hidden, hs = actor_dims(cfg)
    m, c = cfg["n_bs"], cfg["wireless"]["n_subchannels"]
    ks = iter(jax.random.split(key, 16))

    def he(shape, scale=1.0):
        return jax.random.normal(next(ks), (m,) + shape) * (2.0 / shape[0]) ** 0.5 * scale

    zeros = lambda *shape: jnp.zeros((m,) + shape, jnp.float32)  # noqa: E731
    sizes = (compact + f,) + hidden
    return {
        "attn_q": jax.random.normal(next(ks), (m, f)) * 0.5,
        "trunk": [{"w": he((a, b)), "b": zeros(b)} for a, b in zip(sizes[:-1], sizes[1:])],
        "wt": he((f, hs)), "wg": he((hidden[-1], hs)), "bh": zeros(hs),
        "wo": he((hs, 1), 0.5), "bo": zeros(1),
        "wb": he((hidden[-1], 1)), "bb": zeros(1),
        "wtau": he((hidden[-1], c)), "btau": zeros(c),
    }


def _uniform(key, lo_hi):
    lo, hi = lo_hi
    return jax.random.uniform(key, (), minval=lo, maxval=hi)


def _make(cfg, traffic, key):
    n, m = cfg["capacity"], cfg["n_bs"]
    c = cfg["wireless"]["n_subchannels"]
    kn = traffic["knobs"]
    ks = jax.random.split(jax.random.fold_in(key, 7), 16)
    knobs = {name: _uniform(ks[i], kn[name]) for i, name in enumerate(
        ("data_min", "data_max", "skew", "straggler", "outage", "byzantine", "quorum"))}
    knobs["block_size"] = jnp.float32(kn["block_size_bits"])
    u = jax.random.uniform(ks[8], (n,))
    data = knobs["data_min"] + (knobs["data_max"] - knobs["data_min"]) * u ** knobs["skew"]
    w = cfg["wireless"]
    h_up = jax.random.exponential(ks[9], (m, c))
    h_down = jax.random.exponential(ks[10], (m, c))
    dist = jax.random.uniform(ks[11], (m,), minval=w["min_dist_m"], maxval=w["max_dist_m"])
    bad = jax.random.uniform(ks[12], (m,)) < jnp.clip(knobs["outage"], 0.0, 0.95)
    byz = jax.random.uniform(ks[13], (m,)) < knobs["byzantine"]
    out = {"knobs": knobs, "data_sizes": data.astype(jnp.float32), "h_up": h_up,
           "h_down": h_down, "dist": dist, "bad": bad, "byz": byz}
    model = model_module(cfg)
    if model is not None:
        out["params"] = model.init(ks[14], cfg)
        kd = jax.random.split(ks[15])
        out["x"], out["y"], out["x_test"], out["y_test"] = model.make_data(kd[0], kd[1], cfg)
        if hasattr(model, "init_frozen"):
            out["frozen"] = model.init_frozen(jax.random.fold_in(key, 19), cfg)
    if cfg["association"] == "factorized":
        out["actor"] = _actor(jax.random.fold_in(key, 17), cfg)
    return out


@functools.lru_cache(maxsize=None)
def _maker(cfg_json: str, traffic_json: str):
    import json
    cfg, traffic = json.loads(cfg_json), json.loads(traffic_json)
    return jax.jit(functools.partial(_make, cfg, traffic))


def make_world(cfg, traffic, seed):
    """The run's initial data, as device arrays, from one jitted call."""
    import json
    fn = _maker(json.dumps(cfg, sort_keys=True), json.dumps(traffic, sort_keys=True))
    return fn(seed_key(seed))
