"""Everything a run starts from, made on the device from ``--seed``.

One jitted call draws the scenario knobs, the twin population, the radio
realization, the outage and byzantine masks, the global model's weights, the
association policy's actor weights (where the configuration runs one) and
the CIFAR-10-sim data set. The program receives these as its initial state;
the plain reference starts from the same call. Nothing here imports the
program.
"""
import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np

# fixed, seed-independent class templates of the generated data set, as the
# program's own CIFAR-10-sim keeps them fixed (repro.data.cifar10._synthetic)
_TEMPLATE_KEY = 1234


def seed_key(seed: int) -> jnp.ndarray:
    """A raw (2,) uint32 key holding all 64 bits of ``seed``."""
    seed = int(seed) % 2 ** 64
    return jnp.asarray(np.array([seed >> 32, seed & 0xFFFFFFFF], np.uint32))


def model_module(cfg):
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


def _images(key, templ_key, n, classes):
    """Class-conditional Gabor-plus-blob textures with per-image noise and a
    per-channel shift, clipped to [0, 1] (the law of the program's
    CIFAR-10-sim, drawn on the device)."""
    kt = jax.random.split(templ_key, 4)
    angles = jax.random.uniform(kt[0], (classes,), minval=0.0, maxval=np.pi)
    freqs = jax.random.uniform(kt[1], (classes,), minval=3.0, maxval=9.0)
    colors = jax.random.uniform(kt[2], (classes, 3), minval=0.2, maxval=1.0)
    centers = jax.random.uniform(kt[3], (classes, 2), minval=0.25, maxval=0.75)
    yy, xx = jnp.meshgrid(jnp.arange(32, dtype=jnp.float32) / 32.0,
                          jnp.arange(32, dtype=jnp.float32) / 32.0, indexing="ij")
    u = (jnp.cos(angles)[:, None, None] * xx + jnp.sin(angles)[:, None, None] * yy)
    gabor = 0.5 + 0.5 * jnp.sin(2 * np.pi * freqs[:, None, None] * u)
    blob = jnp.exp(-(((xx - centers[:, 0, None, None]) ** 2
                      + (yy - centers[:, 1, None, None]) ** 2) / 0.05))
    base = (0.6 * gabor + 0.4 * blob)[..., None] * colors[:, None, None, :]
    ky, kj, ks = jax.random.split(key, 3)
    y = jax.random.randint(ky, (n,), 0, classes).astype(jnp.int32)
    jitter = 0.25 * jax.random.normal(kj, (n, 32, 32, 3))
    shift = 0.1 * jax.random.normal(ks, (n, 1, 1, 3))
    x = jnp.clip(base[y] + jitter + shift, 0.0, 1.0)
    return x.astype(jnp.float32), y


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
    params = model_module(cfg).init(ks[14], cfg["param_shapes"])
    templ = jax.random.PRNGKey(_TEMPLATE_KEY)
    kd = jax.random.split(ks[15])
    x, y = _images(kd[0], templ, cfg["n_train"], cfg["n_classes"])
    x_test, y_test = _images(kd[1], templ, cfg["n_test"], cfg["n_classes"])
    out = {"knobs": knobs, "data_sizes": data.astype(jnp.float32), "h_up": h_up,
           "h_down": h_down, "dist": dist, "bad": bad, "byz": byz, "params": params,
           "x": x, "y": y, "x_test": x_test, "y_test": y_test}
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
