"""The one traffic generator: per-round keys and FL plans, from ``--seed``.

A mix is a data file under ``traffic/`` (participants per round, churn rates,
rounds per call, pipeline depth, knob ranges). For global round ``r`` the
generator draws, independently of every other round:

* the round's key streams (migration, faults, chain, churn, dynamics), each
  a raw (2,) uint32 key folded from the seed and ``r``;
* where the configuration trains an FL model, the FL plan, by the law of
  the program's ``stream_fl_plan``: ``P`` distinct participants drawn
  uniformly from the capacity (Floyd's algorithm), and for each of them
  ``local_iters`` minibatches of ``B`` distinct samples drawn uniformly
  from the first ``n_use`` samples of its cyclic shard (twin ``u``'s shard
  starts at ``u * stride`` and wraps round the data set).

Rounds are generated ``block_calls`` calls at a time by one jitted call, so
the supply never runs out however fast the step gets. Nothing here imports
the program.
"""
import functools

import jax
import jax.numpy as jnp

from world import seed_key

STREAMS = ("mig", "fault", "chain", "churn", "dyn")
_FOLDS = {"mig": 3, "fault": 5, "chain": 8, "churn": 11, "dyn": 12}
_TRAFFIC_FOLD = 2


def plan_shape(cfg):
    n_train, cap = cfg["n_train"], cfg["capacity"]
    stride = max(1, n_train // cap)
    size = cfg["shard_size"]
    n_use = min(size, max(8, int(cfg["shard_use"] * size)))
    if n_use < cfg["batch_size"]:
        raise ValueError(f"{cfg['name']}: a shard's usable {n_use} samples do not "
                         f"fill a batch of {cfg['batch_size']}")
    return stride, n_use


def _distinct(key, n, p):
    """``p`` distinct ids drawn uniformly from ``range(n)`` (Floyd's
    algorithm: for j = n-p .. n-1 draw t from [0, j]; take t unless it is
    taken already, else j). Costs p draws, whatever ``n``."""
    ks = jax.random.split(key, p)

    def body(i, out):
        j = n - p + i
        t = jax.random.randint(ks[i], (), 0, j + 1)
        return out.at[i].set(jnp.where(jnp.any(out == t), j, t))

    return jax.lax.fori_loop(0, p, body, jnp.full((p,), -1, jnp.int32))


def _round(cfg, traffic, key, r):
    """Global round ``r``'s keys and plan (None without FL)."""
    kr = jax.random.fold_in(key, r)
    keys = {s: jax.random.fold_in(kr, _FOLDS[s]) for s in STREAMS}
    if cfg["model"] is None:
        return keys, None
    cap, p = cfg["capacity"], traffic["participants"]
    n_it, b = cfg["local_iters"], cfg["batch_size"]
    stride, n_use = plan_shape(cfg)
    k_users, k_batch = jax.random.split(jax.random.fold_in(kr, 99))
    users = _distinct(k_users, cap, p)
    scores = jax.random.uniform(k_batch, (p, n_it, n_use))
    j = jax.lax.top_k(scores, b)[1]                      # (P, L, B) distinct
    batch = (users[:, None, None] * stride + j) % cfg["n_train"]
    return keys, {"users": users, "batch": batch.astype(jnp.int32),
                  "valid": jnp.ones((p,), bool)}


def _block(cfg, traffic, key, first_round):
    """``block_calls`` calls of ``rounds_per_call`` rounds from global round
    ``first_round``: a list of (keys, plan) dicts (plan None without FL),
    each leaf led by the call's round axis."""
    rpc, n_calls = traffic["rounds_per_call"], traffic["block_calls"]
    rounds = first_round + jnp.arange(n_calls * rpc)
    keys, plan = jax.vmap(lambda r: _round(cfg, traffic, key, r))(rounds)
    out = []
    for c in range(n_calls):
        sl = slice(c * rpc, (c + 1) * rpc)
        out.append(jax.tree_util.tree_map(lambda v: v[sl], (keys, plan)))
    return out


class Traffic:
    """Endless per-call inputs: ``next()`` returns the next call's
    ``(keys, plan)``, generating a new block when one runs out (``on_block``
    wraps that generation, e.g. in a trace span)."""

    def __init__(self, cfg, traffic, seed, *, on_block=None):
        self.cfg, self.traffic = cfg, traffic
        self.key = jax.random.fold_in(seed_key(seed), _TRAFFIC_FOLD)
        self.fn = jax.jit(functools.partial(_block, cfg, traffic))
        self.on_block = on_block
        self.next_round = 0
        self.queue = []

    def _refill(self):
        self.queue = self.fn(self.key, jnp.int32(self.next_round))

    def next(self):
        if not self.queue:
            if self.on_block is not None:
                with self.on_block():
                    self._refill()
            else:
                self._refill()
        self.next_round += self.traffic["rounds_per_call"]
        return self.queue.pop(0)


def rounds(cfg, traffic, seed, first, n):
    """Rounds ``first .. first+n-1`` as one stacked (keys, plan), for the
    reference."""
    key = jax.random.fold_in(seed_key(seed), _TRAFFIC_FOLD)
    r = jnp.arange(first, first + n)
    return jax.vmap(lambda i: _round(cfg, traffic, key, i))(r)
