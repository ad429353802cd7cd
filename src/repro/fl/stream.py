"""Streamed federated learning: the real FL workload inside the serve loop.

``DTWNSystem.run_round`` is the batch-mode FL driver — host loops over
chosen twins, one jitted SGD call each, host lists stacked per round. This
module folds that workload into the always-on service (``repro.core.serve``):

* **Device-resident FL state** — :class:`FLState` rides inside the donated
  ``ServeState``: the global model, per-twin model/momentum buffers with a
  capacity-padded ``(capacity, ...)`` leading axis (twin-sharded under a
  scope, ``sharding.model_buffer_specs``), the malicious mask, and the
  train/eval data. Evicted twins' rows are zeroed and admitted twins
  warm-start from the current global model
  (:func:`fl_churn_update` — the churn-mask contract of ``serve.admit`` /
  ``serve.evict`` extended to model buffers).
* **Host-planned, device-trained rounds** — ``run_round``'s participant
  sampling and minibatch draws are host ``numpy.RandomState`` laws that
  cannot run in traced code, so :func:`stream_fl_plan` replays them
  up front into dense index plans (:class:`FLPlan`); the jitted round step
  then runs the whole round on device: vmapped local SGD (the shared
  ``fl.client.sgd_step`` under ``lax.scan``), scatter into the twin
  buffers, Eq. 4 over the capacity axis (plain or robust), the
  ``verify_metas`` chain gate on a fixed holdout slice, and Eq. 5.
* **Parity contract** — at a fixed full population (churn off) the
  streamed rounds reproduce ``run_round``: same participants, same
  minibatches, same update law, bit-identical Eq. 4 weights (integer-
  valued D_j sums are order-exact), and loss/param trajectories equal up
  to conv-batching float error (vmap lowers P independent convolutions to
  one grouped conv). Gated by ``tests/test_serve.py`` and
  ``bench_scale --serve-fl-gate``.

Aggregation runs over the **capacity axis**, not the participant axis:
non-participants carry weight 0 and the out-of-range association id, so
they drop out of every segment reduction by the same padding convention
the serve loop already enforces — and under a twin scope the reduction is
the sharded segment-reduce (local + psum), which a replicated
participant-axis reduction would double count.
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import consensus as consensus_mod
from repro.core import faults as faults_mod
from repro.core import hierarchy, sharding
from repro.fl import client as client_mod
from repro.models import cnn, tiny
from repro.optim import make_optimizer

__all__ = [
    "FLServeConfig", "FLPlan", "FLState", "MODELS", "get_model",
    "fl_init", "attach_fl", "stream_fl_plan", "plan_row", "fl_round",
    "fl_churn_update", "fl_specs", "cyclic_shards",
]


# model registry — everything the streamed trainer needs from a model,
# keyed by the hashable name carried in FLServeConfig
MODELS = {
    "cnn": cnn,    # the paper's Section-V CNN (~2.1M params)
    "tiny": tiny,  # ~3.3k params — per-twin buffers at N=10^4+
}


def get_model(name: str):
    if name not in MODELS:
        raise ValueError(f"model must be one of {sorted(MODELS)}, "
                         f"got {name!r}")
    return MODELS[name]


@dataclasses.dataclass(frozen=True)
class FLServeConfig:
    """Static streamed-FL knobs (hashable — rides jit-static inside
    ``ServeConfig.fl``). Mirrors the ``FLConfig`` fields the round step
    consumes; anything data-dependent lives in :class:`FLState`/:class:`FLPlan`.
    """
    model: str = "cnn"
    participants: int = 10       # P twins trained per round (run_round's
    #                              ``participating_users``)
    local_iters: int = 5
    batch_size: int = 32
    lr: float = 0.05
    momentum: float = 0.9
    weighted_global: bool = False
    aggregator: str = "fedavg"   # "fedavg" | "trimmed_mean" | "krum"
    trim_k: int = 1
    krum_f: int = 1
    attack: str = "label_flip"   # applied to malicious twins
    attack_boost: float = 5.0
    verify: bool = True          # ChainState-style Eq. 4 verify gate
    tolerance: float = 0.5       # DPoSChain's default loss tolerance
    n_eval: int = 256            # fixed holdout slice for losses/metrics


class FLPlan(NamedTuple):
    """One stream's host-precomputed round plans (leading axis n_rounds).

    ``users``  — (n_rounds, P) int32 chosen twin ids (-1 = unused slot).
    ``batch``  — (n_rounds, P, local_iters, B) int32 global sample indices.
    ``valid``  — (n_rounds, P) bool; the device additionally gates on the
    live ``active`` mask, so a planned participant that churned out
    contributes nothing.
    """
    users: jnp.ndarray
    batch: jnp.ndarray
    valid: jnp.ndarray


class FLState(NamedTuple):
    """Streamed-FL state — a subtree of the donated ``ServeState``.

    ``params`` (global model) and the datasets are replicated;
    ``twin_params``/``twin_mom``/``malicious`` carry the capacity-padded
    twin leading axis (sharded under a scope). Inactive twins' buffer rows
    are all-zero by the churn contract.
    """
    params: Any          # global model pytree
    twin_params: Any     # (capacity, ...) per-twin model rows
    twin_mom: Any        # (capacity, ...) per-twin SGD momentum rows
    malicious: jnp.ndarray  # (capacity,) bool
    x: jnp.ndarray       # (n_train, ...) training images
    y: jnp.ndarray       # (n_train,) labels
    x_eval: jnp.ndarray  # (n_eval, ...) fixed holdout slice
    y_eval: jnp.ndarray


def fl_specs(fcfg: Optional[FLServeConfig]):
    """Partition-spec prefix tree for the ``ServeState.fl`` slot: twin
    buffers sharded on their leading (capacity) axis, everything else
    replicated. ``P()`` when FL is off (covers the ``None`` subtree)."""
    from jax.sharding import PartitionSpec as P

    if fcfg is None:
        return P()
    return FLState(params=P(), twin_params=P(sharding.TWIN_AXIS),
                   twin_mom=P(sharding.TWIN_AXIS),
                   malicious=P(sharding.TWIN_AXIS),
                   x=P(), y=P(), x_eval=P(), y_eval=P())


# ---------------------------------------------------------------------------
# init — FL state from a dataset realization
# ---------------------------------------------------------------------------


def fl_init(fcfg: FLServeConfig, key, data, active, *,
            params=None, malicious=None, ts=None) -> FLState:
    """Fresh :class:`FLState` at capacity ``active.shape[0]``.

    ``data`` is the ``repro.data.cifar10.load`` tuple. ``active`` (the
    serve state's live mask, host or device) seeds the warm-start: live
    twins' buffer rows start at the global model, empty slots at zero.
    ``params`` overrides the global init (e.g. ``DTWNSystem.params`` for
    parity runs — the system inits from ``PRNGKey(seed)`` too). With a
    multi-shard ``ts`` (a ``TwinSharding``; ``active`` is then the padded
    global mask) the twin buffers are built already split over its mesh,
    so a state larger than one device never lands on one."""
    mdl = get_model(fcfg.model)
    (x, y), (x_test, y_test), _ = data
    active = np.asarray(active, bool)
    cap = active.shape[0]
    if params is None:
        params = mdl.init_params(key)
    # private copy: the serve loop DONATES its state every round, and a
    # shared buffer (e.g. DTWNSystem.params in a parity pairing) would be
    # deleted out from under the caller on the first step
    params = jax.tree_util.tree_map(jnp.array, params)
    n_eval = min(fcfg.n_eval, x_test.shape[0])
    if malicious is None:
        malicious = np.zeros(cap, bool)

    def buffers(params, active):
        def per_twin(p):
            rows = jnp.broadcast_to(p[None], (cap,) + p.shape)
            m = active.reshape((-1,) + (1,) * p.ndim)
            return jnp.where(m, rows, 0.0).astype(p.dtype)

        return (jax.tree_util.tree_map(per_twin, params),
                jax.tree_util.tree_map(
                    lambda p: jnp.zeros((cap,) + p.shape, p.dtype), params))

    malicious = jnp.asarray(malicious)
    if ts is not None and ts.n_shards > 1:
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        twin = NamedSharding(ts.mesh, P(sharding.TWIN_AXIS))
        buffers = jax.jit(buffers, out_shardings=(twin, twin))
        malicious = jax.device_put(malicious, twin)
    twin_params, twin_mom = buffers(params, jnp.asarray(active))

    return FLState(
        params=params,
        twin_params=twin_params,
        twin_mom=twin_mom,
        malicious=malicious,
        x=jnp.asarray(x), y=jnp.asarray(y),
        x_eval=jnp.asarray(x_test[:n_eval]),
        y_eval=jnp.asarray(y_test[:n_eval]))


def attach_fl(scfg, state, system, data, assoc=None):
    """Bridge a batch ``DTWNSystem`` into a serve state: attaches an
    :class:`FLState` built from the system's model init, shards, and
    malicious mask, AND restamps the env's ``data_sizes`` (and, when
    given, ``assoc``) from the system, masked by the live set — so the
    streamed rounds train, weight (Eq. 4), and price (Eqs. 12-17) the
    *same data realization* the batch driver does. Returns the new
    ``ServeState``."""
    mdl = get_model(scfg.fl.model)
    want = jax.eval_shape(mdl.init_params, jax.random.PRNGKey(0))
    shapes = jax.tree_util.tree_map(lambda x: x.shape, want)
    have = jax.tree_util.tree_map(lambda x: jnp.shape(x), system.params)
    if shapes != have:
        raise ValueError(
            f"FLServeConfig.model={scfg.fl.model!r} does not match the "
            f"system's parameter tree — the batch DTWNSystem trains the "
            f"paper CNN; pair it with model='cnn'")
    active = np.asarray(state.active, bool)
    fl = fl_init(scfg.fl, None, data, active, params=system.params,
                 malicious=system.malicious)
    data_sizes = jnp.where(jnp.asarray(active),
                           jnp.asarray(system.data_sizes, jnp.float32), 0.0)
    env = state.env._replace(data_sizes=data_sizes)
    if assoc is not None:
        n_bs = int(system.cfg.n_bs)
        env = env._replace(assoc=jnp.where(
            jnp.asarray(active), jnp.asarray(assoc, jnp.int32), n_bs))
    return state._replace(env=env, fl=fl)


def cyclic_shards(n_samples: int, n_users: int, shard_size: int):
    """Overlapping fixed-size shards for population-scale sweeps: twin u
    reads ``shard_size`` consecutive samples starting at a stride offset,
    wrapping around the dataset. Sample reuse across twins is deliberate —
    at N=10^4+ the dataset is smaller than the population, and the sweep
    measures throughput, not statistical efficiency."""
    stride = max(1, n_samples // n_users)
    base = np.arange(shard_size)
    return [((u * stride + base) % n_samples).astype(np.int64)
            for u in range(n_users)]


# ---------------------------------------------------------------------------
# the plan — run_round's host RNG laws, replayed up front
# ---------------------------------------------------------------------------


def stream_fl_plan(fcfg: FLServeConfig, shards, n_rounds: int, *,
                   seed: int = 0, b: float = 0.5,
                   start_round: int = 0) -> FLPlan:
    """Precompute ``n_rounds`` of participant + minibatch index plans.

    Replays ``DTWNSystem.run_round``'s exact host RNG laws so fixed-
    population streamed rounds are the batch rounds:

    * participants: ``RandomState(seed + 1).choice(n_users, P,
      replace=False)`` per round (the ``active=None`` path — eval draws no
      longer share this stream, the PR 10 bugfix);
    * per twin u at round t: ``n_use = min(shard.size, max(8,
      int(b * shard.size)))``, ``use = shard[:n_use]``, then
      ``RandomState(t*1000 + u)`` draws ``local_iters`` batches
      ``use[choice(n_use, B, replace=n_use < B)]``.

    ``B`` must not exceed any participant's ``n_use`` (rectangular plans;
    ``run_round`` would shrink the batch per twin, which a stacked device
    plan cannot express) — a ``ValueError`` names the offending twin.
    Under churn some planned participants may be inactive on device; they
    are gated out there (weight 0), which has no batch counterpart — churn
    mode is the service's own regime.
    """
    n_users = len(shards)
    p = min(fcfg.participants, n_users)
    rng = np.random.RandomState(seed + 1)
    users = np.full((n_rounds, fcfg.participants), -1, np.int64)
    batch = np.zeros((n_rounds, fcfg.participants, fcfg.local_iters,
                      fcfg.batch_size), np.int64)
    valid = np.zeros((n_rounds, fcfg.participants), bool)
    for t in range(n_rounds):
        chosen = rng.choice(n_users, size=p, replace=False)
        users[t, :p] = chosen
        valid[t, :p] = True
        for k, u in enumerate(chosen):
            shard = np.asarray(shards[u])
            n_use = min(shard.size, max(8, int(b * shard.size)))
            if n_use < fcfg.batch_size:
                raise ValueError(
                    f"twin {u}: n_use={n_use} < batch_size="
                    f"{fcfg.batch_size} — rectangular plans need every "
                    f"participant to fill a batch (shrink batch_size or "
                    f"grow the shards)")
            use = shard[:n_use]
            rng_u = np.random.RandomState((start_round + t) * 1000 + int(u))
            for i in range(fcfg.local_iters):
                idx = rng_u.choice(n_use, size=fcfg.batch_size,
                                   replace=False)
                batch[t, k, i] = use[idx]
    return FLPlan(users=jnp.asarray(users, jnp.int32),
                  batch=jnp.asarray(batch, jnp.int32),
                  valid=jnp.asarray(valid))


def plan_row(plan: FLPlan, t: int) -> FLPlan:
    """Round ``t``'s plan out of a :func:`stream_fl_plan` stack."""
    return jax.tree_util.tree_map(lambda x: x[t], plan)


# ---------------------------------------------------------------------------
# the round — vmapped local SGD + Eq. 4/5 on device
# ---------------------------------------------------------------------------


def fl_round(fcfg: FLServeConfig, fl: FLState, plan: FLPlan, *,
             active, data_sizes, assoc, n_bs: int):
    """One streamed FL round. Traced inside the serve round step.

    Participants (gated by ``plan.valid`` and the live ``active`` mask)
    warm-start from the global model, run ``local_iters`` shared-step SGD
    under vmap, land in their twin buffer rows, and aggregate over the
    capacity axis: Eq. 4 (plain or robust), the ``verify_metas`` loss gate
    on the fixed holdout slice, Eq. 5 over accepted BSs (previous global
    kept when nothing passes — ``run_round`` behavior). Returns
    ``(fl', metrics)``.
    """
    mdl = get_model(fcfg.model)
    opt = make_optimizer("sgd", lr=fcfg.lr, momentum=fcfg.momentum)
    if sharding.in_scope() is not None:
        # replicated-in-fact inputs (global model, plan, eval slice) enter
        # the shard_map through P() specs, which the replication checker
        # treats as shard-varying; stamp them replicated (value-preserving
        # pmean/pmax) so the local-SGD scan carry and the P()-spec'd
        # outputs (global model, metrics) check clean.
        fl = fl._replace(params=sharding.stamp_replicated(fl.params),
                         x_eval=sharding.stamp_replicated(fl.x_eval),
                         y_eval=sharding.stamp_replicated(fl.y_eval))
        plan = sharding.stamp_replicated(plan)
    with jax.named_scope("gather"):
        u = plan.users
        part = plan.valid & sharding.twin_gather(active, u, fill=False)
        mal = part & sharding.twin_gather(fl.malicious, u, fill=False)
        w_u = jnp.where(part, sharding.twin_gather(data_sizes, u, fill=0.0),
                        0.0)
        assoc_u = jnp.where(part, sharding.twin_gather(assoc, u, fill=n_bs),
                            n_bs).astype(jnp.int32)

        # pre-gathered minibatches: (P, L, B, ...) — both attacks train on
        # flipped labels (fl.client law); model_replacement also boosts
        # below
        xb = jnp.take(fl.x, plan.batch, axis=0)
        yb = jnp.take(fl.y, plan.batch, axis=0)
        if sharding.in_scope() is not None:
            # the dataset itself stays unstamped (stamping it would pmean
            # the full training set every round) — stamp the per-round
            # gathers
            xb = sharding.stamp_replicated(xb)
            yb = sharding.stamp_replicated(yb)
        yb = jnp.where(mal[:, None, None], client_mod.flip_labels(yb), yb)

    def train_one(xs, ys):
        p, s, losses = client_mod.local_sgd(mdl.loss_fn, opt, fl.params,
                                            xs, ys)
        return p, s["mom"], losses[-1]

    with jax.named_scope("local_sgd"):
        p_new, mom_new, _ = jax.vmap(train_one)(xb, yb)
        if fcfg.attack == "model_replacement":
            boost = jnp.where(mal, fcfg.attack_boost, 1.0)

            def replace(old, new):
                b = boost.reshape((-1,) + (1,) * old.ndim)
                return old[None] + b * (new - old[None])

            p_new = jax.tree_util.tree_map(replace, fl.params, p_new)

    # scatter trained rows into the twin buffers (dropped participants ->
    # sentinel -1 -> no write); aggregation then runs over the capacity
    # axis so the sharded segment-reduce sees each row exactly once
    with jax.named_scope("scatter"):
        rows = jnp.where(part, u, -1)
        twin_params = jax.tree_util.tree_map(
            lambda buf, r: sharding.twin_scatter_rows(buf, rows, r),
            fl.twin_params, p_new)
        twin_mom = jax.tree_util.tree_map(
            lambda buf, r: sharding.twin_scatter_rows(buf, rows, r),
            fl.twin_mom, mom_new)
        w_cap = sharding.twin_scatter_rows(jnp.zeros_like(data_sizes), rows,
                                           w_u)
        assoc_cap = sharding.twin_scatter_rows(
            jnp.full(data_sizes.shape, n_bs, jnp.int32), rows, assoc_u)

    # --- Eq. 4 (per-BS), plain or robust ---
    with jax.named_scope("eq4"):
        if fcfg.aggregator == "fedavg":
            per_bs, bs_w = hierarchy.bs_aggregate_stacked(
                twin_params, w_cap, assoc_cap, n_bs)
            n_cli = n_sus = None
        else:
            per_bs, bs_w, survivor = faults_mod.robust_bs_aggregate_stacked(
                twin_params, w_cap, assoc_cap, n_bs,
                aggregator=fcfg.aggregator, trim_k=fcfg.trim_k,
                krum_f=fcfg.krum_f)
            n_cli, n_sus = faults_mod.suspect_counts(survivor, assoc_cap,
                                                     n_bs)

    # --- chain verify gate on the fixed holdout slice ---
    eval_batch = {"images": fl.x_eval, "labels": fl.y_eval}
    submitted = bs_w > 0.0
    if fcfg.verify:
        with jax.named_scope("verify"):
            bs_losses = jax.vmap(lambda prm: mdl.loss_fn(prm, eval_batch))(
                per_bs)
            accept = consensus_mod.verify_metas(
                bs_losses, submitted, tolerance=fcfg.tolerance,
                n_clients=n_cli, n_suspect=n_sus)
    else:
        accept = submitted

    # --- Eq. 5 over accepted BSs; keep the old global when none pass ---
    with jax.named_scope("eq5"):
        agg = hierarchy.global_aggregate_stacked(
            per_bs, bs_w, accept, weighted_global=fcfg.weighted_global)
        any_acc = jnp.any(accept)
        params = jax.tree_util.tree_map(
            lambda old, new: jnp.where(any_acc, new, old), fl.params, agg)

    with jax.named_scope("eval"):
        loss = mdl.loss_fn(params, eval_batch)
        acc = mdl.accuracy(params, eval_batch)
    fl2 = fl._replace(params=params, twin_params=twin_params,
                      twin_mom=twin_mom)
    metrics = {
        "fl_loss": loss, "fl_accuracy": acc, "fl_bs_weight": bs_w,
        "fl_n_participants": jnp.sum(part.astype(jnp.int32)),
        "fl_accept_frac": (jnp.sum(accept.astype(jnp.float32))
                           / jnp.maximum(jnp.sum(
                               submitted.astype(jnp.float32)), 1.0)),
    }
    return fl2, metrics


def fl_churn_update(fl: FLState, joined, left) -> FLState:
    """Apply one round's churn to the FL buffers: admitted twins
    warm-start from the *current* global model (zero momentum), evicted
    twins' rows are zeroed — the padding convention, so a departed twin's
    row can never re-enter an Eq. 4 weight. ``joined``/``left`` are
    (capacity,) masks (shard-local under a scope, like the buffers)."""
    joined = jnp.asarray(joined, bool)
    left = jnp.asarray(left, bool)

    def upd_params(buf, g):
        j = joined.reshape((-1,) + (1,) * g.ndim)
        l = left.reshape((-1,) + (1,) * g.ndim)
        out = jnp.where(j, g[None], buf)
        return jnp.where(l, 0.0, out).astype(buf.dtype)

    def upd_mom(buf):
        m = (joined | left).reshape((-1,) + (1,) * (buf.ndim - 1))
        return jnp.where(m, 0.0, buf).astype(buf.dtype)

    return fl._replace(
        twin_params=jax.tree_util.tree_map(upd_params, fl.twin_params,
                                           fl.params),
        twin_mom=jax.tree_util.tree_map(upd_mom, fl.twin_mom))
