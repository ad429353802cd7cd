"""Plain reference of one served DTWN round, written from the round's laws.

It imports nothing of the program. It starts from ``world.py``'s seed-made
data and ``traffic.py``'s keys and plans, and follows the round as the
paper and the service's documentation state it:

1. association: either the round-robin association of the previous round,
   or the policy's: every BS's actor scores every twin from the shared
   observation (per-BS load, channel and chain columns; per-twin data
   shares) and a twin goes to the BS that scores it highest; the actors
   also set the batch fractions and the sub-channel time shares. Then,
   where configured, the between-round migration (a Bernoulli move mask and
   a Gumbel-argmax destination over ring-distance and load logits);
2. faults, where configured: straggler work multipliers and one
   Gilbert-Elliott outage step gating the Eq. 7 uplink;
3. Eq. 17: max over BSs of the Eq. 12 compute time plus max over BSs of
   the Eq. 15 broadcast time plus the block term (PBFT with a chain, the
   fixed Eq. 16 term without);
4. the chain round, where configured: per-BS submission losses, the
   median-plus-tolerance verdict, stakes and verdict history;
5. the FL round, where the configuration trains a model: every participant
   runs ``local_iters`` steps of SGD (with the configured momentum) from the
   global model, Eq. 4 takes each BS's data-weighted mean of its
   participants, the verify gate (where configured) accepts BSs whose
   holdout loss is at most the median plus the tolerance, and Eq. 5 takes
   the plain mean of the accepted BSs (the old model stays when none is
   accepted). A model's frozen tree (``init_frozen``) is a constant of the
   loss: it is neither trained nor aggregated;
6. churn: Bernoulli departures and admissions, admitted twins drawing a
   population size and a uniform association.

Three decisions compare a float with a line: the association's argmax, the
verify gate and a moving twin's destination. Where the reference's own
margin to the line is under the configured margin (``margins``), lower
precision may decide either way; there the reference follows the decision of
what it is compared with (``follow``) and the rounds go on from the same
decisions. Where the margin is wider, it decides alone, and a different
decision of the other side is counted. A destination is followed by
``follow["mig_alt"]``, which sends the twin to its runner-up BS; the round
reports each mover's margin (``mig_gap``) for the caller to choose it.

Per-BS sums are plain one-hot contractions, exact in float32 (``HIGHEST``).
The models' and the actors' matmuls run at the precision the configuration
states (``matmul_precision``: the program's float32 at default precision,
which the TPU executes as bfloat16 passes with float32 sums). ``control``
makes the same code the bfloat16 control: bfloat16 values and default
precision everywhere. Random draws are float32 in both, as they are drawn
from the same keys.
"""
import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from world import actor_dims, freqs_hz, model_module

HIGHEST = jax.lax.Precision.HIGHEST
DEFAULT = jax.lax.Precision.DEFAULT
_PRECISION = {"default": DEFAULT, "high": jax.lax.Precision.HIGH, "highest": HIGHEST}


def _dbm_to_watt(dbm):
    return 10.0 ** (dbm / 10.0) * 1e-3


def _per_bs(values, assoc, m, precision):
    """(M, ...) sums of ``values`` (N, ...) by BS id; ids outside [0, M)
    drop."""
    onehot = (assoc[None, :] == jnp.arange(m)[:, None]).astype(values.dtype)
    return jnp.tensordot(onehot, values, axes=[[1], [0]], precision=precision)


def _median(losses, submitted):
    """numpy median (middle-two average) of the submitted losses; 0 when
    none is submitted."""
    k = jnp.sum(submitted.astype(jnp.int32))
    s = jnp.sort(jnp.where(submitted, losses, jnp.inf))
    c = jnp.maximum(k, 1)
    lo = s[jnp.clip((c - 1) // 2, 0, losses.shape[0] - 1)]
    hi = s[jnp.clip(c // 2, 0, losses.shape[0] - 1)]
    return jnp.where(k > 0, 0.5 * (lo + hi), 0.0)


def _rates(cfg, tau, h_up, h_down, dist, dt):
    """Eq. 7 uplink with the (M, C) sub-channel time shares ``tau``, and the
    Eq. 8 downlink."""
    w = cfg["wireless"]
    noise = _dbm_to_watt(w["noise_dbm_per_hz"]) * w["subchannel_bw_hz"]
    pl = dist.astype(dt)[:, None] ** (-w["path_loss_exp"])
    tau = tau.astype(dt)
    sig = _dbm_to_watt(w["p_uplink_dbm"]) * h_up.astype(dt) * pl
    tot = jnp.sum(tau * sig, axis=0, keepdims=True)
    sinr = sig / (tot - tau * sig + noise)
    up = jnp.sum(tau * w["subchannel_bw_hz"] * jnp.log2(1.0 + sinr), axis=1)
    sig = _dbm_to_watt(w["p_downlink_dbm"]) * h_down.astype(dt) * pl
    sinr = sig / (jnp.sum(sig, axis=0, keepdims=True) - sig + noise)
    down = jnp.sum(w["subchannel_bw_hz"] * jnp.log2(1.0 + sinr), axis=1)
    return up, down


def _pbft(cfg, down, freqs, knobs, dt):
    """PBFT block term: pre-prepare + validation + two quorum waits, times
    the expected view changes."""
    lat, cc = cfg["latency"], cfg["consensus"]
    m = cfg["n_bs"]
    sb = knobs["block_size"].astype(dt)
    safe = jnp.maximum(down, 1.0)
    pre = jnp.max(lat["xi"] * np.log2(max(lat["n_producers"], 2)) * sb / safe)
    val = jnp.max(sb / 8.0 * lat["cycles_per_val_byte"] / freqs)
    msg = jnp.sort(lat["xi"] * np.log2(max(m, 2)) * cc["header_bits"] / safe)
    need = jnp.clip(2 * jnp.round(knobs["quorum"]).astype(jnp.int32), 0, m)
    tq = jnp.where(need > 0, msg[jnp.clip(need - 1, 0, m - 1)], 0.0)
    p = jnp.clip(knobs["byzantine"], 0.0, 0.95).astype(dt)
    return (pre + val + 2.0 * tq) * (1.0 + cc["view_timeout"] * p / (1.0 - p))


def _block_fixed(cfg, down, freqs, dt):
    """Eq. 16: block propagation among the producers plus the slowest
    validation (the block term without a chain)."""
    lat = cfg["latency"]
    prop = (lat["xi"] * np.log2(max(lat["n_producers"], 2)) * lat["block_size_bits"]
            / jnp.maximum(down, 1.0))
    val = jnp.max(lat["block_size_bits"] / 8.0 * lat["cycles_per_val_byte"] / freqs)
    return jnp.max(prop) + val


def _mlp(layers, x, precision):
    for i, layer in enumerate(layers):
        x = jnp.dot(x, layer["w"], precision=precision) + layer["b"]
        if i < len(layers) - 1:
            x = jax.nn.relu(x)
    return x


def _policy(cfg, actor, st, consts, dt, precision, mp):
    """Every BS's actor on the shared observation: (M, N) scores, (M,) batch
    controls and (M, C) bandwidth bids, each in [-1, 1]."""
    m, n = cfg["n_bs"], cfg["capacity"]
    w = cfg["wireless"]
    d = st["data"].astype(dt) / cfg["policy"]["obs_data_max"]
    assoc = st["assoc"]
    k = _per_bs(jnp.ones((n,), dt), assoc, m, precision)
    load = _per_bs(d, assoc, m, precision) / jnp.maximum(jnp.sum(d), 1e-9)
    cols = [consts["freqs"].astype(dt)[:, None] / 3.6e9, (k / n)[:, None], load[:, None],
            consts["h_up"].astype(dt) / 2.0,
            (consts["dist"].astype(dt) / w["max_dist_m"])[:, None]]
    if cfg["consensus"]:
        ch = st["chain"]
        cols.append(jnp.mean(ch["verdicts"], axis=0).astype(dt)[:, None])
        share = ch["stakes"] / jnp.maximum(jnp.sum(ch["stakes"]), 1e-9)
        cols.append((share * m).astype(dt)[:, None])
    bs_feats = jnp.concatenate(cols, axis=1)
    tf = jnp.stack([d, d * n / jnp.maximum(jnp.sum(d), 1e-9)], axis=1)
    pooled = jnp.concatenate([jnp.mean(tf, 0), jnp.max(tf, 0), jnp.min(tf, 0),
                              jnp.std(tf, 0)])
    compact = jnp.concatenate([bs_feats.reshape(-1), pooled])

    def one(p):
        p = jax.tree_util.tree_map(lambda v: v.astype(dt), p)
        att = jax.nn.softmax(jnp.dot(tf, p["attn_q"], precision=mp))
        summary = jnp.dot(att, tf, precision=mp)
        g = jax.nn.relu(_mlp(p["trunk"], jnp.concatenate([compact, summary]), mp))
        h = jax.nn.relu(jnp.dot(tf, p["wt"], precision=mp)
                        + jnp.dot(g, p["wg"], precision=mp) + p["bh"])
        scores = jnp.tanh(jnp.dot(h, p["wo"], precision=mp) + p["bo"])[:, 0]
        b = jnp.tanh(jnp.dot(g, p["wb"], precision=mp) + p["bb"])[0]
        tau = jnp.tanh(jnp.dot(g, p["wtau"], precision=mp) + p["btau"])
        return scores, b, tau

    return jax.vmap(one)(actor)


def _top_two_gap(scores):
    """Per twin, the highest score over BSs less the second highest."""
    s = jnp.sort(scores, axis=0)
    return s[-1] - s[-2]


def model_fns(model, frozen, dt, mp):
    """``(loss, accuracy)`` of ``model``, each ``f(params, xs, ys)``, in
    ``dt`` at matmul precision ``mp``: the module's own, given ``frozen``,
    where it has them; else softmax cross-entropy and argmax accuracy over
    its ``forward``'s logits, one label per example."""
    if hasattr(model, "loss"):
        kw = {"precision": mp, "dtype": dt}
        return (lambda p, xs, ys: model.loss(p, frozen, xs, ys, **kw),
                lambda p, xs, ys: model.accuracy(p, frozen, xs, ys, **kw))
    fwd = functools.partial(model.forward, precision=mp, dtype=dt)

    def loss(p, xs, ys):
        logp = jax.nn.log_softmax(fwd(p, xs).astype(jnp.float32), axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, ys[:, None], axis=-1))

    def accuracy(p, xs, ys):
        return jnp.mean(jnp.argmax(fwd(p, xs).astype(jnp.float32), -1) == ys)

    return loss, accuracy


def _fl(cfg, model, params, frozen, plan, active, data, assoc, x, y, x_eval, y_eval,
        follow_accepts, margin, dt, precision, mp):
    """One FL round over the plan's participants. Returns the new global
    model and the round's FL answers. ``frozen`` (or None) is the model's
    frozen tree, in ``dt``; ``follow_accepts`` (or None) the other side's
    number of accepted BSs; ``margin`` the verify gate's."""
    m = cfg["n_bs"]
    loss_fn, accuracy_fn = model_fns(model, frozen, dt, mp)

    users = plan["users"]
    part = plan["valid"] & active[users]
    w_u = jnp.where(part, data[users], 0.0).astype(dt)
    a_u = jnp.where(part, assoc[users], m)
    p0 = jax.tree_util.tree_map(lambda v: v.astype(dt), params)

    def train(xs, ys):
        def step(carry, batch):
            p, mom = carry
            g = jax.grad(loss_fn)(p, *batch)
            mom = jax.tree_util.tree_map(
                lambda mo, gi: (cfg["momentum"] * mo + gi).astype(dt), mom, g)
            p = jax.tree_util.tree_map(lambda pi, mo: (pi - cfg["lr"] * mo).astype(dt),
                                       p, mom)
            return (p, mom), None

        mom0 = jax.tree_util.tree_map(jnp.zeros_like, p0)
        (p, _), _ = jax.lax.scan(step, (p0, mom0), (xs, ys))
        return p

    trained = jax.vmap(train)(x[plan["batch"]], y[plan["batch"]])
    bs_w = _per_bs(w_u, a_u, m, precision)
    safe_w = jnp.where(bs_w > 0.0, bs_w, 1.0)

    def eq4(leaf):
        flat = leaf.reshape(leaf.shape[0], -1) * w_u[:, None]
        agg = _per_bs(flat, a_u, m, precision) / safe_w[:, None]
        return agg.reshape((m,) + leaf.shape[1:])

    per_bs = jax.tree_util.tree_map(eq4, trained)
    submitted = bs_w > 0.0
    n_sub = jnp.sum(submitted.astype(jnp.int32))
    outside = verify_near = jnp.int32(0)
    bs_loss = jnp.zeros((m,), jnp.float32)
    if cfg["verify"]:
        bs_loss = jax.vmap(lambda p: loss_fn(p, x_eval, y_eval))(per_bs)
        line = _median(bs_loss, submitted) + cfg["verify_tolerance"]
        accept = submitted & (bs_loss <= line)
        if follow_accepts is not None:
            # BSs within the margin of the line take the other side's
            # decision: it accepted ``follow_accepts`` in all, and the
            # gate accepts by loss, so it accepted the lowest of them
            clear = submitted & (bs_loss < line - margin)
            near = submitted & (jnp.abs(bs_loss - line) <= margin)
            n_near = follow_accepts - jnp.sum(clear.astype(jnp.int32))
            rank = jnp.argsort(jnp.argsort(jnp.where(near, bs_loss, jnp.inf)))
            accept = clear | (near & (rank < n_near))
            outside = ((n_near < 0) | (n_near > jnp.sum(near.astype(jnp.int32)))
                       ).astype(jnp.int32)
            verify_near = jnp.sum(near.astype(jnp.int32))
    else:
        accept = submitted
    wg = jnp.where(accept, 1.0, 0.0).astype(dt)
    tot = jnp.maximum(jnp.sum(wg), 1e-12)
    agg = jax.tree_util.tree_map(
        lambda leaf: jnp.tensordot(wg, leaf, axes=[[0], [0]], precision=precision) / tot,
        per_bs)
    new = jax.tree_util.tree_map(lambda old, a: jnp.where(jnp.any(accept), a, old),
                                 p0, agg)
    answers = {
        "fl_loss": loss_fn(new, x_eval, y_eval),
        "fl_accuracy": accuracy_fn(new, x_eval, y_eval),
        "fl_bs_weight": bs_w.astype(jnp.float32),
        "fl_n_participants": jnp.sum(part.astype(jnp.int32)),
        "fl_accept_frac": (jnp.sum(accept.astype(jnp.float32))
                           / jnp.maximum(n_sub.astype(jnp.float32), 1.0)),
        "fl_bs_loss": bs_loss.astype(jnp.float32),
        "verify_outside": outside,
        "verify_near": verify_near,
    }
    return new, answers


def _round(cfg, traffic, model, consts, st, keys, plan, follow, margins, dt, precision, mp):
    m = cfg["n_bs"]
    knobs = consts["knobs"]
    lat, mig, flt, cc = cfg["latency"], cfg["migration"], cfg["faults"], cfg["consensus"]
    freqs = consts["freqs"].astype(dt)
    active, data, assoc_prev = st["active"], st["data"], st["assoc"]
    n = data.shape[0]
    disagree = n_near = jnp.int32(0)
    policy_answers = {}

    # 1. association and controls, then migration
    if cfg["association"] == "factorized":
        scores, b_ctl, tau = _policy(cfg, consts["actor"], st, consts, dt, precision, mp)
        own = jnp.argmax(scores, axis=0).astype(jnp.int32)
        assoc_cmd = own
        if follow is not None:
            near = _top_two_gap(scores) <= margins["assoc"]
            assoc_cmd = jnp.where(near, follow["assoc"], own)
            disagree = jnp.sum((active & ~near & (follow["assoc"] != own)).astype(jnp.int32))
            n_near = jnp.sum((active & near).astype(jnp.int32))
        policy_answers["scores"] = scores.astype(jnp.float32)
        assoc_cmd = jnp.where(active, assoc_cmd, m)
        frac = (jnp.clip(b_ctl, -1.0, 1.0) + 1.0) / 2.0
        b_bs = lat["b_min"] + frac * (lat["b_max"] - lat["b_min"])
        b = jnp.where(active, b_bs[jnp.clip(assoc_cmd, 0, m - 1)], 0.0).astype(dt)
        tau = jax.nn.softmax(tau * 4.0, axis=0)
    else:
        assoc_cmd = assoc_prev
        b = jnp.where(active, 0.5, 0.0).astype(dt)
        tau = jnp.full(consts["h_up"].shape, 1.0 / m, dt)
    up, down = _rates(cfg, tau, consts["h_up"], consts["h_down"], consts["dist"], dt)
    assoc = assoc_cmd
    step_answers = {}
    if mig:
        loads = _per_bs(data.astype(dt), assoc_cmd, m, precision)
        pen = loads / jnp.maximum(jnp.mean(loads), 1e-12)
        i = jnp.arange(m)
        ring = jnp.abs(i[:, None] - i[None, :])
        ring = (jnp.minimum(ring, m - ring) / max(m // 2, 1)).astype(dt)
        logits = (-mig["locality"] * ring[jnp.clip(assoc_cmd, 0, m - 1)]
                  - mig["load_weight"] * pen[None, :])
        k_move, k_dst = jax.random.split(keys["mig"])
        move = jax.random.uniform(k_move, (n,)) < mig["p_move"]
        z = logits + jax.random.gumbel(k_dst, (n, m)).astype(dt)
        choice = jnp.argmax(z, axis=1)
        top, top_bs = jax.lax.top_k(z, 2)
        if follow is not None and "mig_alt" in follow:
            choice = jnp.where(follow["mig_alt"], top_bs[:, 1], choice)
        assoc = jnp.where(active, jnp.where(move, choice, assoc_cmd), m).astype(jnp.int32)
        step_answers["migration_rate"] = (jnp.sum((assoc != assoc_cmd).astype(jnp.float32))
                                          / n)
        step_answers["mig_gap"] = jnp.where(active & move, top[:, 0] - top[:, 1],
                                            jnp.inf).astype(jnp.float32)

    # 2. faults
    bad = st["bad"]
    if flt:
        k_slow, k_out = jax.random.split(keys["fault"])
        k_mask, k_mag = jax.random.split(k_slow)
        is_slow = jax.random.uniform(k_mask, (n,)) < knobs["straggler"]
        extra = jax.random.exponential(k_mag, (n,)) * flt["straggler_slowdown"]
        slow = 1.0 + jnp.where(is_slow, extra, 0.0)
        b = b * slow.astype(dt)
        pi_b = jnp.clip(knobs["outage"], 0.0, 0.95)
        p_bg = 1.0 / max(flt["burst_len"], 1.0)
        p_gb = jnp.clip(pi_b * p_bg / (1.0 - pi_b), 0.0, 1.0)
        u = jax.random.uniform(k_out, (m,))
        bad = jnp.where(st["bad"], u >= p_bg, u < p_gb)
        up = jnp.where(bad, up * flt["outage_floor"], up)
        step_answers["straggler_frac"] = jnp.mean((slow > 1.0).astype(jnp.float32))
        step_answers["outage_frac"] = jnp.mean(bad.astype(jnp.float32))

    # 3. Eq. 17
    t_cmp = _per_bs(b * data.astype(dt), assoc, m, precision) * lat["cycles_per_sample"] / freqs
    k_i = _per_bs(jnp.ones((n,), dt), assoc, m, precision)
    t_bc = (lat["xi"] * np.log2(max(m, 2)) * k_i * lat["model_size_bits"]
            / jnp.maximum(up, 1.0))
    t_block = _pbft(cfg, down, freqs, knobs, dt) if cc else _block_fixed(cfg, down, freqs, dt)
    round_time = jnp.max(t_cmp) + jnp.max(t_bc) + t_block

    # 4. chain round
    chain, chain_answers = st["chain"], {}
    if cc:
        ch = st["chain"]
        sub_loss = (0.5 + 0.1 * jax.random.normal(keys["chain"], (m,))
                    + jnp.where(consts["byz"], 2.0, 0.0))
        submitted = k_i > 0
        verdict = submitted & (sub_loss <= _median(sub_loss, submitted) + cc["tolerance"])
        rew = jnp.where(verdict, cc["reward"], 0.0)
        row = (jnp.arange(cc["history"]) == ch["round"] % cc["history"])[:, None]
        chain = {"stakes": ch["stakes"] + rew,
                 "verdicts": jnp.where(row, jnp.where(submitted, verdict, True)[None, :]
                                       .astype(jnp.float32), ch["verdicts"]),
                 "rewards": jnp.where(row, rew[None, :], ch["rewards"]),
                 "round": ch["round"] + 1}
        chain_answers["accept_frac"] = (
            jnp.sum(verdict.astype(jnp.float32))
            / jnp.maximum(jnp.sum(submitted.astype(jnp.float32)), 1.0))

    # 5. FL round on the pre-churn population and the migrated association
    params, fl = st["params"], {}
    if model is not None:
        frozen = (jax.tree_util.tree_map(lambda v: v.astype(dt), consts["frozen"])
                  if "frozen" in consts else None)
        params, fl = _fl(cfg, model, params, frozen, plan, active, data, assoc,
                         consts["x"], consts["y"], consts["x_eval"], consts["y_eval"],
                         None if follow is None else follow["accepts"],
                         margins["verify"], dt, precision, mp)

    # 6. churn
    n_joined = n_left = jnp.int32(0)
    assoc_next = assoc
    if traffic["join_rate"] > 0.0 or traffic["leave_rate"] > 0.0:
        k_leave, k_join, k_data, k_assoc = jax.random.split(keys["churn"], 4)
        leave = active & (jax.random.uniform(k_leave, (n,)) < traffic["leave_rate"])
        join = ~active & (jax.random.uniform(k_join, (n,)) < traffic["join_rate"])
        u_d = jax.random.uniform(k_data, (n,))
        new_data = knobs["data_min"] + (knobs["data_max"] - knobs["data_min"]) * u_d ** knobs["skew"]
        new_assoc = jax.random.randint(k_assoc, (n,), 0, m)
        active = (active & ~leave) | join
        data = jnp.where(join, new_data, jnp.where(leave, 0.0, data))
        assoc_next = jnp.where(join, new_assoc, jnp.where(leave, m, assoc)).astype(jnp.int32)
        n_joined = jnp.sum(join.astype(jnp.int32))
        n_left = jnp.sum(leave.astype(jnp.int32))

    answers = dict(fl, **chain_answers, **step_answers)
    answers.update({"round_time": round_time.astype(jnp.float32),
                    "n_active": jnp.sum(active.astype(jnp.int32)),
                    "n_joined": n_joined, "n_left": n_left,
                    "assoc": assoc, "assoc_disagree": disagree,
                    "assoc_near": n_near}, **policy_answers)
    st2 = {"active": active, "data": data, "assoc": assoc_next, "bad": bad,
           "chain": chain, "params": params}
    return st2, answers


def initial(cfg, world):
    """The reference's round-0 state and constants from the seed-made
    world (the same data the program's initial state holds)."""
    n, m = cfg["capacity"], cfg["n_bs"]
    cc = cfg["consensus"]
    assoc = jnp.arange(n, dtype=jnp.int32) % m
    data = world["data_sizes"]
    chain = None
    if cc:
        per_bs = jnp.zeros((m,), jnp.float32).at[assoc].add(data)
        chain = {"stakes": cc["s_ini"] * per_bs / jnp.maximum(jnp.sum(per_bs), 1e-9),
                 "verdicts": jnp.ones((cc["history"], m), jnp.float32),
                 "rewards": jnp.zeros((cc["history"], m), jnp.float32),
                 "round": jnp.zeros((), jnp.int32)}
    st = {"active": jnp.ones((n,), bool), "data": data, "assoc": assoc,
          "bad": world["bad"], "chain": chain, "params": world.get("params")}
    consts = {"knobs": world["knobs"], "h_up": world["h_up"], "h_down": world["h_down"],
              "dist": world["dist"], "byz": world["byz"], "freqs": freqs_hz(cfg)}
    if "params" in world:
        consts.update(x=world["x"], y=world["y"], x_eval=world["x_test"][:cfg["n_eval"]],
                      y_eval=world["y_test"][:cfg["n_eval"]])
    for k in ("actor", "frozen"):
        if k in world:
            consts[k] = world[k]
    return st, consts


def run(cfg, traffic, world, rounds, n_rounds, margins, *, follow=None, control=False):
    """Follow the first ``n_rounds`` rounds. ``rounds`` is
    ``traffic.rounds(...)`` over them; ``follow`` (or None) the other side's
    decisions, {"assoc": (rounds, N), "accepts": (rounds,)}, taken where the
    reference's own margin is under ``margins``, and {"mig_alt": (rounds, N)}
    the destinations it takes the runner-up for. Returns the per-round
    answers (host arrays on a leading round axis) and the global model after
    each round (None without FL). ``control`` computes in bfloat16 at
    default matmul precision; otherwise the models run at the configured
    precision and the sums at ``HIGHEST``."""
    fn = _runner(json.dumps(cfg, sort_keys=True), json.dumps(traffic, sort_keys=True),
                 n_rounds, json.dumps(margins, sort_keys=True), control)
    with jax.default_matmul_precision("default" if control else cfg["matmul_precision"]):
        answers, globals_ = fn(world, rounds, follow)
    return (jax.tree_util.tree_map(np.asarray, answers),
            [jax.tree_util.tree_map(np.asarray, g) for g in globals_])


@functools.lru_cache(maxsize=None)
def _runner(cfg_json, traffic_json, n_rounds, margins_json, control):
    """``run``'s jitted rounds, one per configuration, mix, length and
    precision, so that following other decisions traces nothing anew."""
    cfg, traffic, margins = (json.loads(cfg_json), json.loads(traffic_json),
                             json.loads(margins_json))
    dt = jnp.bfloat16 if control else jnp.float32
    precision = DEFAULT if control else HIGHEST
    mp = DEFAULT if control else _PRECISION[cfg["matmul_precision"]]
    model = model_module(cfg)

    @jax.jit
    def go(world, rounds, follow):
        st, consts = initial(cfg, world)
        keys, plans = rounds
        out, globals_ = [], []
        for r in range(n_rounds):
            k = {s: v[r] for s, v in keys.items()}
            p = None if plans is None else {s: v[r] for s, v in plans.items()}
            f = None if follow is None else {s: v[r] for s, v in follow.items()}
            st, ans = _round(cfg, traffic, model, consts, st, k, p, f, margins, dt,
                             precision, mp)
            out.append(ans)
            globals_.append(jax.tree_util.tree_map(lambda v: v.astype(jnp.float32),
                                                   st["params"]))
        return {k: jnp.stack([a[k] for a in out]) for k in out[0]}, globals_

    return go
