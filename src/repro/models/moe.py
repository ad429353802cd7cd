"""Mixture-of-Experts layers: top-k routing with two execution modes.

``dense``    — exact weighted einsum over all experts (every expert computes
               every token, combine weights zero out non-selected ones). Exact
               math, no token drops; used by smoke tests and as the oracle.
``capacity`` — production path: scatter/gather token dispatch into per-expert
               capacity buffers (zero matmul FLOPs for dispatch, so compiled
               HLO FLOPs reflect *active* expert compute), expert-parallel
               friendly. Tokens over capacity are dropped (standard Switch/
               Mixtral-style behavior), residual passthrough keeps them sane.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.sharding.act import constrain, ep_enabled, unshard


def moe_init(cfg, key, dtype):
    E = cfg.n_experts
    d = cfg.d_model
    ff = cfg.moe_d_ff or cfg.d_ff
    ks = jax.random.split(key, 5)
    scale = 1.0 / (d ** 0.5)
    p = {
        "router": L.dense_init(ks[0], d, E, dtype=jnp.float32, scale=0.02),
        "wg": (jax.random.normal(ks[1], (E, d, ff)) * scale).astype(dtype),
        "wu": (jax.random.normal(ks[2], (E, d, ff)) * scale).astype(dtype),
        "wd": (jax.random.normal(ks[3], (E, ff, d)) * (1.0 / ff ** 0.5)).astype(dtype),
    }
    if cfg.n_shared_experts:
        p["shared"] = L.mlp_init(ks[4], d, ff * cfg.n_shared_experts, dtype)
    return p


def router_probs(cfg, p, x):
    """x: (T, d) -> (gates (T,k), idx (T,k), aux_loss scalar)."""
    logits = x.astype(jnp.float32) @ unshard(p["router"], None, None)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, cfg.moe_top_k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balance auxiliary loss
    E = cfg.n_experts
    me = jnp.mean(probs, axis=0)  # mean router prob per expert
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(idx, E, dtype=jnp.float32), axis=1), axis=0)
    aux = E * jnp.sum(me * ce)
    return gates, idx, aux


def _experts_apply(p, xe):
    """xe: (E, C, d) -> (E, C, d) through each expert's SwiGLU.

    Two layouts (chosen by mesh divisibility, DESIGN.md §6):
      EP  (E %% fsdp == 0: deepseek 160, jamba 16): expert weights stay
          resident (storage ("data", ., "model")); the capacity buffer is
          expert-sharded, dispatch is an all-to-all, matmuls fully local.
      TPC (mixtral E=8 < 16): capacity dim sharded over data; expert weights
          ZeRO-gathered per layer on d_model (the "model" dim stays sharded —
          ~300 MB/layer/device)."""
    E = xe.shape[0]
    if ep_enabled(E):
        wg = unshard(p["wg"], "data", None, "model")
        wu = unshard(p["wu"], "data", None, "model")
        wd = unshard(p["wd"], "data", "model", None)
        xe = constrain(xe, "data", None, None)
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, wg))
        h = h * jnp.einsum("ecd,edf->ecf", xe, wu)
        h = constrain(h, "data", None, "model")
        return constrain(jnp.einsum("ecf,efd->ecd", h, wd), "data", None, None)
    wg = unshard(p["wg"], None, None, "model")
    wu = unshard(p["wu"], None, None, "model")
    wd = unshard(p["wd"], None, "model", None)
    xe = constrain(xe, None, "data", None)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, wg))
    h = h * jnp.einsum("ecd,edf->ecf", xe, wu)
    h = constrain(h, None, "data", "model")
    return constrain(jnp.einsum("ecf,efd->ecd", h, wd), None, "data", None)


def moe_dense(cfg, p, x):
    """Exact all-experts path. x: (B,S,d).

    Gate-combine is fused into the down-projection einsum (contracting e and
    f together keeps the model-axis partial sums (T, d)-sized). Measured
    variants on mixtral train_4k (EXPERIMENTS.md §Perf C): an unrolled
    per-expert matmul loop was 1.5x WORSE (3.6 TB/dev — per-expert dx
    gathers), the batched einsum with fused combine is the best dense form."""
    B, S, d = x.shape
    T = B * S
    xt = constrain(x.reshape(T, d), "batch", None)
    gates, idx, aux = router_probs(cfg, p, xt)
    E = cfg.n_experts
    comb = jnp.sum(jax.nn.one_hot(idx, E, dtype=jnp.float32)
                   * gates[..., None], axis=1)  # (T, E)
    wg = unshard(p["wg"], None, None, "model")
    wu = unshard(p["wu"], None, None, "model")
    wd = unshard(p["wd"], None, "model", None)
    h = jax.nn.silu(jnp.einsum("td,edf->tef", xt, wg))
    h = h * jnp.einsum("td,edf->tef", xt, wu)
    h = constrain(h, "batch", None, "model")
    out = jnp.einsum("tef,te,efd->td", h, comb.astype(h.dtype), wd)
    out = constrain(out.astype(x.dtype).reshape(B, S, d), "batch", None, None)
    if cfg.n_shared_experts:
        out = out + L.mlp_apply(p["shared"], x)
    return out, aux


def moe_capacity(cfg, p, x):
    """Scatter/gather dispatch with fixed per-expert capacity.

    All data movement is gather/scatter (no dispatch matmuls), so compiled
    FLOPs ~= active-expert FLOPs * capacity_factor. Over-capacity tokens are
    dropped (their expert contribution is zero; the transformer residual
    stream carries them through).
    """
    B, S, d = x.shape
    T = B * S
    k = cfg.moe_top_k
    E = cfg.n_experts
    C = max(8, int(cfg.capacity_factor * T * k / E))
    if T * k >= 1024:
        C = ((C + 127) // 128) * 128  # lane-aligned, shardable capacity
    xt = x.reshape(T, d)
    gates, idx, aux = router_probs(cfg, p, xt)

    flat_e = idx.reshape(T * k)  # expert of each (token, slot)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)  # (T*k, E)
    pos_in_e = jnp.cumsum(onehot, axis=0) - onehot  # rank within its expert
    pos = jnp.sum(pos_in_e * onehot, axis=-1)  # (T*k,)
    keep = pos < C
    pos_c = jnp.minimum(pos, C - 1)

    tok = jnp.repeat(jnp.arange(T), k)
    # dropped tokens scatter-ADD zeros into the clamped slot (never corrupt a
    # resident token) and read back gated-to-zero below.
    vals = xt[tok] * keep[:, None].astype(xt.dtype)
    buf = jnp.zeros((E, C, d), xt.dtype).at[flat_e, pos_c].add(vals)
    buf = (constrain(buf, "data", None, None) if ep_enabled(E)
           else constrain(buf, None, "data", None))
    ye = _experts_apply(p, buf)  # (E, C, d)
    y_tok = ye[flat_e, pos_c].reshape(T, k, d)  # gather back
    g_eff = gates * keep.reshape(T, k).astype(gates.dtype)
    out = jnp.sum(y_tok.astype(jnp.float32) * g_eff[..., None], axis=1)
    out = constrain(out.astype(x.dtype).reshape(B, S, d), "batch", None, None)
    if cfg.n_shared_experts:
        out = out + L.mlp_apply(p["shared"], x)
    return out, aux


def moe_capacity_ep_a2a(cfg, p, x):
    """Expert-parallel capacity dispatch via shard_map + all_to_all.

    GSPMD cannot partition the global scatter/gather dispatch (it replicates
    the capacity buffer and all-reduces it — 10.8 TB/device/step on deepseek
    train_4k). This is the GShard/Switch formulation instead: the fsdp axes
    are MANUAL (each shard routes its own tokens, local cumsum positions,
    local scatter into an (E, C_local, d) buffer), experts are exchanged
    with one tiled all_to_all each way (payload = dispatched token
    embeddings only), and expert matmuls are fully local — expert weights
    live on their owner shard (storage ("data", ., "model")) with the
    "model" axis left to GSPMD (auto) inside the manual region.

    Capacity is per (source shard, expert) — drop behavior differs from
    global capacity only under shard-imbalanced routing; exactness vs dense
    at high capacity_factor is covered by tests.
    """
    from repro.sharding.act import _current, batch_axes, fsdp_size, manual_axes

    mesh = _current()
    man_axes = batch_axes(mesh, layout="2d")
    man = (man_axes,) if isinstance(man_axes, str) else tuple(man_axes)
    n_sh = fsdp_size()
    E = cfg.n_experts
    E_loc = E // n_sh
    B, S, d = x.shape
    k = cfg.moe_top_k

    def local_fn(xb, router, wg, wu, wd):
        with manual_axes(man):
            return _local_body(xb, router, wg, wu, wd)

    def _local_body(xb, router, wg, wu, wd):
        B_loc = xb.shape[0]
        T_loc = B_loc * S
        xt = xb.reshape(T_loc, d)
        logits = xt.astype(jnp.float32) @ router
        probs = jax.nn.softmax(logits, axis=-1)
        gates, idx = jax.lax.top_k(probs, k)
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
        me = jnp.mean(probs, axis=0)
        ce = jnp.mean(jnp.sum(jax.nn.one_hot(idx, E, dtype=jnp.float32),
                              axis=1), axis=0)
        aux = E * jnp.sum(me * ce)
        aux = jax.lax.pmean(aux, man)

        C_loc = max(8, int(cfg.capacity_factor * T_loc * k / E))
        C_loc = ((C_loc + 7) // 8) * 8
        flat_e = idx.reshape(T_loc * k)
        onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)
        pos = jnp.sum((jnp.cumsum(onehot, axis=0) - onehot) * onehot, axis=-1)
        keep = pos < C_loc
        pos_c = jnp.minimum(pos, C_loc - 1)
        tok = jnp.repeat(jnp.arange(T_loc), k)
        vals = xt[tok] * keep[:, None].astype(xt.dtype)
        buf = jnp.zeros((E, C_loc, d), xt.dtype).at[flat_e, pos_c].add(vals)

        # ---- dispatch: one tiled all_to_all (involution) ----
        buf4 = buf.reshape(n_sh, E_loc, C_loc, d)
        recv = jax.lax.all_to_all(buf4, man, split_axis=0, concat_axis=0,
                                  tiled=True)  # (n_src, E_loc, C_loc, d)
        xe = jnp.transpose(recv, (1, 0, 2, 3)).reshape(E_loc, n_sh * C_loc, d)

        # ---- local expert compute (model axis auto-sharded on ff) ----
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, wg))
        h = h * jnp.einsum("ecd,edf->ecf", xe, wu)
        h = constrain(h, None, None, "model")
        ye = jnp.einsum("ecf,efd->ecd", h, wd)  # (E_loc, n_sh*C_loc, d)

        # ---- return path: inverse all_to_all ----
        y4 = jnp.transpose(ye.reshape(E_loc, n_sh, C_loc, d), (1, 0, 2, 3))
        back = jax.lax.all_to_all(y4, man, split_axis=0, concat_axis=0,
                                  tiled=True).reshape(E, C_loc, d)
        y_tok = back[flat_e, pos_c].reshape(T_loc, k, d)
        g_eff = gates * keep.reshape(T_loc, k).astype(gates.dtype)
        out = jnp.sum(y_tok.astype(jnp.float32) * g_eff[..., None], axis=1)
        return out.astype(xb.dtype).reshape(B_loc, S, d), aux

    P = jax.sharding.PartitionSpec
    in_specs = (P(man_axes, None, None), P(None, None),
                P(man_axes, None, None), P(man_axes, None, None),
                P(man_axes, None, None))
    out_specs = (P(man_axes, None, None), P())
    fn = jax.shard_map(local_fn, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False,
                       axis_names=set(man))
    out, aux = fn(x, p["router"], p["wg"], p["wu"], p["wd"])
    if cfg.n_shared_experts:
        # shared experts run OUTSIDE the manual region: their weights are
        # replicated, and the bf16 gradient psum the shard_map transpose
        # would insert trips an XLA-CPU AllReducePromotion crash (the GSPMD
        # path handles the same reduction fine).
        out = out + L.mlp_apply(p["shared"], x)
    return out, aux


def _use_ep_a2a(cfg) -> bool:
    from repro.sharding.act import _current, current_layout, ep_enabled

    return (_current() is not None and current_layout() == "2d"
            and ep_enabled(cfg.n_experts))


def moe_apply(cfg, p, x):
    if cfg.router_mode == "capacity":
        if _use_ep_a2a(cfg):
            return moe_capacity_ep_a2a(cfg, p, x)
        return moe_capacity(cfg, p, x)
    return moe_dense(cfg, p, x)
