"""Activation sharding constraints.

GSPMD propagation through the double-scan attention and the layer scan can
drop the batch sharding (observed: full global batch replicated per device
inside the attention while-loops, with the model axis landing on head_dim).
``constrain`` pins activations at layer boundaries, guarded by divisibility,
and is a no-op outside an ``activation_mesh`` context so smoke tests and
single-device runs are unaffected.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_STATE = threading.local()


def _current() -> Optional[Mesh]:
    return getattr(_STATE, "mesh", None)


@contextlib.contextmanager
def activation_mesh(mesh: Mesh, layout: str = "2d"):
    """layout: "2d" (FSDP x TP) or "dp" (pure data parallel: batch sharded
    over every mesh axis, no tensor parallelism — right for small models
    where TP activation all-reduces dominate the roofline)."""
    prev = (_current(), getattr(_STATE, "layout", "2d"))
    _STATE.mesh = mesh
    _STATE.layout = layout
    try:
        yield
    finally:
        _STATE.mesh, _STATE.layout = prev


def current_layout() -> str:
    return getattr(_STATE, "layout", "2d")


@contextlib.contextmanager
def manual_axes(axes):
    """Mark axes as shard_map-manual during tracing: ``constrain``/``unshard``
    drop any PartitionSpec part referring to them (with_sharding_constraint
    may only mention auto axes inside a manual region)."""
    prev = getattr(_STATE, "manual", frozenset())
    _STATE.manual = frozenset(axes)
    try:
        yield
    finally:
        _STATE.manual = prev


def _manual() -> frozenset:
    return getattr(_STATE, "manual", frozenset())


def _axis_size(mesh: Mesh, name) -> int:
    if isinstance(name, tuple):
        return int(np.prod([mesh.shape[n] for n in name]))
    return mesh.shape[name]


def batch_axes(mesh: Optional[Mesh] = None, layout: Optional[str] = None):
    mesh = mesh or _current()
    layout = layout or current_layout()
    if layout == "dp":
        return tuple(mesh.axis_names) if mesh is not None else "data"
    if mesh is not None and "pod" in mesh.axis_names:
        return ("pod", "data")
    return "data"


def constrain(x, *parts):
    """with_sharding_constraint(x, P(*parts)) with divisibility guards.

    Use the string "batch" for the (pod?, data) composite axis. Axes that do
    not divide their dim are dropped (replicated) rather than erroring."""
    mesh = _current()
    if mesh is None or x is None:
        return x
    layout = current_layout()
    resolved = []
    for dim, part in zip(x.shape, parts):
        if part is None:
            resolved.append(None)
            continue
        if part == "model" and layout == "dp":
            resolved.append(None)  # pure-DP: no tensor parallelism
            continue
        if part == "data" and layout == "dp":
            part = batch_axes(mesh)  # EP axis widens to all-data in pure DP
        ax = batch_axes(mesh) if part == "batch" else part
        if ax == "pod" and "pod" not in mesh.axis_names:
            resolved.append(None)
            continue
        manual = _manual()
        if manual:
            ax_t = (ax,) if isinstance(ax, str) else tuple(ax)
            ax_t = tuple(a for a in ax_t if a not in manual)
            if not ax_t:
                resolved.append(None)
                continue
            ax = ax_t[0] if len(ax_t) == 1 else ax_t
        resolved.append(ax if dim % _axis_size(mesh, ax) == 0 else None)
    resolved += [None] * (x.ndim - len(resolved))
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*resolved)))


def fsdp_size() -> int:
    """Size of the fsdp (data [x pod]) axis group, or 0 if no mesh context."""
    mesh = _current()
    if mesh is None:
        return 0
    return _axis_size(mesh, batch_axes(mesh, layout="2d"))


def ep_enabled(n_experts: int) -> bool:
    """Expert parallelism applies when the expert count divides the fsdp
    axis (deepseek 160, jamba 16 — not mixtral 8 on a 16-wide axis)."""
    n = fsdp_size()
    return n > 0 and n_experts % n == 0


def unshard(w, *parts):
    """FSDP weight-gather at point of use (ZeRO-3 semantics).

    Weights are STORED fully sharded (fsdp x model, sharding/specs.py); inside
    a layer the FSDP axes are gathered so matmul contractions never run over
    an fsdp-sharded dim (which XLA otherwise resolves with activation-sized
    partial-sum all-reduces — observed 138 GB/device/step vs the ~11 GB of
    weight gathers). ``parts`` give the retained (TP) sharding, e.g.
    (None, "model") for an in-projection.

    In the "decode" layout this is a NO-OP: one-token steps touch tiny
    activations, so re-gathering weights every token (observed 131 GB/device
    on deepseek-v2 decode) is catastrophic — weights stay resident in their
    storage sharding and the per-matmul partial-sum reductions are
    activation-sized (cheap at batch x 1 tokens).

    No-op outside activation_mesh."""
    if current_layout() == "decode":
        return w
    return constrain(w, *parts)
