"""Twin-axis mesh sharding of the DTWN simulation core.

PR 1-3 removed the O(N*M) memory and O(N) replay/params bottlenecks, but the
simulation step itself (latency Eqs. 12-17, env observe/step, the scan
trainer) remained single-device O(N). This module distributes the *twin
population* — the only large axis in the system — over a 1-D device mesh
(``repro.launch.mesh.make_twin_mesh``, axis name ``"twin"``), pushing the
step cost to O(N / n_shards) per device plus M-sized collectives:

* every per-BS quantity is a segment reduction over twins, so the sharded
  form is "local segment_reduce per shard + one (M, K) ``psum``" — wired as
  ``backend="sharded"`` in ``repro.kernels.segment_reduce`` and selected
  *automatically* by ``backend="auto"`` inside a :func:`scope` region (via
  the hook registered below), so latency / env / association code needed no
  call-site changes;
* population statistics (sums, means, min/max/std pooling, attention
  pooling) become masked local reductions + ``psum``/``pmax``/``pmin``
  through the ``twin_*`` helpers here, which fall back to plain ``jnp``
  reductions when no scope is active — single-device behavior is
  bit-identical to PR 3.

What is sharded vs replicated (the PR 3 compact-encoding invariant is what
makes this split possible):

=====================================  =====================================
sharded over ``"twin"``                replicated on every shard
=====================================  =====================================
``EnvState.data_sizes``, ``.assoc``    ``EnvState`` freqs/h_up/h_down/dist
``Observation.twin_feats``             ``Observation.bs_feats``
``Action.scores`` (axis 1)             ``Action.b_ctl`` / ``.tau``
OU noise on scores                     MADDPG params, opt state, targets
(per-shard twin blocks)                replay buffer (824 B compact rows)
=====================================  =====================================

Replay rows store ``compact_obs`` + the psum'd ``(M, E)`` action encoding —
both *replicated values* — so the buffer needs no cross-device traffic and
no shard-aware indexing: replay is shard-free.

Padding convention: a global twin array of length N is padded to
``padded_n(N) = n_shards * ceil(N / n_shards)``. Padding rows carry
``assoc = M`` (out of range — dropped by every segment backend) and zero
payloads; the :func:`scope` mask excludes them from pooled statistics.

Gradients: regions run with ``jax.shard_map``'s varying-manual-axes check
on (``check_vma``), under which jax's autodiff through ``psum`` is exact —
verified against the single-device trainer by ``tests/test_sharding.py``.
The checker cannot statically *prove* the resulting parameter gradients
replicated, so :func:`pmean_in_scope` stamps them with a value-preserving
``pmean`` (see ``repro.core.marl.ddpg``).

Single-device meshes are a no-op fast path: every ``sharded_*`` entry point
returns the plain function's result untouched, so CPU CI never traces a
collective.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import threading
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import latency
from repro.kernels.segment_reduce import TWIN_AXIS, register_twin_axis_hook
from repro.launch.mesh import make_twin_mesh

__all__ = [
    "TWIN_AXIS", "TwinSharding", "in_scope", "twin_scope", "localize",
    "slice_local", "mask_twins", "twin_gather", "twin_scatter_rows",
    "model_buffer_specs", "twin_sum", "twin_count", "twin_mean",
    "twin_max",
    "twin_min", "twin_std", "twin_softmax_pool", "local_twin_count",
    "global_twin_count", "pmean_in_scope", "sharded_t_cmp",
    "sharded_t_local_agg", "sharded_t_broadcast", "sharded_round_time",
    "sharded_round_time_per_bs", "sharded_total_time",
]


# ---------------------------------------------------------------------------
# twin-axis trace scope
# ---------------------------------------------------------------------------


class TwinScope(NamedTuple):
    """Static facts about the twin region currently being traced.

    ``axis``     — mesh axis name (always ``TWIN_AXIS`` today).
    ``n_global`` — true (unpadded) twin count N of the whole system.
    ``n_local``  — per-shard block size, ``ceil(N / n_shards)``.
    ``n_shards`` — mesh size along the twin axis.
    """
    axis: str
    n_global: int
    n_local: int
    n_shards: int

    @property
    def exact(self) -> bool:
        """True when N divides evenly — no padding rows exist anywhere."""
        return self.n_local * self.n_shards == self.n_global


_STATE = threading.local()


def in_scope() -> Optional[TwinScope]:
    """The active :class:`TwinScope`, or None outside any twin region."""
    return getattr(_STATE, "scope", None)


@contextlib.contextmanager
def twin_scope(n_global: int, n_local: int, n_shards: int,
               axis: str = TWIN_AXIS):
    """Mark the enclosed *tracing* as happening per-shard inside a twin
    ``shard_map`` region. All ``twin_*`` helpers and ``segment_reduce``'s
    ``"auto"`` dispatch consult this (trace-time only — no runtime state).
    Prefer :meth:`TwinSharding.scope`, which fills the sizes in."""
    prev = in_scope()
    _STATE.scope = TwinScope(axis=axis, n_global=n_global, n_local=n_local,
                             n_shards=n_shards)
    try:
        yield _STATE.scope
    finally:
        _STATE.scope = prev


# let `segment_reduce(..., backend="auto")` see the scope without the kernel
# layer importing upward
register_twin_axis_hook(
    lambda: in_scope().axis if in_scope() is not None else None)


def _require_scope() -> TwinScope:
    s = in_scope()
    if s is None:
        raise RuntimeError("this helper requires an active twin_scope "
                           "(trace it inside TwinSharding.shard_map)")
    return s


def twin_indices() -> jnp.ndarray:
    """Global twin ids of this shard's block, (n_local,) int32. Requires an
    active scope (uses ``lax.axis_index`` over the twin axis)."""
    s = _require_scope()
    return (jax.lax.axis_index(s.axis) * s.n_local
            + jnp.arange(s.n_local, dtype=jnp.int32))


def _mask() -> Optional[jnp.ndarray]:
    """(n_local,) bool validity mask of this shard, or None when N divides
    the mesh exactly (every row real everywhere)."""
    s = _require_scope()
    if s.exact:
        return None
    return twin_indices() < s.n_global


def _bcast_mask(mask: jnp.ndarray, ndim: int, axis: int) -> jnp.ndarray:
    shape = [1] * ndim
    shape[axis] = mask.shape[0]
    return mask.reshape(shape)


def mask_twins(x, fill, *, axis: int = 0):
    """Overwrite padding rows of a local twin array with ``fill``.

    ``x``: (..., n_local, ...) with the twin dimension at ``axis``. Outside
    a scope (or when N divides exactly) this is the identity — the
    single-device no-op guarantee.
    """
    if in_scope() is None:
        return x
    m = _mask()
    if m is None:
        return x
    return jnp.where(_bcast_mask(m, jnp.ndim(x), axis), x, fill)


def local_twin_count(default: int) -> int:
    """Per-shard twin block size inside a scope, else ``default``. Used
    where code materializes twin-shaped arrays (e.g. the OU noise state)."""
    s = in_scope()
    return s.n_local if s is not None else default


def global_twin_count(default: int) -> int:
    """True global N inside a scope, else ``default``. Used by
    normalizations that must divide by the *system* twin count even though
    the local arrays are shard-sized."""
    s = in_scope()
    return s.n_global if s is not None else default


# ---------------------------------------------------------------------------
# population reductions — masked local op + collective; plain jnp otherwise
# ---------------------------------------------------------------------------


def twin_sum(x, axis: int = 0):
    """Global sum over the twin axis: ``jnp.sum`` outside a scope, masked
    local sum + ``psum`` inside. Shapes per shard: x (..., n_local, ...) ->
    global (...,) — identical to the single-device result."""
    s = in_scope()
    if s is None:
        return jnp.sum(x, axis=axis)
    return jax.lax.psum(jnp.sum(mask_twins(x, 0, axis=axis), axis=axis),
                        s.axis)


def twin_count(mask, axis: int = 0) -> jnp.ndarray:
    """Global count of True rows of a boolean twin mask (padding rows
    excluded), int32 — the live-population accounting primitive of the
    serve loop's churn masks (``repro.core.serve``). Replicated (psum'd)
    under a scope, a plain sum outside."""
    return twin_sum(jnp.asarray(mask).astype(jnp.int32), axis=axis)


def twin_mean(x, axis: int = 0):
    """Global mean over the twin axis (masked sum / true N under a scope)."""
    s = in_scope()
    if s is None:
        return jnp.mean(x, axis=axis)
    return twin_sum(x, axis=axis) / s.n_global


def twin_max(x, axis: int = 0):
    """Global max over the twin axis (``pmax`` of masked local maxima)."""
    s = in_scope()
    if s is None:
        return jnp.max(x, axis=axis)
    return jax.lax.pmax(
        jnp.max(mask_twins(x, -jnp.inf, axis=axis), axis=axis), s.axis)


def twin_min(x, axis: int = 0):
    """Global min over the twin axis (``pmin`` of masked local minima)."""
    s = in_scope()
    if s is None:
        return jnp.min(x, axis=axis)
    return jax.lax.pmin(
        jnp.min(mask_twins(x, jnp.inf, axis=axis), axis=axis), s.axis)


def twin_std(x, axis: int = 0):
    """Global population std (ddof=0, matching ``jnp.std``) over the twin
    axis, via the psum'd moments E[x^2] - E[x]^2 under a scope."""
    if in_scope() is None:
        return jnp.std(x, axis=axis)
    m = twin_mean(x, axis=axis)
    m2 = twin_mean(jnp.square(x), axis=axis)
    return jnp.sqrt(jnp.maximum(m2 - jnp.square(m), 0.0))


def twin_softmax_pool(logits, feats):
    """Attention pooling ``softmax(logits) @ feats`` over the twin axis.

    Shapes per shard: logits (n_local,), feats (n_local, F) -> (F,) global.
    Under a scope this is the numerically-stable cross-shard softmax:
    ``pmax`` shift (stop-gradient — the shift is mathematically inert),
    masked exponentials, and psum'd numerator/denominator, so the result
    and its gradients match the single-device pooling."""
    s = in_scope()
    if s is None:
        return jax.nn.softmax(logits) @ feats
    local_max = jnp.max(mask_twins(logits, -jnp.inf))
    shift = jax.lax.pmax(jax.lax.stop_gradient(local_max), s.axis)
    e = jnp.exp(logits - shift)
    m = _mask()
    if m is not None:
        e = e * m
    den = jax.lax.psum(jnp.sum(e), s.axis)
    num = jax.lax.psum(e @ feats, s.axis)
    return num / jnp.maximum(den, 1e-30)


def pmean_in_scope(tree):
    """Stamp a pytree of (replicated-in-fact) gradients with ``pmean`` so
    the replication checker accepts them as replicated outputs. Exact
    gradients come out of jax's autodiff already (see module docstring);
    this is value-preserving. No-op outside a scope."""
    s = in_scope()
    if s is None:
        return tree
    return jax.tree_util.tree_map(lambda g: jax.lax.pmean(g, s.axis), tree)


def stamp_replicated(tree):
    """Tag every leaf of a replicated-in-fact pytree as replicated for the
    checker: ``pmean`` on floats, ``pmax`` on integer/bool leaves (both
    value-preserving when all shards hold the same data). Needed for scan
    carries whose initial value the checker cannot trace to a collective
    (e.g. zero-initialized replay/optimizer state) but whose body output
    is psum-derived. No-op outside a scope. Do NOT apply to twin-sharded
    leaves — averaging different blocks destroys them."""
    s = in_scope()
    if s is None:
        return tree

    def one(x):
        if jnp.issubdtype(jnp.asarray(x).dtype, jnp.inexact):
            return jax.lax.pmean(x, s.axis)
        return jax.lax.pmax(x, s.axis)

    return jax.tree_util.tree_map(one, tree)


def stamp_varying(tree):
    """Tag every leaf of a twin-sharded pytree as varying over the twin
    axis (``lax.pcast(..., to="varying")``, value-preserving) — for scan
    carries initialized shard-locally from constants (e.g. zero OU noise)
    whose body output varies per shard. No-op outside a scope."""
    s = in_scope()
    if s is None:
        return tree
    return jax.tree_util.tree_map(
        lambda x: jax.lax.pcast(x, s.axis, to="varying"), tree)


# ---------------------------------------------------------------------------
# parity-exact localization of globally-drawn arrays
# ---------------------------------------------------------------------------


def slice_local(x, *, axis: int = 0, fill=None):
    """This shard's block of a *global* twin array, (..., n_local, ...).

    ``x`` has the true global extent N at ``axis`` (typically a PRNG draw
    every shard computed identically from a replicated key). The array is
    zero-padded to ``n_shards * n_local``, dynamically sliced at this
    shard's offset, and — when ``fill`` is given — padding rows are
    overwritten with ``fill`` (e.g. ``M`` for association ids, so padded
    twins drop out of every segment reduction).

    Drawing the full array and slicing (instead of drawing per-shard
    streams) is what makes the sharded env/trainer *bit-identical* to the
    single-device path: both consume the same PRNG draws. The transient is
    O(N) bytes but holds only for one fused op — at N=10^6 that is 4 MB.
    Requires an active scope.
    """
    s = _require_scope()
    x = jnp.asarray(x)
    pad = s.n_local * s.n_shards - x.shape[axis]
    if pad < 0:
        raise ValueError(f"axis {axis} of {x.shape} exceeds the scope's "
                         f"global twin count {s.n_global}")
    if pad:
        widths = [(0, 0)] * x.ndim
        widths[axis] = (0, pad)
        x = jnp.pad(x, widths)
    starts = [0] * x.ndim
    starts[axis] = jax.lax.axis_index(s.axis) * s.n_local
    sizes = list(x.shape)
    sizes[axis] = s.n_local
    out = jax.lax.dynamic_slice(x, starts, sizes)
    if fill is not None:
        out = mask_twins(out, fill, axis=axis)
    return out


def localize(x, *, axis: int = 0, fill=None):
    """:func:`slice_local` under a scope, identity outside — the one-liner
    that makes a globally-written sampler shard-aware (see
    ``env_reset`` / ``scenario.sample_population``)."""
    if in_scope() is None:
        return x
    return slice_local(x, axis=axis, fill=fill)


# ---------------------------------------------------------------------------
# global-id row access on twin buffers — the streamed-FL scatter/gather
# ---------------------------------------------------------------------------


def twin_gather(x, idx, *, fill=0):
    """Rows ``idx`` (global twin ids, any shape) of a twin array ``x``.

    Out-of-range ids (negative, >= N, or a shard's padding rows) return
    ``fill`` — the sentinel the streamed-FL plan uses for dropped
    participants. Under a scope each id is owned by exactly one shard, so
    the masked local gather psums to the single owner's row and the result
    is replicated (every shard sees the full participant slate)."""
    idx = jnp.asarray(idx, jnp.int32)
    s = in_scope()
    if s is None:
        return jnp.take(x, idx, axis=0, mode="fill", fill_value=fill)
    li = idx - jax.lax.axis_index(s.axis) * s.n_local
    own = (li >= 0) & (li < s.n_local) & (idx >= 0) & (idx < s.n_global)
    vals = jnp.take(x, jnp.clip(li, 0, s.n_local - 1), axis=0)
    zero = jnp.zeros((), vals.dtype)
    shape = own.shape + (1,) * (vals.ndim - own.ndim)
    picked = jnp.where(own.reshape(shape), vals, zero)
    # bool/int rows survive the psum as int32, then cast back
    summed = jax.lax.psum(picked.astype(jnp.int32), s.axis) \
        if vals.dtype == jnp.bool_ else jax.lax.psum(picked, s.axis)
    out = summed.astype(vals.dtype)
    miss = (idx < 0) | (idx >= s.n_global)
    return jnp.where(miss.reshape(shape), jnp.asarray(fill, vals.dtype), out)


def twin_scatter_rows(x, idx, rows):
    """Write ``rows`` (K, ...) at global twin ids ``idx`` (K,) into twin
    array ``x``; out-of-range ids (the dropped-participant sentinel ``-1``,
    or another shard's rows under a scope) are silently dropped — each
    shard writes only the rows it owns, so the sharded buffer stays the
    row-for-row image of the single-device one. Duplicate ids are not
    supported (participants are sampled without replacement)."""
    idx = jnp.asarray(idx, jnp.int32)
    s = in_scope()
    if s is None:
        n = x.shape[0]
        safe = jnp.where((idx >= 0) & (idx < n), idx, n)
        return x.at[safe].set(rows, mode="drop")
    li = idx - jax.lax.axis_index(s.axis) * s.n_local
    own = (li >= 0) & (li < s.n_local) & (idx >= 0) & (idx < s.n_global)
    safe = jnp.where(own, li, s.n_local)
    return x.at[safe].set(rows, mode="drop")


def model_buffer_specs(tree) -> object:
    """Partition specs for a ``(capacity, ...)``-leading model/optimizer
    buffer pytree (the streamed-FL twin buffers): every leaf twin-sharded
    on its leading axis, trailing parameter dims replicated."""
    return jax.tree_util.tree_map(lambda _: P(TWIN_AXIS), tree)


# ---------------------------------------------------------------------------
# TwinSharding — mesh handle, specs, padding, shard_map surface
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class TwinSharding:
    """Handle for a twin-axis device mesh (axis name ``TWIN_AXIS``).

    Construct via :meth:`make` (wraps ``launch.mesh.make_twin_mesh``). All
    ``sharded_*`` entry points take one of these; ``n_shards == 1`` is the
    documented no-op fast path everywhere.
    """
    mesh: object  # jax.sharding.Mesh with the single axis TWIN_AXIS

    @classmethod
    def make(cls, n_shards: int | None = None) -> "TwinSharding":
        """Mesh over ``n_shards`` devices (default: all visible)."""
        return cls(mesh=make_twin_mesh(n_shards))

    def __post_init__(self):
        names = tuple(getattr(self.mesh, "axis_names", ()))
        if names != (TWIN_AXIS,):
            raise ValueError(f"TwinSharding needs a 1-D mesh with axis "
                             f"{TWIN_AXIS!r}, got axes {names}")

    @property
    def n_shards(self) -> int:
        return self.mesh.shape[TWIN_AXIS]

    def local_n(self, n: int) -> int:
        """Per-shard block size ``ceil(n / n_shards)``."""
        return -(-n // self.n_shards)

    def padded_n(self, n: int) -> int:
        """Smallest multiple of ``n_shards`` covering ``n``."""
        return self.local_n(n) * self.n_shards

    def twin_spec(self, axis: int = 0, ndim: int = 1) -> P:
        """PartitionSpec sharding dimension ``axis`` of an ``ndim``-array
        over the twin axis (everything else replicated)."""
        return P(*[TWIN_AXIS if i == axis else None for i in range(ndim)])

    def pad_twin(self, x, *, axis: int = 0, fill=0):
        """Pad a global twin array to :meth:`padded_n` with ``fill`` rows
        (use ``fill=M`` for association ids so padding drops out of the
        segment reductions)."""
        x = jnp.asarray(x)
        pad = self.padded_n(x.shape[axis]) - x.shape[axis]
        if pad == 0:
            return x
        widths = [(0, 0)] * x.ndim
        widths[axis] = (0, pad)
        return jnp.pad(x, widths, constant_values=fill)

    def unpad_twin(self, x, n: int, *, axis: int = 0):
        """Strip padding rows back to the true global extent ``n``."""
        return jax.lax.slice_in_dim(x, 0, n, axis=axis)

    def shard_keys(self, key) -> jnp.ndarray:
        """Independent per-shard PRNG streams, (n_shards, 2) uint32. For
        scale-out sampling where cross-path parity is NOT required (the
        parity-exact alternative is drawing globally + :func:`slice_local`
        — see that docstring). Pair with :meth:`take_shard_key` inside the
        region."""
        return jax.random.split(key, self.n_shards)

    @staticmethod
    def take_shard_key(keys) -> jnp.ndarray:
        """This shard's key out of a :meth:`shard_keys` stack (requires an
        active scope)."""
        s = _require_scope()
        return jax.lax.dynamic_index_in_dim(
            keys, jax.lax.axis_index(s.axis), keepdims=False)

    def scope(self, n_global: int):
        """The :func:`twin_scope` for a region over this mesh — call inside
        the ``shard_map``-traced function, with the *true* twin count."""
        return twin_scope(n_global, self.local_n(n_global), self.n_shards)

    def shard_map(self, fn, in_specs, out_specs):
        """``jax.shard_map`` over this mesh with replication checking ON
        (required for exact autodiff — module docstring)."""
        return jax.shard_map(fn, mesh=self.mesh, in_specs=in_specs,
                             out_specs=out_specs)


# ---------------------------------------------------------------------------
# sharded latency model — Eqs. 12-17 over the mesh
# ---------------------------------------------------------------------------
#
# Each wrapper pads the (N,)-shaped inputs, shard_maps the *unchanged*
# latency function, and lets the scope flip segment_reduce's "auto" dispatch
# to the local-reduce + psum composition. Outputs ((M,) or scalar) are
# replicated. Single-device meshes return the plain call untouched.


def _shard_call(ts: TwinSharding, fn, kinds: str, fills, *args):
    """Run ``fn(*args)`` under ``ts``: ``kinds[i]`` is ``"t"`` for a
    twin-sharded (N,)-leading arg (padded with ``fills[i]``) or ``"r"`` for
    a replicated one. The first ``"t"`` arg defines N."""
    if ts.n_shards == 1:
        return fn(*args)
    n = next(jnp.shape(a)[0] for a, k in zip(args, kinds) if k == "t")
    padded = tuple(
        ts.pad_twin(a, fill=f) if k == "t" else a
        for a, k, f in zip(args, kinds, fills))
    in_specs = tuple(P(TWIN_AXIS) if k == "t" else P() for k in kinds)

    def local(*local_args):
        with ts.scope(n):
            return fn(*local_args)

    return ts.shard_map(local, in_specs=in_specs, out_specs=P())(*padded)


def sharded_t_cmp(ts: TwinSharding, params: latency.LatencyParams, assoc, b,
                  data_sizes, freqs) -> jnp.ndarray:
    """Eq. 12 over the mesh: assoc/b/data_sizes are global (N,) arrays
    (sharded + padded internally), freqs (M,) replicated. Returns the
    replicated (M,) per-BS compute time."""
    m = freqs.shape[0]
    return _shard_call(ts, functools.partial(latency.t_cmp, params), "tttr",
                       (m, 0, 0, None), assoc, b, data_sizes, freqs)


def sharded_t_local_agg(ts: TwinSharding, params: latency.LatencyParams,
                        assoc, freqs) -> jnp.ndarray:
    """Eq. 14 over the mesh (per-BS twin counts psum'd), (M,) replicated."""
    m = freqs.shape[0]
    return _shard_call(ts, functools.partial(latency.t_local_agg, params),
                       "tr", (m, None), assoc, freqs)


def sharded_t_broadcast(ts: TwinSharding, params: latency.LatencyParams,
                        assoc, uplink, n_bs: int) -> jnp.ndarray:
    """Eq. 15 over the mesh, (M,) replicated."""
    fn = lambda a, u: latency.t_broadcast(params, a, u, n_bs)
    return _shard_call(ts, fn, "tr", (n_bs, None), assoc, uplink)


def sharded_round_time(ts: TwinSharding, params: latency.LatencyParams,
                       assoc, b, data_sizes, freqs, uplink, downlink,
                       consensus=None) -> jnp.ndarray:
    """Eq. 17 system round time over the mesh (scalar, replicated). The
    per-BS partial sums travel as one (M,)-sized psum per reduction; the
    max compositions run on the replicated (M,) results. ``consensus``
    (a static ``ConsensusConfig``) swaps the Eq. 16 constant for the PBFT
    term — computed on replicated (M,) link rates, so it needs no extra
    collectives."""
    m = freqs.shape[0]
    return _shard_call(
        ts, functools.partial(latency.round_time, params,
                              consensus=consensus),
        "tttrrr", (m, 0, 0, None, None, None),
        assoc, b, data_sizes, freqs, uplink, downlink)


def sharded_round_time_per_bs(ts: TwinSharding,
                              params: latency.LatencyParams, assoc, b,
                              data_sizes, freqs, uplink, downlink,
                              consensus=None) -> jnp.ndarray:
    """Per-BS T_i (the MARL reward term) over the mesh, (M,) replicated."""
    m = freqs.shape[0]
    return _shard_call(
        ts, functools.partial(latency.round_time_per_bs, params,
                              consensus=consensus), "tttrrr",
        (m, 0, 0, None, None, None), assoc, b, data_sizes, freqs, uplink,
        downlink)


def sharded_total_time(ts: TwinSharding, params: latency.LatencyParams,
                       assoc, b, data_sizes, freqs, uplink, downlink,
                       consensus=None) -> jnp.ndarray:
    """Problem (18) objective over the mesh (scalar, replicated)."""
    m = freqs.shape[0]
    return _shard_call(
        ts, functools.partial(latency.total_time, params,
                              consensus=consensus),
        "tttrrr", (m, 0, 0, None, None, None),
        assoc, b, data_sizes, freqs, uplink, downlink)
