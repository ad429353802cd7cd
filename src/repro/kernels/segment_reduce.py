"""Unified segment-reduction subsystem for the DTWN latency hot path.

Every per-BS quantity in the paper's latency model (Eqs. 12-17) and the
hierarchical aggregation (Eqs. 4-5) is a *segment reduction*: sum per-twin
values grouped by the association vector ``assoc: (N,) int`` into ``M``
base-station bins. PR 1 routed these through ``jax.ops.segment_sum``, which
is O(N+M) memory but lowers to a scatter-add that XLA-CPU serializes —
ROADMAP notes it loses to the dense one-hot matmul below N ~ 10^4. This
module makes the reduction strategy a first-class, swappable backend:

``"segment_sum"``
    ``jax.ops.segment_sum`` scatter-add — the PR 1 reference path. Best on
    CPU at large N (linear, no sort), and on GPU where scatter-add is
    parallel.
``"sort"``
    Sort-based contiguous grouping: ``argsort(assoc)``, gather values into
    segment-contiguous order, exclusive ``cumsum``, then per-segment
    differences at the segment boundaries found with ``searchsorted``.
    No scatter at all — every step is a sort, gather, or prefix sum.
    In practice XLA-CPU's comparator sort dominates its runtime and it
    loses the sweep at every N (see the measured table below); it is kept
    for platforms with fast radix sorts and as the contiguous-reduction
    reference the multi-tier/migration scenarios will want (segment
    boundaries come for free once twins are sorted by BS).
``"pallas"``
    The tiled-accumulator kernel: the twin axis streams through VMEM in
    ``_PALLAS_BLOCK``-sized tiles and, per 128-lane-aligned tile of the
    payload axis K, an (M, tile)-wide fp32 accumulator stays resident
    across the twin steps — per tile it builds the (tile, M) membership
    mask and contracts it against the value tile on the MXU. VMEM use is
    bounded independently of K (an Eq. 4 CNN leaf is K = 2^21). One pass
    over HBM, no serialized scatter. On TPU this compiles as a
    native Pallas kernel; on CPU/GPU it executes as the XLA reference
    lowering with *identical tiling* (a ``lax.scan`` over the same twin
    tiles — measured 4-5x faster than the serialized scatter-add on
    XLA-CPU at M=8; see the sweep). ``interpret=True`` forces the Pallas
    interpreter on the kernel itself (used by the parity tests;
    numerics-correct but slow).
``"onehot"``
    The dense ``(N, M)`` one-hot contraction the seed used: one BLAS-sized
    matmul, the fastest CPU path while the (N, M) mask fits in cache-ish
    memory, but O(N*M) bytes so it dies at large N*M. Kept both as the
    numerical oracle for the parity tests and as an auto-dispatch choice
    below ``_ONEHOT_BYTES_BUDGET``.
``"sharded"``
    The device-mesh composition: inside a ``shard_map`` region whose mesh
    carries the ``"twin"`` axis (see ``repro.core.sharding``), each shard
    reduces its local twin block with whichever single-device backend
    ``resolve_backend`` picks for the *local* N, then the (M, K) partials
    are combined with one ``lax.psum`` over the twin axis. Only valid
    inside such a region; ``"auto"`` resolves to it automatically whenever
    ``repro.core.sharding`` reports an active twin-axis scope (registered
    via :func:`register_twin_axis_hook`), so every existing caller —
    latency Eqs. 12-17, env observe, association loads — shards without
    source changes.

``segment_reduce(values, assoc, M, backend="auto")`` dispatches between
them from static information only (N, M, payload width, platform), so it is
safe to call inside ``jit``/``vmap``/``scan`` — the choice is made at trace
time and never introduces data-dependent control flow.

Measured on XLA-CPU, M=8, fp32 (results/bench/scale.json,
``segment_reduce_sweep_us``): onehot wins to N~10^6 (30us @ 10^3, 441us @
10^5), the tiled pallas lowering is next (27us @ 10^3, 12.5ms @ 10^6,
always 4-5x ahead of segment_sum's 79us @ 10^3 / 61ms @ 10^6), and the
sort path loses everywhere because XLA-CPU's comparator sort dominates its
runtime — it exists for platforms with fast sorts and as the
cumsum-boundary reference.

Conventions (shared by all callers in ``repro.core``):
    ``assoc``  — (N,) integer twin->BS map, values in ``[0, M)``. Ids
                 outside the range are dropped by every backend.
    ``values`` — (N,) or (N, ...) per-twin payload; trailing dims are
                 flattened to a lane axis K and restored on return.
    returns    — (M,) or (M, ...) fp32 per-BS sums (accumulation is fp32
                 regardless of input dtype, matching ``bs_sum`` in PR 1).
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BACKENDS = ("auto", "pallas", "sort", "segment_sum", "onehot", "sharded")

# Mesh axis name of the twin dimension (bound by repro.core.sharding /
# repro.launch.mesh.make_twin_mesh). Lives here so the kernel layer needs no
# upward import to name the psum axis of the "sharded" backend.
TWIN_AXIS = "twin"

# Optional hook registered by repro.core.sharding: a zero-arg callable
# returning the active twin-axis name (str) when tracing inside a twin
# shard_map region, else None. With it, backend="auto" transparently
# resolves to "sharded" inside such regions — callers keep their code.
_TWIN_AXIS_HOOK = None


def register_twin_axis_hook(fn) -> None:
    """Install the scope probe ``fn() -> str | None`` (see module docstring).
    Called once by ``repro.core.sharding`` at import; identity-checked so a
    re-import is a no-op."""
    global _TWIN_AXIS_HOOK
    _TWIN_AXIS_HOOK = fn


def _active_twin_axis():
    return _TWIN_AXIS_HOOK() if _TWIN_AXIS_HOOK is not None else None

# Auto-dispatch constants, measured on XLA-CPU (results/bench/scale.json:
# segment_reduce_sweep_us — rerun `python -m benchmarks.bench_scale` after
# touching any backend):
# dense one-hot while the (N, M) fp32 mask stays under this many bytes...
_ONEHOT_BYTES_BUDGET = 64 * 2**20
# ...then the tiled pallas lowering while its N*M mask FLOPs stay ahead of
# the O(N) serialized scatter — beyond this M the scatter-add wins.
_TILED_MAX_SEGMENTS = 32

# Twin-axis tile for the Pallas kernel and its XLA reference lowering:
# 8 sublanes x 128 lanes of fp32.
_PALLAS_BLOCK = 1024
# VMEM bytes of one (twin tile, lane tile) fp32 value block of the Pallas
# kernel. The payload axis K is tiled in 128-lane multiples to stay under
# it, so VMEM use is independent of K (a whole CNN layer, K = 2^21, would
# otherwise need a 250 MB window). Pallas double-buffers each block.
_PALLAS_TILE_BYTES = 2 * 2**20
_LANES = 128


def default_interpret() -> bool:
    """Pallas interpret-mode default: native on a TPU, the interpreter
    elsewhere. ``REPRO_PALLAS_INTERPRET`` overrides it off the chip only —
    asking for the interpreter on a TPU raises, so a chip run can never
    quietly execute its kernels in interpret mode. The single source of
    this convention — repro.kernels.ops delegates here for the other
    Pallas kernels."""
    on_tpu = jax.default_backend() == "tpu"
    env = os.environ.get("REPRO_PALLAS_INTERPRET")
    if env is None:
        return not on_tpu
    interpret = env not in ("0", "false", "False")
    if interpret and on_tpu:
        raise RuntimeError(
            f"REPRO_PALLAS_INTERPRET={env!r} asks for the Pallas interpreter"
            f" on a TPU; unset it to run the compiled kernels")
    return interpret


def resolve_backend(n: int, num_segments: int, *, platform=None) -> str:
    """Pick a concrete backend from static shape/platform information.

    TPU -> the Pallas kernel (VMEM-resident accumulator, MXU contraction)
    at every shape: its grid tiles both the twin axis and the payload axis
    K, so any (N, K, M) compiles — a per-BS latency sum (K = 1) as well as
    an Eq. 4 model-leaf aggregation (K = |leaf|, up to 2^21 for the paper
    CNN's fc1 weight).
    CPU -> dense one-hot while the (N, M) mask fits ``_ONEHOT_BYTES_BUDGET``
    (a single BLAS matmul — the measured CPU winner at small N*M), then the
    tiled pallas lowering while M <= ``_TILED_MAX_SEGMENTS`` (4-5x over the
    serialized scatter at M=8), scatter-add ``segment_sum`` beyond that.
    GPU -> one-hot under the same budget (matmul >> serial tile scan on
    parallel hardware), scatter-add otherwise. Never picks ``sort`` —
    XLA-CPU's comparator sort makes it a measured loss at every N (see
    module docstring); it stays available explicitly.
    """
    platform = platform or jax.default_backend()
    if platform == "tpu":
        return "pallas"
    if n * max(num_segments, 1) * 4 <= _ONEHOT_BYTES_BUDGET:
        return "onehot"
    if platform == "cpu" and num_segments <= _TILED_MAX_SEGMENTS:
        return "pallas"
    return "segment_sum"


# ---------------------------------------------------------------------------
# backends — each takes values (N, K) fp32, assoc (N,) int, returns (M, K)
# ---------------------------------------------------------------------------


def _seg_segment_sum(values, assoc, num_segments: int):
    return jax.ops.segment_sum(values, assoc, num_segments=num_segments)


def sort_groups(assoc, num_segments: int):
    """Contiguous-grouping primitive of the ``"sort"`` backend.

    Args:
        assoc: (N,) integer segment ids (any order, out-of-range allowed).
        num_segments: M, the static number of segments.

    Returns:
        ``(order, bounds)``: ``order`` (N,) int32 is the stable argsort of
        ``assoc`` — gathering any per-twin array through it makes every
        segment a contiguous slice — and ``bounds`` (M+1,) int32 marks the
        slice boundaries: segment m occupies sorted positions
        ``[bounds[m], bounds[m+1])``. Ids below 0 sort before ``bounds[0]``
        and ids >= M after ``bounds[M]``, so out-of-range rows (twin-axis
        padding) fall outside every segment. This is the free by-product of
        sorting twins by BS that the migration subsystem
        (``repro.core.migration``) consumes as per-BS segment boundaries.
    """
    order = jnp.argsort(assoc)
    bounds = jnp.searchsorted(jnp.take(assoc, order),
                              jnp.arange(num_segments + 1), side="left")
    return order.astype(jnp.int32), bounds.astype(jnp.int32)


def _seg_sorted(values, assoc, num_segments: int):
    """Contiguous grouping: sort by segment id, exclusive prefix sum, then
    difference the prefix sums at segment boundaries. All gathers — no
    scatter for XLA-CPU to serialize."""
    order, bounds = sort_groups(assoc, num_segments)
    sv = jnp.take(values, order, axis=0)
    csum = jnp.concatenate(
        [jnp.zeros_like(sv[:1]), jnp.cumsum(sv, axis=0)], axis=0)  # (N+1, K)
    # bounds[m] = first sorted position with id >= m; bounds[M] ends the last
    # in-range segment, so ids outside [0, M) fall off either end and drop.
    return jnp.take(csum, bounds[1:], axis=0) - jnp.take(csum, bounds[:-1],
                                                         axis=0)


def _seg_onehot(values, assoc, num_segments: int):
    """Dense (N, M) one-hot contraction — the seed implementation and the
    parity oracle. O(N*M) memory; do not use at large N."""
    onehot = (assoc[:, None] == jnp.arange(num_segments)[None, :])
    return jnp.tensordot(onehot.astype(values.dtype), values,
                         axes=[[0], [0]])


def _seg_pallas_kernel(a_ref, v_ref, o_ref, *, num_segments: int, n: int,
                       block_n: int):
    """Grid step (j, i) reduces twin tile i of lane tile j into the resident
    accumulator.

    The output BlockSpec maps every twin step of lane tile j to the same
    (M, block_k) block, so it stays in VMEM across the sequential twin axis
    and accumulates — the standard matmul-k-loop pattern, with the twin
    axis as the contraction. Rows past ``n`` in a ragged last twin tile
    hold whatever the block window read; they are masked out here, so
    nothing is padded in HBM.
    """
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    a = a_ref[...]                                    # (block_n, 1)
    v = v_ref[...].astype(jnp.float32)                # (block_n, block_k)
    seg_ids = jax.lax.broadcasted_iota(jnp.int32, (a.shape[0], num_segments),
                                       1)
    hit = a == seg_ids                                # (block_n, M)
    if n % block_n:
        rows = i * block_n + jax.lax.broadcasted_iota(jnp.int32, a.shape, 0)
        valid = rows < n
        hit = hit & valid
        v = jnp.where(valid, v, 0.0)
    # (M, block_k) partial = mask^T @ v — contraction over the twin tile
    o_ref[...] += jax.lax.dot_general(
        hit.astype(jnp.float32), v,
        dimension_numbers=(((0,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


def _seg_tiled_ref(values, assoc, num_segments: int, *,
                   block: int = _PALLAS_BLOCK):
    """XLA reference lowering of the Pallas kernel — the same twin tiling
    and (M, K) accumulator, expressed as a ``lax.scan`` over tiles so the
    compiler sees O(block*M) live memory instead of the dense (N, M) mask.
    This is what ``backend="pallas"`` runs on non-TPU platforms."""
    n, k = values.shape
    block = min(block, max(n, 1))
    pad = (-n) % block
    ap = jnp.pad(assoc.astype(jnp.int32), (0, pad),
                 constant_values=num_segments)
    vp = jnp.pad(values, ((0, pad), (0, 0)))
    nb = (n + pad) // block
    ids = jnp.arange(num_segments)

    def body(acc, tile):
        a_t, v_t = tile
        mask = (a_t[:, None] == ids[None, :]).astype(jnp.float32)
        part = jax.lax.dot_general(
            mask, v_t, dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return acc + part, None

    acc, _ = jax.lax.scan(body, jnp.zeros((num_segments, k), jnp.float32),
                          (ap.reshape(nb, block), vp.reshape(nb, block, k)))
    return acc


def _pallas_blocks(n: int, k: int, block: int) -> tuple:
    """(twin tile, lane tile) of the Pallas grid. The twin tile is
    ``block`` (or all of a shorter N); the lane tile is all of K while the
    value block fits ``_PALLAS_TILE_BYTES``, else the largest multiple of
    128 lanes that does."""
    block_n = min(block, n)
    rows = -(-block_n // 8) * 8                       # sublane-padded
    max_k = max(_LANES, _PALLAS_TILE_BYTES // (4 * rows) // _LANES * _LANES)
    return block_n, (k if k <= max_k else max_k)


def _pcast_varying(x, vma):
    missing = tuple(sorted(vma - jax.typeof(x).vma))
    return jax.lax.pcast(x, missing, to="varying") if missing else x


def _seg_pallas_call(values, assoc, num_segments: int, block: int,
                     interpret: bool):
    n, k = values.shape
    block_n, block_k = _pallas_blocks(n, k, block)
    # ids travel as an (N, 1) column: a 2-D block keeps the TPU tiling
    # rule (last two block dims divisible by (8, 128) or full) satisfied
    # when vmap prepends a batch axis
    a2 = assoc.astype(jnp.int32).reshape(n, 1)
    # inside a shard_map region the output varies over the mesh axes its
    # inputs vary over; both inputs are brought to that same set
    vma = jax.typeof(values).vma | jax.typeof(a2).vma
    values, a2 = (_pcast_varying(x, vma) for x in (values, a2))
    return pl.pallas_call(
        functools.partial(_seg_pallas_kernel, num_segments=num_segments,
                          n=n, block_n=block_n),
        grid=(pl.cdiv(k, block_k), pl.cdiv(n, block_n)),
        in_specs=[
            pl.BlockSpec((block_n, 1), lambda j, i: (i, 0)),
            pl.BlockSpec((block_n, block_k), lambda j, i: (i, j)),
        ],
        out_specs=pl.BlockSpec((num_segments, block_k), lambda j, i: (0, j)),
        out_shape=jax.ShapeDtypeStruct((num_segments, k), jnp.float32,
                                       vma=vma),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="segment_reduce",
    )(a2, values)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _seg_pallas_diff(values, assoc, num_segments, block, interpret):
    return _seg_pallas_call(values, assoc, num_segments, block, interpret)


def _seg_pallas_fwd(values, assoc, num_segments, block, interpret):
    return (_seg_pallas_call(values, assoc, num_segments, block, interpret),
            assoc)


def _seg_pallas_bwd(num_segments, block, interpret, assoc, g):
    # the reduction is linear in values: d values[j] = g[assoc[j]], zero for
    # dropped (out-of-range) ids
    ids = jnp.where((assoc >= 0) & (assoc < num_segments), assoc,
                    num_segments)
    g0 = jnp.concatenate([g, jnp.zeros_like(g[:1])], axis=0)
    return jnp.take(g0, ids, axis=0), None


_seg_pallas_diff.defvjp(_seg_pallas_fwd, _seg_pallas_bwd)


def _seg_pallas(values, assoc, num_segments: int, *, block: int = _PALLAS_BLOCK,
                interpret=None):
    """Tiled Pallas reduction: twins stream HBM->VMEM in ``block``-row
    tiles, lane tiles of the payload in parallel, and each (M, block_k)
    accumulator never leaves VMEM. On non-TPU platforms (unless
    ``interpret`` is given or ``REPRO_PALLAS_INTERPRET`` is set) this
    routes to the XLA reference lowering with identical twin tiling — the
    Pallas interpreter is numerics-faithful but far too slow for the hot
    path. Differentiable through a custom VJP (the gather ``g[assoc]``)
    and batchable under ``vmap``."""
    if interpret is None:
        if (os.environ.get("REPRO_PALLAS_INTERPRET") is None
                and jax.default_backend() != "tpu"):
            return _seg_tiled_ref(values, assoc, num_segments, block=block)
        interpret = default_interpret()
    return _seg_pallas_diff(values, assoc, num_segments, block,
                            bool(interpret))


_IMPLS = {
    "segment_sum": _seg_segment_sum,
    "sort": _seg_sorted,
    "onehot": _seg_onehot,
}


# ---------------------------------------------------------------------------
# dispatch
# ---------------------------------------------------------------------------


def segment_reduce(values, assoc, num_segments: int, *, backend: str = "auto",
                   interpret=None, axis_name: str | None = None
                   ) -> jnp.ndarray:
    """Sum per-twin ``values`` grouped by BS: out[m] = sum_{j: assoc[j]==m}.

    Args:
        values: (N,) or (N, ...) per-twin payload (any real dtype). Under
            ``backend="sharded"`` this is the *local* shard (N_local, ...)
            and the result is the global per-BS sum.
        assoc: (N,) integer segment ids in [0, num_segments); out-of-range
            ids are dropped (which is how twin-axis padding rows opt out).
        num_segments: M, the static number of output bins.
        backend: one of ``BACKENDS``. ``"auto"`` resolves from static shape
            and platform via :func:`resolve_backend` at trace time — or to
            ``"sharded"`` when the registered twin-axis hook reports an
            active mesh scope.
        interpret: Pallas interpret-mode override (pallas backend only);
            default follows ``REPRO_PALLAS_INTERPRET`` / the platform.
        axis_name: mesh axis for the ``"sharded"`` psum; defaults to the
            hook's active axis, then ``TWIN_AXIS``.

    Returns:
        (num_segments,) or (num_segments, ...) fp32 sums — per shard *and*
        global under ``"sharded"`` (the psum replicates the result).
    """
    if backend not in BACKENDS:
        raise ValueError(f"backend must be one of {BACKENDS}, got {backend!r}")
    values = jnp.asarray(values)
    assoc = jnp.asarray(assoc)
    if assoc.ndim != 1:
        raise ValueError(f"assoc must be (N,), got shape {assoc.shape}")
    if values.ndim == 0 or values.shape[0] != assoc.shape[0]:
        raise ValueError(
            f"values leading axis {values.shape} must match assoc "
            f"{assoc.shape}")
    n = assoc.shape[0]
    tail = values.shape[1:]
    if n == 0:
        # empty twin population: all segments empty (matches what the PR 1
        # jax.ops.segment_sum path returned; reshape(-1)/grid=(0,) would
        # misbehave below)
        return jnp.zeros((num_segments,) + tail, jnp.float32)
    if backend == "auto":
        backend = ("sharded" if _active_twin_axis() is not None
                   else resolve_backend(n, num_segments))
    psum_axis = None
    if backend == "sharded":
        psum_axis = axis_name or _active_twin_axis() or TWIN_AXIS
        # local block through the best single-device backend for local N
        backend = resolve_backend(n, num_segments)

    flat = values.astype(jnp.float32).reshape(n, -1)  # (N, K)
    if backend == "pallas":
        out = _seg_pallas(flat, assoc, num_segments, interpret=interpret)
    else:
        out = _IMPLS[backend](flat, assoc.astype(jnp.int32), num_segments)
    if psum_axis is not None:
        # one (M, K)-sized collective combines the per-shard partials —
        # the Eq. 14 "sum over twins on BS i" composed across the mesh
        out = jax.lax.psum(out, psum_axis)
    return out.reshape((num_segments,) + tail)


def segment_count(assoc, num_segments: int, *, backend: str = "auto"
                  ) -> jnp.ndarray:
    """Occupancy histogram: out[m] = #{j : assoc[j] == m}, (M,) fp32.

    The ``K_i`` twins-per-BS count of Eqs. 14-15, through the same dispatch.
    """
    return segment_reduce(jnp.ones(assoc.shape, jnp.float32), assoc,
                          num_segments, backend=backend)


def _segment_extreme(values, assoc, num_segments: int, *, largest: bool,
                     axis_name: str | None) -> jnp.ndarray:
    values = jnp.asarray(values)
    assoc = jnp.asarray(assoc)
    if assoc.ndim != 1:
        raise ValueError(f"assoc must be (N,), got shape {assoc.shape}")
    if values.ndim == 0 or values.shape[0] != assoc.shape[0]:
        raise ValueError(
            f"values leading axis {values.shape} must match assoc "
            f"{assoc.shape}")
    n = assoc.shape[0]
    tail = values.shape[1:]
    fill = jnp.float32(-jnp.inf if largest else jnp.inf)
    if n == 0:
        return jnp.full((num_segments,) + tail, fill, jnp.float32)
    flat = values.astype(jnp.float32).reshape(n, -1)  # (N, K)
    valid = (assoc >= 0) & (assoc < num_segments)
    ids = jnp.where(valid, assoc, 0).astype(jnp.int32)
    flat = jnp.where(valid[:, None], flat, fill)
    op = jax.ops.segment_max if largest else jax.ops.segment_min
    out = op(flat, ids, num_segments=num_segments)
    if axis_name is None:
        axis_name = _active_twin_axis()
    if axis_name is not None:
        out = (jax.lax.pmax if largest else jax.lax.pmin)(out, axis_name)
    return out.reshape((num_segments,) + tail)


def segment_max(values, assoc, num_segments: int, *,
                axis_name: str | None = None) -> jnp.ndarray:
    """Per-segment maximum: out[m] = max_{j: assoc[j]==m} values[j], fp32.

    Out-of-range ids (the twin-axis padding convention) are dropped; empty
    segments return the identity ``-inf`` — callers that need a finite
    default should guard with :func:`segment_count`. Inside an active twin
    scope the per-shard maxima combine with one ``lax.pmax`` (padding rows
    carry ``assoc == M`` so they never contribute), keeping the sharded
    result bit-identical to the single-device one.
    """
    return _segment_extreme(values, assoc, num_segments, largest=True,
                            axis_name=axis_name)


def segment_min(values, assoc, num_segments: int, *,
                axis_name: str | None = None) -> jnp.ndarray:
    """Per-segment minimum; mirror of :func:`segment_max` (identity +inf)."""
    return _segment_extreme(values, assoc, num_segments, largest=False,
                            axis_name=axis_name)


def segment_median(values, assoc, num_segments: int) -> jnp.ndarray:
    """Per-segment median, numpy semantics (middle-two average), fp32.

    Sort-backend by-product like :func:`sort_groups`: one lexicographic sort
    (segment id primary, value secondary) makes every segment a contiguous
    *value-sorted* slice, then two gathers pick the middle elements.
    Out-of-range ids are dropped — the consensus verifier
    (``repro.core.consensus.verify_metas``) routes non-submitters to id M so
    they never move a committee's median. Empty segments return 0.

    Order-statistic, not a sum — there is no sharded combining rule, so this
    is sort-path-only: under an active twin scope the inputs must be
    replicated (M-sized per-BS rows), not twin-sharded.
    """
    v = jnp.asarray(values, jnp.float32)
    a = jnp.asarray(assoc)
    order = jnp.lexsort((v, a))
    sa = jnp.take(a, order)
    sv = jnp.take(v, order)
    # method="compare_all" (dense comparisons, O(n * num_segments)) keeps
    # the boundary search free of lax.scan AND of sorting the constant
    # query — both break the shard_map replication checker when the median
    # feeds a scan carry (the consensus chain under run_consensus_sharded);
    # num_segments is the BS/committee count here, so dense is cheap
    bounds = jnp.searchsorted(sa, jnp.arange(num_segments + 1), side="left",
                              method="compare_all").astype(jnp.int32)
    cnt = bounds[1:] - bounds[:-1]
    c = jnp.maximum(cnt, 1)
    last = v.shape[0] - 1
    lo = jnp.clip(bounds[:-1] + (c - 1) // 2, 0, last)
    hi = jnp.clip(bounds[:-1] + c // 2, 0, last)
    med = 0.5 * (jnp.take(sv, lo) + jnp.take(sv, hi))
    return jnp.where(cnt > 0, med, 0.0)


def segment_std(values, assoc, num_segments: int, *, backend: str = "auto"
                ) -> jnp.ndarray:
    """Per-segment population std (ddof=0) via two moment sums.

    Built on :func:`segment_reduce`, so it inherits the full backend
    dispatch including the sharded psum path — E[x^2] - E[x]^2 composes
    across shards where a direct per-shard ``jnp.std`` would not. Empty
    segments return 0.
    """
    v = jnp.asarray(values).astype(jnp.float32)
    s1 = segment_reduce(v, assoc, num_segments, backend=backend)
    s2 = segment_reduce(v * v, assoc, num_segments, backend=backend)
    cnt = segment_count(assoc, num_segments, backend=backend)
    cnt = cnt.reshape((num_segments,) + (1,) * (s1.ndim - 1))
    c = jnp.maximum(cnt, 1.0)
    mean = s1 / c
    return jnp.sqrt(jnp.maximum(s2 / c - mean * mean, 0.0))
