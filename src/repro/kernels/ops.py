"""Jitted public wrappers around the Pallas kernels.

Off the chip the kernels execute with interpret=True; on a TPU they compile
natively (``segment_reduce.default_interpret``: ``REPRO_PALLAS_INTERPRET``
overrides the choice off the chip only).
"""
from __future__ import annotations

import functools

import jax

from repro.kernels import fedavg_reduce as _fr
from repro.kernels import flash_attention as _fa
from repro.kernels import ssd_scan as _ssd
# note: `from repro.kernels import segment_reduce` would grab the FUNCTION
# re-exported by the package __init__, not the submodule — import directly.
from repro.kernels.segment_reduce import default_interpret as _sr_interpret
from repro.kernels.segment_reduce import segment_reduce as _sr_dispatch


def _interpret() -> bool:
    return _sr_interpret()


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "logit_softcap", "q_offset", "scale", "block_q",
    "block_k"))
def flash_attention(q, k, v, *, causal=True, window=0, logit_softcap=None,
                    q_offset=0, scale=None, block_q=128, block_k=128):
    return _fa.flash_attention(
        q, k, v, causal=causal, window=window, logit_softcap=logit_softcap,
        q_offset=q_offset, scale=scale, block_q=block_q, block_k=block_k,
        interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd_scan(x, dt, A, Bm, Cm, *, chunk=128):
    return _ssd.ssd_scan(x, dt, A, Bm, Cm, chunk=chunk, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("block",))
def fedavg_reduce(stacked, weights, *, block=65536):
    return _fr.fedavg_reduce(stacked, weights, block=block,
                             interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("num_segments", "backend"))
def segment_reduce(values, assoc, num_segments, *, backend="auto"):
    """Jitted standalone entry to the segment-reduction dispatch (callers
    already inside jit should import repro.kernels.segment_reduce directly).
    ``interpret`` is left to the dispatch: non-TPU platforms run the pallas
    backend's XLA tiled lowering, not the interpreter."""
    return _sr_dispatch(values, assoc, num_segments, backend=backend)
