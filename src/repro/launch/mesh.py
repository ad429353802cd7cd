"""Production mesh construction and per-chip roofline peaks.

Single pod : (16, 16)      axes ("data", "model")   — 256 chips (v5e pod)
Multi-pod  : (2, 16, 16)   axes ("pod", "data", "model") — 512 chips

FUNCTIONS (not module-level constants) so importing this module never
touches jax device state; the dry-run sets XLA_FLAGS before first jax use.

Every mesh here is built with ``AxisType.Auto`` axes: the repo's sharded
code is written against jit-with-shardings + ``jax.shard_map`` regions, the
Auto-axis model. ``jax.make_mesh`` defaults to Explicit axes, under which a
plain ``jit`` over a twin-sharded array asks for a ``jax.set_mesh`` context.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

# Per-chip peaks for the roofline model, keyed by ``jax.Device.device_kind``.
# Source: Google Cloud TPU documentation, "TPU v5e" system architecture
# page — 197 TFLOP/s bf16, 819 GB/s HBM2, 1,600 Gbps ICI per chip over four
# links (= 50 GB/s per link).
CHIP_PEAKS = {
    "TPU v5 lite": {"peak_flops_bf16": 197e12, "hbm_bw": 819e9,
                    "ici_bw_per_link": 50e9},
}

# The chip the production meshes above model.
PRODUCTION_DEVICE_KIND = "TPU v5 lite"


def chip_peaks(device_kind: str) -> dict:
    """Roofline peaks of one chip of ``device_kind`` (see ``CHIP_PEAKS``).
    An unknown kind is an error — a roofline share against another chip's
    peaks is a wrong number, not an approximate one."""
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no roofline peaks recorded for device kind {device_kind!r}; "
            f"known kinds: {sorted(CHIP_PEAKS)}") from None


def _mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_debug_mesh(n_devices: int | None = None, *, multi_pod: bool = False):
    """Small mesh over whatever devices exist (tests use 8 host devices)."""
    n = n_devices or len(jax.devices())
    if multi_pod:
        assert n % 2 == 0
        return _mesh((2, n // 4, 2), ("pod", "data", "model"))
    return _mesh((n // 2, 2), ("data", "model"))


def make_twin_mesh(n_shards: int | None = None):
    """1-D mesh over the twin axis of the DTWN simulation core.

    The simulation's only large axis is the twin population (N up to 10^6),
    so its mesh is one-dimensional with the single axis name ``"twin"`` —
    the axis name ``repro.core.sharding`` binds for its ``psum`` composition
    of per-BS segment reductions. Defaults to all visible devices; tests and
    CI force 8 host devices via ``--xla_force_host_platform_device_count``.
    """
    n = n_shards or len(jax.devices())
    return _mesh((n,), ("twin",))
