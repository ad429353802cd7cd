"""Peaks of one chip, keyed by ``jax.Device.device_kind``.

Source: Google Cloud TPU documentation, "TPU v5e": 197 TFLOP/s bf16,
393 TOP/s int8, 16 GB HBM at 819 GB/s per chip. A kind that is not here is
an error, never a default.
"""
PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no peaks recorded for device kind {device_kind!r}; "
                         f"known: {sorted(PEAKS)}") from None
