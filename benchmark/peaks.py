"""Published peaks of one chip, keyed by jax's exact ``device_kind``.

A device that is not in the table is an error, not a default: a utilization
against a guessed peak is not a measurement.
"""

from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s
    # int8, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip
    # interconnect.
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bytes_per_s": 200e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peak(device_kind: str, what: str) -> float:
    """The published peak ``what`` of ``device_kind``; raises for a device
    or a quantity the table does not hold."""
    try:
        return PEAKS[device_kind][what]
    except KeyError:
        raise KeyError(
            f"no published peak {what!r} for device kind {device_kind!r}; "
            f"known kinds: {sorted(PEAKS)}") from None
