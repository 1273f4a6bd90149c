"""Published peaks per chip, keyed by JAX's ``device_kind``.

A device that is not in the table is an error, never a default: a share
of a peak that nobody published for this chip is not a measurement.
"""
from __future__ import annotations

#: Google Cloud documentation, "TPU v5e" (system architecture): per chip
#: 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2 at 819 GB/s.
PEAKS: dict[str, dict[str, float | str]] = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": 'Google Cloud documentation, "TPU v5e"',
    },
}

#: the peak that bounds each kind of arithmetic a configuration states
ARITHMETIC_PEAK = {"int8": "int8_ops", "bf16": "bf16_flops"}


class UnknownDevice(LookupError):
    """The device kind has no published peaks in :data:`PEAKS`."""


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peaks for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}") from None


def compute_peak_from(table: dict, arithmetic: str) -> float:
    """Operations per second at ``arithmetic`` ('int8' or 'bf16') in one
    device's row of the table."""
    return float(table[ARITHMETIC_PEAK[arithmetic]])
