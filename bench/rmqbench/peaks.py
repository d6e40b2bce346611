"""Published peaks per chip, keyed by JAX's ``device_kind``.

A device kind that is not in the table is an error, never a default.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819e9,
        "bf16_flops_per_s": 197e12,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e': 819 GB/s HBM, 197 TFLOP/s bf16, 16 GB HBM per chip",
    },
}


def peak_row(device_kind: str) -> dict:
    """The table's row for ``device_kind``; raises on an unknown kind."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no peaks for device kind {device_kind!r}; have {sorted(PEAKS)}") from None
