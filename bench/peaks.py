"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

A device kind that is not in the table is an error: a roofline share
against a guessed peak is no measurement.
"""

from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e" (system architecture), per chip.
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud TPU documentation, TPU v5e: 197 TFLOP/s "
                  "bf16, 393 TOP/s int8, 16 GB HBM2 at 819 GB/s per chip",
    },
}


class UnknownDevice(LookupError):
    pass


def for_kind(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(f"no published peaks for device kind "
                            f"{device_kind!r}; have {sorted(PEAKS)}") from None
