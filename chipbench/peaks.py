"""Published peaks of each chip, keyed by ``device.device_kind``.

A device that is not in the table is an error, never a default: a roofline
share or an mfu read against the wrong peaks is a wrong number.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_s": 197e12,
        "hbm_bytes_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud TPU documentation, 'TPU v5e': 197 TFLOP/s "
                  "bf16, 819 GB/s HBM bandwidth, 16 GB HBM per chip",
    },
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peaks for device kind {device_kind!r}: add its row, with "
            f"its source, to chipbench/peaks.py (have {sorted(PEAKS)})"
        ) from None
