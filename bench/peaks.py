"""Published peaks of one chip, keyed by ``device_kind`` as JAX reports it.

Source: Google Cloud documentation, "TPU v5e" (per chip: 197 TFLOP/s
bf16, 394 TOP/s int8, 16 GB HBM at 819 GB/s).  Copied from the program's
``repro/roofline.py`` so that the yardstick cannot move with the program.
A device that is not in the table is an error, never a default.
"""
from __future__ import annotations

CHIP_PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 394e12,
        "hbm_bytes": 16e9,
        "hbm_bw": 819e9,
    },
}


def chip_peaks(device_kind: str) -> dict:
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device_kind "
                         f"{device_kind!r}; known: {sorted(CHIP_PEAKS)}") from None
