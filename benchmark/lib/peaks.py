"""Published peaks of one chip, keyed by the exact ``device_kind`` string
jax reports.  A device that is not in the table is an error, never a
default: a roofline share over a guessed peak is not a measurement.

Source for "TPU v5 lite": Google Cloud documentation, "TPU v5e" system
architecture page (per chip: 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e
at 819 GB/s, 1,600 Gbit/s inter-chip interconnect).
"""

from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bits_per_s": 1600e9,
    },
}

SOURCE = 'Google Cloud documentation, "TPU v5e" (system architecture)'


def peaks_for(device_kind: str) -> Dict[str, float]:
    if device_kind not in PEAKS:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}: add a row "
            "to benchmark/lib/peaks.py with its source")
    return PEAKS[device_kind]
