"""Published peaks per chip, keyed by ``device_kind`` as JAX reports it.

A device that is not in the table is an error, not a default: a roofline
share against a guessed peak is a guess.
"""

from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e" system architecture page:
    # 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s,
    # 1,600 Gbit/s inter-chip interconnect per chip.
    "TPU v5 lite": {
        "flops_per_s_bf16": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bits_per_s": 1600e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"device kind {device_kind!r} is not in the "
                       f"benchmark's peaks table ({sorted(PEAKS)})")
    return PEAKS[device_kind]
