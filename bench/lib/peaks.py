"""Published peaks of the chips the benchmark runs on, keyed by the
`device_kind` JAX reports.  A kind that is not here is an error, never a
default: a roofline share against the wrong chip is a wrong number.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
per chip 197 TFLOP/s bf16, 394 TOP/s int8, 16 GB HBM2 at 819 GB/s,
1,600 Gbit/s of inter-chip interconnect."""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 394e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bits_per_s": 1600e9,
    },
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
