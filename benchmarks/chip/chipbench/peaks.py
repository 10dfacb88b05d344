"""Published peaks of each accelerator, keyed by JAX's ``device_kind``.

TPU v5e (``device_kind`` "TPU v5 lite"): 197 TFLOP/s in bf16, 819 GB/s of
HBM bandwidth and 16 GB of HBM per chip, from Google Cloud's documentation
page "TPU v5e" (system architecture, chip specifications). The served GEMMs
and attention run on the MXU in bf16 (FP4 weights and FP8 activations are
decoded to bf16 in VMEM), so bf16 is the compute peak every share uses.
A kind that is not in the table is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
