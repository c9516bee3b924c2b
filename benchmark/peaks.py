"""Published peaks of one chip, keyed by the ``device_kind`` JAX reports.
A device that is not here is an error, not a default.

Source: Google Cloud documentation, "TPU v5e" system architecture: 197
TFLOP/s (bf16), 393 TOP/s (int8), 16 GB of HBM2e at 819 GB/s. No peak
for 32-bit integer vector work is published, so a kernel made of such
work is held to the memory peak alone (see kernel_cost.py).
"""

PEAKS = {
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "bf16_flop_per_s": 197e12,
                    "int8_op_per_s": 393e12, "hbm_bytes": 16e9},
}


def peak(device_kind: str, what: str) -> float:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}")
    return PEAKS[device_kind][what]
