"""Published peaks, keyed by ``device_kind``. A device that is not in the
table is an error, not a default.

Source: Google Cloud documentation, "TPU v5e" (system architecture): one
chip gives 197 TFLOP/s in bfloat16, 393 TOP/s in int8, 16 GB of HBM2e at
819 GB/s. The bf16 figure is the one ``utils/metrics.PEAK_BF16_FLOPS``
holds; the HBM figure is added here.
"""

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
    "TPU v5e": {"bf16_flops": 197e12, "int8_ops": 393e12,
                "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peaks_of(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks on record for device kind "
                       f"{device_kind!r}; add it to perfbench/lib/peaks.py "
                       f"with its source")
    return PEAKS[device_kind]
