"""Published peaks of one chip, keyed by the exact `device_kind` JAX reports.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 819 GB/s HBM, 16 GB HBM per chip. A device that is not in
the table is an error, never a default.
"""

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9, "source": "Google Cloud, TPU v5e"},
}


def peaks_for(device_kind):
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise SystemExit(
            f"benchmarks: no published peaks for device kind "
            f"{device_kind!r}; add it to harness/peaks.py with its source")
