"""Published peaks of the chips the benchmark may run on, keyed by
``device_kind`` as JAX reports it.

Source: Google Cloud documentation, "TPU v5e" system architecture page
(197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s per chip).
A device that is not in the table is an error, never a default.
"""

PEAKS = {
    "TPU v5 lite": {
        "int8_ops_per_s": 393e12,
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud, TPU v5e: 393 TOP/s int8, 197 TFLOP/s bf16, "
                  "819 GB/s, 16 GB",
    },
}


def peaks_for(device_kind):
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device_kind {device_kind!r}; add it to "
                       "benchmark/peaks.py with its source") from None
