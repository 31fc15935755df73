"""Peaks of the chips this benchmark knows, keyed by ``device_kind`` as JAX
reports it. Source: Google Cloud documentation, "TPU v5e" system architecture
(197 TFLOP/s bf16, 819 GB/s HBM, 16 GB HBM per chip). A device that is not in
the table is an error, never a default."""

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_s": 819e9,
                    "hbm_bytes": 16 * 2 ** 30,
                    "source": "cloud.google.com/tpu/docs/v5e"},
    "TPU v5e": {"bf16_flops": 197e12, "hbm_bytes_s": 819e9,
                "hbm_bytes": 16 * 2 ** 30,
                "source": "cloud.google.com/tpu/docs/v5e"},
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peak numbers for device kind {device_kind!r}; add a row to "
            "benchmark/lib/peaks.py with its source") from None
