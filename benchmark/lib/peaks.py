"""Published peaks per chip, keyed by ``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture
table): 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s. A
device kind that is not in the table is an error, never a default."""

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise RuntimeError(
            f"no published peaks recorded for device kind {device_kind!r}; "
            "add it to benchmark/lib/peaks.py with its source")
    return PEAKS[device_kind]
