"""Published peaks of each accelerator the benchmark may run on, keyed by
JAX's ``device_kind``.  A kind that is not here is an error, never a default.

Source of the TPU v5e row: Google Cloud documentation, "TPU v5e"
(https://cloud.google.com/tpu/docs/v5e): 197 TFLOP/s bf16, 393 TOP/s int8,
16 GB of HBM at 819 GB/s per chip.
"""

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9,
    },
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks known for device kind {device_kind!r}") from None
