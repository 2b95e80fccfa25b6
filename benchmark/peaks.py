"""Published peaks of the chips the benchmark may run on, keyed by JAX's
``device_kind``.  A kind that is not listed is an error, never a default."""

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of HBM
    # at 819 GB/s per chip.
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "Google Cloud documentation, 'TPU v5e'"},
}


def lookup(device_kind):
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks on record for device kind "
                       f"{device_kind!r} (known: {sorted(PEAKS)})")
    return PEAKS[device_kind]
