"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` jax reports. The benchmark's own copy: later PRs may change
the program's table (``accelerator/tpu_accelerator.py``), not this yardstick.
A device that is not listed is an error, never a default."""

PEAKS = {
    # Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/v5e):
    # 197 TFLOP/s bf16 and 393 TOP/s int8 per chip, 16 GB of HBM2e at
    # 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect.
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bits_per_s": 1600e9,
    },
}


def peak(device_kind: str, what: str) -> float:
    """The peak ``what`` of one chip of ``device_kind``; raises ``KeyError``
    on a device or a quantity the table does not hold."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind][what]
