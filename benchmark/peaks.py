"""Published peaks of one chip, keyed by ``device_kind``. A kind that is not
here is an error, never a default.

TPU v5e: Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/v5e),
system architecture table: 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at
819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect per chip.
"""

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
                    "ici_bits_per_s": 1600e9},
}
PEAKS["TPU v5e"] = PEAKS["TPU v5 lite"]


def for_kind(kind: str) -> dict:
    if kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {kind!r}: add it to benchmark/peaks.py with its source")
    return PEAKS[kind]
