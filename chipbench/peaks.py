"""Published peaks of the chips the benchmark runs on, by ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/v5e):
197 TFLOP/s bf16, 394 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI
per chip.  A reader of a share of a peak refuses a device that is not in
the table; it never takes a default.
"""

PEAKS: dict[str, dict[str, float]] = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 394e12,
        "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9,
        "ici_bits_per_s": 1.6e12,
    },
}
