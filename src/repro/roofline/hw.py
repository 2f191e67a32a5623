"""TPU v5e hardware constants (the roofline denominators)."""

PEAK_FLOPS_BF16 = 197e12       # per chip, bf16
HBM_BW = 819e9                 # bytes/s per chip

DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4,
    "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
    "c64": 8, "c128": 16,
}
