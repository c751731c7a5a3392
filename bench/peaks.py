"""Per-chip peaks keyed by `jax.devices()[0].device_kind`.

Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16,
819 GB/s HBM, 1,600 Gbit/s inter-chip interconnect over 4 links = 50 GB/s
per link). A copy of the program's table (`benchmarks/roofline.PEAKS`), so
that the yardstick cannot move with the program. A device that is not in
the table is an error, never a default.
"""
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no peak table for device_kind {device_kind!r}; "
                         f"known: {sorted(PEAKS)}") from None
