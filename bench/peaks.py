"""Published peaks of each accelerator the benchmark runs on, keyed by the
`device_kind` JAX reports.  A device not in the table is an error.

Source: Google Cloud documentation, "TPU v5e" (per chip: 197 TFLOP/s
bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s)."""

PEAKS = {
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819e9,
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes": 16e9,
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise SystemExit(f"bench: no peaks for device kind {device_kind!r}; "
                         f"known: {sorted(PEAKS)}") from None
