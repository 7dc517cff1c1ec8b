"""Published peaks of the chips the benchmark may run on, by ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 819 GB/s,
16 GB HBM per chip). A device that is not in the table is an error."""

PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
    "TPU v5e": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                "hbm_bytes": 16e9},
}


def peaks_for(device_kind: str):
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}") from None
