"""Published peaks of one chip, keyed by jax's `device_kind`. A device
that is not here is an error, never a default: a share of some other
chip's peak means nothing. (Copied from `bench.DEVICE_PEAKS`.)

Source: Google Cloud documentation, "TPU v5e" (system architecture
page): 197 TFLOP/s in bf16, 16 GB of HBM at 819 GB/s, one chip."""

DEVICE_PEAKS = {
    "TPU v5 lite": {"flops": 1.97e14, "bytes_per_s": 8.19e11,
                    "hbm_bytes": 16e9},
}


def peaks_for(device_kind):
    if device_kind not in DEVICE_PEAKS:
        raise RuntimeError(
            f"no published peaks for device kind {device_kind!r} in "
            f"benchmark.peaks.DEVICE_PEAKS (known: {sorted(DEVICE_PEAKS)}); "
            "the benchmark measures a chip and does not fall back")
    return DEVICE_PEAKS[device_kind]
