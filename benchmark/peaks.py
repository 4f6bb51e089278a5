"""Published peaks of the chips this benchmark has run on, by the
``device_kind`` jax reports.  A kind that is not here is an error, never
a default: add its row, with its source, when it is first measured."""

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of
    # HBM at 819 GB/s.  jax reports the chip as "TPU v5 lite".
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def lookup(device_kind):
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise SystemExit(
            f"benchmark: no peak rates for device_kind {device_kind!r} "
            f"(benchmark/peaks.py knows {sorted(PEAKS)}): add its row "
            f"with the source") from None
