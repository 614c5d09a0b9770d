"""Published peaks of the chips this benchmark may run on, keyed by the
``device_kind`` jax reports. A device that is not here is an error, never a
default: a utilization against a guessed peak is not a measurement.

Source of the v5e row: Google Cloud documentation, "TPU v5e" system
architecture page (197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at
819 GB/s per chip, 1600 Gbit/s inter-chip interconnect per chip).
"""

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bits_per_s": 1600e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks_for(device_kind):
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}; add a row "
            f"with its source to benchmark/peaks.py (known: {sorted(PEAKS)})"
        ) from None
