"""Published peaks of the chips the benchmark may run on, keyed by jax's
``device_kind``.  Copied from ``paddle_tpu/observability/costmodel.py``
(PROFILES) so that no PR to the program can move the yardstick.

Source: Google Cloud documentation, "TPU v5e" system architecture: 197
TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s per chip, 1,600 Gbit/s
(200 GB/s) of inter-chip interconnect per chip.
"""

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_s": 819e9, "ici_bytes_s": 200e9,
                    "hbm_bytes": 16e9},
}


def peaks_for(device_kind):
    """The peaks of one device kind; an unknown kind is an error, never a
    default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)} (add it to benchmark/harness/peaks.py with "
            f"its source)") from None
