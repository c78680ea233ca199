"""Published peaks of one chip, keyed by ``jax.Device.device_kind``.

A device with no row has no peak: :func:`peaks_for` raises and the
runner exits non-zero before any work.  There is no default row."""

#: Google Cloud documentation, "TPU v5e" (System architecture): 197
#: TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s per chip.  The
#: chip reports itself to JAX as ``TPU v5 lite``.
PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks_for(device_kind):
    """The row of ``device_kind`` or KeyError naming the table."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            "benchmarks/peaks.py has no row for device_kind %r (rows: %s)"
            % (device_kind, sorted(PEAKS))) from None
