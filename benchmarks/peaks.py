"""Published peaks of the chips the benchmark may run on, keyed by
``jax.devices()[0].device_kind``.  One table; a device that is not in
it is an error, never a default."""

#: Google Cloud documentation, "TPU v5e" (system architecture page): one
#: chip has 197 TFLOP/s in bf16, 393 TOP/s in int8, 16 GB of HBM2e at
#: 819 GB/s and 1,600 Gbit/s of chip-to-chip interconnect.
PEAKS = {
    "TPU v5 lite": {
        "flops_per_s": {"bfloat16": 197e12, "int8": 393e12},
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bits_per_s": 1600e9,
        "source": "cloud.google.com/tpu/docs/v5e (TPU v5e, per chip)",
    },
}


def peaks_for(device_kind):
    """The peaks of ``device_kind``; ``KeyError`` naming the table's
    keys for a device that has no published row here."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            "no published peaks for device kind {0!r}; the table holds "
            "{1}".format(device_kind, sorted(PEAKS))
        ) from None
