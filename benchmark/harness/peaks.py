"""Published peaks of one chip, keyed by ``jax.devices()[0].device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s in bf16, 16 GB of HBM2e at 819 GB/s, 1,600 Gbit/s of
chip-to-chip interconnect.  The table was copied from ``bench.py``'s
``_TPU_PEAK_TFLOPS`` (the v5e row; the other rows had no chip to be
checked on) and extended by the bytes.  A kind that is not here is an
error, not a default: add it with its source.
"""

PEAKS = {
    # device_kind: the v5e reports "TPU v5 lite" (my chip runs, PR 22/23)
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9, "ici_bits_per_s": 1600e9},
    "TPU v5e": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                "hbm_bytes": 16e9, "ici_bits_per_s": 1600e9},
}


def peaks_for(device_kind):
    """The row of `device_kind`; KeyError names the kind when unknown."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError("benchmark/harness/peaks.py: no peaks known for "
                       "device_kind %r; add it with its source"
                       % (device_kind,)) from None
