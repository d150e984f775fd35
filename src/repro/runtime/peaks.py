"""Published per-chip peaks, keyed by JAX's ``device_kind``.

The one table of accelerator peaks in the repository.  Every roofline
bound, cost-model default and benchmark projection reads it through
:func:`device_peaks`; a device kind that is not listed is an error, not
an assumed profile.  Source: Google Cloud documentation, "TPU v5e"
(system architecture: per-chip specifications).  One entry,
``vpu_flops_f32``, is MODELLED, not published: the elementwise f32 rate
of the vector unit, which the extraction kernels (mostly VPU work) are
priced at until a measured calibration replaces it.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "name": "v5e",
        "peak_flops_bf16": 197e12,  # FLOP/s (MXU)
        "peak_ops_int8": 393e12,    # OP/s
        "hbm_bytes": 16e9,          # B
        "hbm_bw": 819e9,            # B/s
        "ici_bw": 1600e9 / 8,       # B/s per chip (1,600 Gbit/s)
        "vpu_flops_f32": 7e12,      # FLOP/s, MODELLED (no published figure)
        "source": 'Google Cloud documentation, "TPU v5e"',
    },
}

V5E = PEAKS["TPU v5 lite"]


def device_peaks(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind`` (e.g. "TPU v5 lite").

    Raises ``KeyError`` for a kind with no published entry.
    """
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)} (add the chip to runtime/peaks.PEAKS with "
            "its source)"
        ) from None
