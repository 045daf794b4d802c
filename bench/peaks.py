"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

Copied from ``repro.roofline.analysis.DEVICE_PEAKS``; the numbers are Google
Cloud's published TPU v5e figures.  A device kind that is not here is an
error, never a default.
"""
from __future__ import annotations

_V5E = {
    "flops": 197e12,     # bf16 FLOP/s per chip
    "hbm_bw": 819e9,     # HBM bytes/s per chip
    "link_bw": 50e9,     # bytes/s per ICI link (1,600 Gbit/s over 4 links)
    "hbm_bytes": 16e9,
    "source": 'Google Cloud documentation, "TPU v5e"',
}

DEVICE_PEAKS = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}


def device_peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; unknown kinds raise."""
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; known: "
            f"{sorted(DEVICE_PEAKS)}") from None
