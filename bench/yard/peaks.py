"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2 at 819 GB/s, 1,600 Gbit/s
of inter-chip interconnect per chip. A device that is not in the table is
an error: the benchmark never guesses a peak.
"""

from __future__ import annotations

from typing import Dict

SOURCE = "Google Cloud documentation, 'TPU v5e' (cloud.google.com/tpu/docs/v5e)"

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {
        "flops_bf16": 197e12,      # FLOP/s
        "hbm_bytes_per_s": 819e9,  # bytes/s
        "hbm_bytes": 16e9,         # bytes
        "ici_bits_per_s": 1600e9,  # bits/s per chip
    },
}


def peaks_for(device_kind: str) -> Dict[str, float]:
    """The peaks of ``device_kind``; raises ``KeyError`` for any other."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"(known: {sorted(PEAKS)})") from None
