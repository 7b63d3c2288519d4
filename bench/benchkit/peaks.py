"""Published peaks of each accelerator the benchmark may run on.

Keyed by ``jax.Device.device_kind``.  A device that is not in the table is
an error: a roofline share or an MFU against a guessed peak means nothing.
"""
from __future__ import annotations

from typing import Dict, NamedTuple


class Peaks(NamedTuple):
    bf16_flops: float     # FLOP/s per chip, bf16 matrix units
    hbm_bytes_s: float    # HBM bandwidth per chip, bytes/s
    ici_bytes_s: float    # chip-to-chip interconnect per chip, bytes/s
    hbm_bytes: float      # HBM capacity per chip
    source: str


PEAKS: Dict[str, Peaks] = {
    "TPU v5 lite": Peaks(
        bf16_flops=197e12, hbm_bytes_s=819e9, ici_bytes_s=1600e9 / 8,
        hbm_bytes=16e9, source="Google Cloud, TPU v5e"),
}


def peaks_for(device_kind: str) -> Peaks:
    """The peaks of ``device_kind``; raises ``KeyError`` for an unknown chip."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None
