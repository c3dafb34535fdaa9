"""Published per-chip peaks, keyed by JAX's ``device_kind``.

One table for every number priced against the hardware: the dry-run
roofline analysis (`launch.roofline`) and the measured-bandwidth benchmark
(`benchmarks/vm_stream.py`) read their denominators here, and
``chip_smoke.py`` looks up the running chip's entry. A device kind missing
from the table is an error, never a default: add its row, with its source,
before pricing anything against it.
"""
from __future__ import annotations

import dataclasses
from typing import Dict


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    """Published peaks of one chip."""

    flops_bf16: float        # FLOP/s, bf16 matrix units
    hbm_bw: float            # bytes/s
    hbm_bytes: int           # device memory capacity
    ici_bw: float            # bytes/s per interconnect link
    source: str


#: ``jax.devices()[i].device_kind`` -> published peaks
PEAKS: Dict[str, ChipPeaks] = {
    # 1,600 Gbit/s of chip-to-chip interconnect over 4 links = 50 GB/s/link
    "TPU v5 lite": ChipPeaks(
        flops_bf16=197e12, hbm_bw=819e9, hbm_bytes=16 * 10**9,
        ici_bw=50e9,
        source="Google Cloud documentation, 'TPU v5e' system architecture"),
}

#: the chip the production meshes of the dry run describe
V5E = "TPU v5 lite"


def peaks(device_kind: str) -> ChipPeaks:
    """The published peaks of ``device_kind``; raises if it has no entry."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peak entry for device kind {device_kind!r}; add it to "
            f"repro.hw.PEAKS with its source (known: {sorted(PEAKS)})"
        ) from None
