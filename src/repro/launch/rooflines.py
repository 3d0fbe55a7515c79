"""Peak rates per accelerator, keyed by ``device_kind``, with their source.

Side-effect-free home for the machine model: ``launch.dryrun`` (which MUST
set XLA_FLAGS before jax initializes and therefore cannot be imported
without consequences) and ``launch.perf`` consume the reference machine's
peaks for the compile-time roofline terms, and
``diffusion.tiers.roofline_tier_bw`` calibrates the DES's modeled tier
bandwidths from the same numbers.  A measurement taken on a real device
looks its peaks up by that device's ``device_kind`` (``device_peaks``); a
kind missing from the table is an error, never a silent default.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict


@dataclass(frozen=True)
class DevicePeaks:
    kind: str                   # jax ``Device.device_kind``
    source: str
    flops_bf16: float           # FLOP/s
    hbm_bw: float               # bytes/s
    ici_bw: float               # bytes/s per chip-to-chip link
    host_link_bw: float         # bytes/s host DRAM <-> device (one direction)


PEAKS: Dict[str, DevicePeaks] = {
    "TPU v5 lite": DevicePeaks(
        kind="TPU v5 lite",
        source=("Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
                "16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI over 4 links.  The "
                "page gives no host link; 32 GB/s is the PCIe Gen4 x16 "
                "nominal, an assumed upper bound"),
        flops_bf16=197e12,
        hbm_bw=819e9,
        ici_bw=1600e9 / 8 / 4,
        host_link_bw=32e9,
    ),
}


def device_peaks(kind: str) -> DevicePeaks:
    """Peaks of the device kind a measurement ran on; unknown kinds raise."""
    try:
        return PEAKS[kind]
    except KeyError:
        raise ValueError(f"no peak rates for device_kind {kind!r}; add it to "
                         f"launch.rooflines.PEAKS with its source") from None


# Reference machine of the dry-run / perf model and the DES tier calibration.
REFERENCE = PEAKS["TPU v5 lite"]
PEAK_FLOPS = REFERENCE.flops_bf16
HBM_BW = REFERENCE.hbm_bw
ICI_BW = REFERENCE.ici_bw
# Local-disk class for the KV spill tier: pinned at 1/25 of the interconnect
# (the nominal 2 GB/s NVMe read at the reference 50 GB/s link), the same
# ratio ``diffusion.tiers.roofline_tier_bw`` has always used.  A host-side
# class, not a device peak, so it is not in the table.
DISK_BW = ICI_BW / 25.0     # bytes/s

__all__ = ["DevicePeaks", "PEAKS", "device_peaks", "REFERENCE",
           "PEAK_FLOPS", "HBM_BW", "ICI_BW", "DISK_BW"]
