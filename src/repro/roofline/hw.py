"""Per-chip peaks for the roofline terms, keyed by ``Device.device_kind``."""
from __future__ import annotations

from typing import NamedTuple


class HwSpec(NamedTuple):
    name: str
    peak_flops_bf16: float     # FLOP/s per chip
    hbm_bw: float              # bytes/s per chip
    ici_bw_per_link: float     # bytes/s per link
    ici_links: int             # links per chip participating in a collective
    hbm_bytes: float           # capacity per chip


PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of
    # HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect
    "TPU v5 lite": HwSpec(
        name="tpu-v5e",
        peak_flops_bf16=197e12,
        hbm_bw=819e9,
        ici_bw_per_link=50e9,
        ici_links=1,           # conservative: one active link per chip
        hbm_bytes=16e9,
    ),
}

# the AOT dry-run's named target (it compiles for a chip it does not run on)
TPU_V5E = PEAKS["TPU v5 lite"]


def peaks_for(device_kind: str) -> HwSpec:
    """Peak row of the device JAX reports as ``device_kind``.  A kind with
    no row is an error, never a default: a roofline share computed against
    another chip's peaks would be silently wrong."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peak row for device kind {device_kind!r}; "
                       f"have {sorted(PEAKS)}") from None
