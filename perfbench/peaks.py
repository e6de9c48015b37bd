"""Published peaks of one chip, keyed by ``Device.device_kind``.

A kind with no row is an error, never a default: a share computed against
another chip's peaks would be silently wrong.
"""
from __future__ import annotations

from typing import NamedTuple


class Peak(NamedTuple):
    flops_bf16: float   # FLOP/s
    hbm_bw: float       # bytes/s
    hbm_bytes: float    # capacity


PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of
    # HBM at 819 GB/s
    "TPU v5 lite": Peak(flops_bf16=197e12, hbm_bw=819e9, hbm_bytes=16e9),
}


def peak_for(device_kind: str) -> Peak:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peak row for device kind {device_kind!r}; "
                       f"have {sorted(PEAKS)}") from None
