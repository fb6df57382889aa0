"""Published peak rates of one chip, keyed by ``jax.Device.device_kind``.

TPU v5e ("TPU v5 lite"): Google Cloud documentation, "TPU v5e" --
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s
of chip-to-chip interconnect.  A copy of the program's
``analysis/roofline.PEAKS``, kept here so that the yardstick does not
move when the program does.  A kind missing here is an error.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class ChipPeaks:
    bf16_flops: float          # per second
    hbm_bw: float              # bytes per second
    hbm_bytes: float


PEAKS = {
    "TPU v5 lite": ChipPeaks(bf16_flops=197e12, hbm_bw=819e9,
                             hbm_bytes=16e9),
}


def peaks(device_kind: str) -> ChipPeaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}") from None
