"""Published per-chip peaks, keyed by `jax.Device.device_kind`.

A device that is not in the table is an error, not a default: a roofline
share computed against another chip's peaks is a wrong number.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops_bf16: float      # FLOP/s, bf16 matmul
    hbm_bw: float          # bytes/s
    hbm_bytes: int         # device memory
    ici_bw: float          # bytes/s of chip-to-chip interconnect, all links
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(
        flops_bf16=197e12,
        hbm_bw=819e9,
        hbm_bytes=16 * 1024 ** 3,
        ici_bw=1600e9 / 8,             # 1,600 Gbit/s per chip
        source="Google Cloud documentation, \"TPU v5e\" (system "
               "architecture): 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s, "
               "1,600 Gbit/s ICI per chip"),
}


def peaks(device_kind: str) -> Peaks:
    """The published peaks of `device_kind`; raises for an unknown chip."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r} "
            f"(known: {sorted(PEAKS)})") from None
