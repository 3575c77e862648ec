"""Published per-chip peak rates, keyed by ``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture
page): 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s, and
1,600 Gbit/s of inter-chip interconnect per chip over four links
(50 GB/s each).  A kind that is not in the table is an error, never a
default: a roofline computed against the wrong chip is worse than none.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float        # FLOP/s
    hbm_bw: float            # B/s
    ici_bw_per_link: float   # B/s


# the chip the production-mesh dry-runs project onto
V5E = "TPU v5 lite"

PEAKS = {
    V5E: Peaks(bf16_flops=197e12, hbm_bw=819e9, ici_bw_per_link=50e9),
}


def peaks(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)} (add the chip with its source)") from None
