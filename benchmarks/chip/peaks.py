"""Published per-chip peak rates, keyed by ``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e" (Cloud TPU system
architecture, v5e page): "197 TFLOPs (bf16), 393 TOPs (int8), 16 GiB
HBM2 at 819 GBps, 1,600 Gbps interchip interconnect".  A kind that is
not in the table is an error, never a default: a roofline computed
against the wrong chip is worse than none.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float        # FLOP/s
    int8_ops: float          # OP/s
    hbm_bw: float            # B/s
    ici_bw_per_link: float   # B/s (4 links a chip)

    def rate(self, name: str) -> float:
        """The peak a configuration names: ``bf16_flops`` or ``int8_ops``."""
        if name not in ("bf16_flops", "int8_ops"):
            raise ValueError(f"unknown peak {name!r}")
        return getattr(self, name)


PEAKS = {
    "TPU v5 lite": Peaks(bf16_flops=197e12, int8_ops=393e12,
                         hbm_bw=819e9, ici_bw_per_link=50e9),
}


def peaks(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)} (add the chip with its source)") from None
