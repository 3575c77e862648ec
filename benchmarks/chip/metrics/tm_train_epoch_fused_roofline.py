"""The TM epoch kernel's share of its roofline: the least time the chip
needs for a round's local epochs (``counts.tm_train_ops`` at the int8
peak, ``counts.tm_train_bytes`` at the HBM bandwidth, whichever is
larger) over the summed device time of the kernel's events."""
import counts
import peaks

KERNEL = "tm_train_epoch_fused"


def read(rec: dict) -> float | None:
    tr = rec.get("trace")
    if not tr or tr["kernel_s"].get(KERNEL, 0.0) <= 0.0:
        return None
    ops, bytes_ = rec["work"]["kernels"][KERNEL]
    p = peaks.peaks(rec["device_kind"])
    least = counts.least_seconds(ops, bytes_, p.int8_ops, p.hbm_bw) \
        * tr["calls"]
    return 100.0 * least / tr["kernel_s"][KERNEL]
