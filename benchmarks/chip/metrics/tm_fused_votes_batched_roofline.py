"""The votes kernel's share of its roofline while serving: for each
traced ``predict`` call the least time the chip needs (the requests'
literal checks at the int8 peak, or the distinct models' include bits
and weights at the HBM bandwidth, whichever is larger), summed, over the
summed device time of the kernel's events."""
import counts
import peaks

KERNEL = "tm_fused_votes_batched"


def read(rec: dict) -> float | None:
    tr, work = rec.get("trace"), rec.get("serve_work")
    if not tr or not work or tr["kernel_s"].get(KERNEL, 0.0) <= 0.0:
        return None
    p = peaks.peaks(rec["device_kind"])
    least = sum(counts.least_seconds(o, b, p.int8_ops, p.hbm_bw)
                for o, b in zip(work["ops"], work["bytes"]))
    return 100.0 * least / tr["kernel_s"][KERNEL]
