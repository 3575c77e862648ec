"""The whole round's share of the chip's peak: the operations a round
requires (``counts``), over the traced round time, over the peak the
configuration names (int8 for the TM, bf16 for the MLP)."""
import peaks


def read(rec: dict) -> float | None:
    tr = rec.get("trace")
    if not tr or not tr["calls"] or tr["window_s"] <= 0.0:
        return None
    rate = rec["work"]["round_ops"] * tr["calls"] / tr["window_s"]
    return 100.0 * rate / peaks.peaks(rec["device_kind"]).rate(rec["peak"])
