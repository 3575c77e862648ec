"""Serving's share of the chip's peak: the votes work the served
requests require (``counts.tm_predict_ops``, padding not counted), over
the summed host time of the traced ``predict`` calls, over the peak the
configuration names."""
import peaks


def read(rec: dict) -> float | None:
    work = rec.get("serve_work")
    if not work or work["predict_s"] <= 0.0:
        return None
    rate = sum(work["ops"]) / work["predict_s"]
    return 100.0 * rate / peaks.peaks(rec["device_kind"]).rate(rec["peak"])
