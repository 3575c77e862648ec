"""Share of the traced serving window in which the device ran no
operation: 1 − (union of device op intervals) / window."""


def read(rec: dict) -> float | None:
    tr = rec.get("trace")
    if not tr or tr["window_s"] <= 0.0 or tr["busy_s"] <= 0.0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
