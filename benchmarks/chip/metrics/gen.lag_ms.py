"""How late the harness's load generator ran: the mean delay from each
request's due time to the moment the generator enqueued it."""


def read(rec: dict) -> float | None:
    vals = [v for v in rec.get("gen_lag_s") or [] if v == v]
    if not vals:
        return None
    return 1e3 * sum(vals) / len(vals)
