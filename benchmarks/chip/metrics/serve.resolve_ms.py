"""Mean per batch of the serving plane's ``serve/resolve`` span (unique
ids, row gather, lane expansion), from the traced run's fenced spans
(``fl/serve`` telemetry)."""


def read(rec: dict) -> float | None:
    vals = rec.get("resolve_s") or []
    if not vals:
        return None
    return 1e3 * sum(vals) / len(vals)
