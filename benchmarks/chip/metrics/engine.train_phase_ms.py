"""Mean per round of the engine's training phase: the ``client_step``
span, or ``fused_round`` where the engine runs the fused round, from the
traced run's fenced phase spans (``fl/obs``)."""


def train_s(span: dict) -> float | None:
    return span.get("client_step", span.get("fused_round"))


def read(rec: dict) -> float | None:
    spans = rec.get("spans") or []
    vals = [train_s(s) for s in spans]
    if not vals or None in vals:
        return None
    return 1e3 * sum(vals) / len(vals)
