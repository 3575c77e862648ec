"""Mean per round of the ``round`` span less the training phase: gather,
codec, aggregation, merge, evaluation and host bookkeeping, from the
traced run's fenced phase spans (``fl/obs``)."""


def read(rec: dict) -> float | None:
    spans = rec.get("spans") or []
    vals = [s["round"] - s.get("client_step", s.get("fused_round", 0.0))
            for s in spans if "round" in s]
    if not vals or len(vals) != len(spans):
        return None
    return 1e3 * sum(vals) / len(vals)
