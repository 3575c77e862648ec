"""Compile milliseconds per round (tracing to a jaxpr, lowering, backend
compile), as the engine's phase tracer charges JAX's compile events to
its stage spans (``fl/obs``), over the traced run's span rounds."""


def read(rec: dict) -> float | None:
    spans = rec.get("spans") or []
    per_round = [getattr(s, "compile_s", None) for s in spans]
    if not per_round or None in per_round:
        return None
    return 1e3 * sum(sum(c.values()) for c in per_round) / len(per_round)
