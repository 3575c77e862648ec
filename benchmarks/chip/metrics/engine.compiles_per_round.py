"""Backend compiles per round, as the engine's phase tracer charges them
to the stage span open when JAX reported them (``fl/obs``), over the
traced run's span rounds; the split by stage goes to standard error."""
import sys


def read(rec: dict) -> float | None:
    spans = rec.get("spans") or []
    per_round = [getattr(s, "compiles", None) for s in spans]
    if not per_round or None in per_round:
        return None
    by_stage: dict[str, int] = {}
    for c in per_round:
        for stage, n in c.items():
            by_stage[stage] = by_stage.get(stage, 0) + n
    print(f"compiles by stage over {len(per_round)} rounds: {by_stage}",
          file=sys.stderr)
    return sum(by_stage.values()) / len(per_round)
