"""The share of its Type-I rows the TM epoch kernel hashed — the active
ones, the rest skipped — as the engine charges the round's program
counter ``tm.type1_hash_share`` (``fl/obs``: rows hashed over K · E · S
· 2 · ⌈m/2⌉), over the traced run's span rounds, in %.  A program
without the counter reads nothing."""


def read(rec: dict) -> float | None:
    spans = rec.get("spans") or []
    per_round = [getattr(s, "counts", {}).get("tm.type1_hash_share")
                 for s in spans]
    if not per_round or None in per_round:
        return None
    return 100.0 * sum(per_round) / len(per_round)
