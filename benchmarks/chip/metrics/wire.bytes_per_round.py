"""Upload plus per-client download bytes of a round, as the engine meters
them on its wire (``RoundReport``), averaged over the traced rounds."""


def read(rec: dict) -> float | None:
    wire = rec.get("wire_bytes") or []
    if not wire:
        return None
    return float(sum(wire)) / len(wire)
