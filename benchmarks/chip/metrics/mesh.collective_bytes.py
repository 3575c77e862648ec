"""Payload bytes the round's aggregation collective moves to each device,
as the engine's gauge computes them from the mesh collective and the
upload shapes (``Engine.collective_payload_bytes``)."""


def read(rec: dict) -> float | None:
    value = rec.get("collective_bytes")
    return None if value is None else float(value)
