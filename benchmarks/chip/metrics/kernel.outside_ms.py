"""Device time per round in operations outside the named Pallas kernels
(the epoch draws, pads, gathers, evaluation), from the device trace."""


def read(rec: dict) -> float | None:
    tr = rec.get("trace")
    if not tr or not tr["calls"]:
        return None
    return 1e3 * tr["outside_s"] / tr["calls"]
