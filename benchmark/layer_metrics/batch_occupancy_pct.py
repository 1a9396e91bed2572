"""Occupied decode slots over slots, mean of the /metrics snapshots taken
through the window."""
from _common import in_window


def read(sources):
    snaps = in_window(sources, sources.get("snapshots", []))
    if not snaps:
        return None
    return 100.0 * sum(s["batch_occupancy"] for s in snaps) / (len(snaps) * sources["num_slots"])
