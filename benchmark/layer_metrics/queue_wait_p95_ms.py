"""Submission to slot binding, ``queue_ms`` of each finished response."""
from _common import percentile


def read(sources):
    waits = [r["final"]["queue_ms"] for r in sources.get("finished", [])
             if r.get("final") and "queue_ms" in r["final"]]
    return percentile(waits, 0.95) if waits else None
