"""How late the generator sent: sent minus due, by its own clock."""
from _common import percentile


def read(sources):
    lat = sources.get("latency")
    return percentile(lat["lag_ms"], 0.95) if lat else None
