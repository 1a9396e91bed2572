"""Arithmetic several readers share. A reader takes the run's collected
sources (see the traffic kinds for the keys) and returns a number, or None
when this cell has nothing for it to read."""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmark.loadgen import percentile  # noqa: E402


def device_idle_pct(sources):
    """1 minus the union of device operation intervals over the traced
    window, mean over devices."""
    tr = sources.get("trace")
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def iter_device_ms(sources):
    """Device busy time in the traced window over the engine iterations that
    did work in it: the program's ``busy_iterations`` counter, read from
    ``/metrics`` where the trace starts and stops (``iterations`` also ticks
    on an idle loop turn, fifty a second, and would flatter a cell below
    capacity)."""
    tr, it = sources.get("trace"), sources.get("trace_iterations") or {}
    if not tr or "start" not in it or "stop" not in it or it["stop"] <= it["start"]:
        return None
    return 1e3 * tr["busy_s"] / (it["stop"] - it["start"])


def in_window(sources, snapshots):
    w0, w1 = sources["window"]
    return [s for s in snapshots if w0 <= s["t"] <= w1]
