"""One record a step of the trainer's loop, a window's summary of its
records, and the run's median step.

A record runs from one top of the loop to the next, or to where work beside
the step begins (an evaluation, a checkpoint): its wall time, the seconds of
the loop's four phases inside it, and the differences of
``hoststats.step_totals()`` across it. ``step_window`` events carry a
window's summary (:meth:`StepRecords.window`) with the slowest step's
record whole; a step far over the run's median is a stall
(``x_median`` in its record; the trainer warns and writes a ``step_stall``
event). Nothing here reads a file or asks the device.
"""

from __future__ import annotations

import bisect
import collections
import statistics
import time
from typing import Any, Deque, Dict, List, Optional

from . import hoststats

PHASES = ("data_get_s", "dispatch_s", "loss_sync_s", "log_window_s")
# What of a record the zero-length ``train.step_record`` annotation carries
# into an open profiler session.
ANNOTATED = ("step", "wall_s", "proc_cpu_s", "nivcsw")


class StepRecords:
    STALL_FACTOR = 2.0   # a step over this many median steps is a stall
    MEDIAN_OVER = 1000   # the median is over this many steps, the newest
    MEDIAN_FROM = 5      # and says nothing before it has this many

    def __init__(self) -> None:
        self._rec: Optional[Dict[str, Any]] = None   # the open step
        self._t0 = 0.0                               # its top of the loop:
        self._c0: tuple = ()                         # perf_counter, step_totals, compiles
        self._compiles0 = 0
        self.closed_at = 0.0                         # perf_counter of the last close
        self._window: List[Dict[str, Any]] = []      # closed since the last window()
        self._recent: Deque[float] = collections.deque()
        self._sorted: List[float] = []

    def median(self) -> Optional[float]:
        """Median wall time of the newest ``MEDIAN_OVER`` steps that count
        (no compile, a profiler's start or stop inside the step taken off)."""
        n = len(self._sorted)
        if n < self.MEDIAN_FROM:
            return None
        return 0.5 * (self._sorted[(n - 1) // 2] + self._sorted[n // 2])

    def turn(self, step: int, first_dispatch: bool, compiles_seen: int,
             side_s: float = 0.0) -> Optional[Dict[str, Any]]:
        """Top of the loop: one read of the clocks ends the step before (its
        record is returned; None where none is open, on the first turn and
        after a :meth:`close`) and begins ``step``."""
        now, c = time.perf_counter(), hoststats.step_totals()
        rec = self._close(now, c, compiles_seen, side_s) if self._rec is not None else None
        self._rec = {"step": int(step), "wall_s": 0.0, **dict.fromkeys(PHASES, 0.0)}
        if first_dispatch:
            self._rec["first_dispatch"] = True
        self._compiles0 = compiles_seen
        self._t0, self._c0 = now, c
        return rec

    def note(self, **fields: Any) -> None:
        """A phase's seconds, the prefetcher's queue depth: into the open record."""
        self._rec.update(fields)

    def close(self, compiles_seen: int, side_s: float = 0.0) -> Dict[str, Any]:
        """End the open step here: before work beside the step, whose seconds
        are no step's, and at the loop's end."""
        return self._close(time.perf_counter(), hoststats.step_totals(), compiles_seen, side_s)

    def _close(self, now: float, c1, compiles_seen: int, side_s: float) -> Dict[str, Any]:
        """``side_s``: seconds inside the step that the program spent starting or
        stopping a profiler capture, which is no stall. Returns the record; ``in_median`` says
        whether it counted towards the run's median, ``x_median`` is its wall
        time over the median of the steps before it."""
        rec, self._rec = self._rec, None
        self.closed_at = now
        rec["wall_s"] = round(now - self._t0, 6)
        for name, a, b in zip(hoststats.STEP_FIELDS, self._c0, c1):
            rec[name] = round(b - a, 6) if isinstance(b, float) else b - a
        compiled = compiles_seen - self._compiles0
        if compiled:
            rec["xla_compiles"] = compiled
        if side_s > 0:
            rec["side_s"] = round(side_s, 6)
        own = max(rec["wall_s"] - side_s, 0.0)
        rec["in_median"] = not (compiled or rec.get("first_dispatch"))
        if rec["in_median"]:
            median = self.median()
            if median:
                rec["x_median"] = round(own / median, 3)
            self._recent.append(own)
            bisect.insort(self._sorted, own)
            if len(self._recent) > self.MEDIAN_OVER:
                del self._sorted[bisect.bisect_left(self._sorted, self._recent.popleft())]
        self._window.append(rec)
        return rec

    def window(self) -> Dict[str, Any]:
        """The summary of the steps closed since the last call, as a
        ``step_window`` event carries it; {} where there were none."""
        recs, self._window = self._window, []
        if not recs:
            return {}
        slow = max(recs, key=lambda r: r["wall_s"])
        return {"step_s_max": slow["wall_s"],
                "step_s_med": round(statistics.median(r["wall_s"] for r in recs), 6),
                "slow_step": slow["step"], "slow": slow,
                "proc_cpu_s": round(sum(r["proc_cpu_s"] for r in recs), 6),
                "nivcsw": sum(r["nivcsw"] for r in recs)}
