"""What a run's set-up was made of, from the program's own account of it: the
``compile`` event the trainer appends where its first dispatch returns
(``train/trainer.py::_setup_record``: ``process_start_t``, ``phases`` with each
phase's start on ``time.time()``, its seconds and what of it was a trace, a
lowering or a backend compile, ``stages``, ``step_fun``, ``step_stages``), and
the ``step_window`` events after it (``xla_compiled``: what was built since, by
name, with the cache's outcome).

``sources`` has no handle on events before the window, so the run's log is
found as ``traffic_kinds/train_job_kda.py`` finds it: ``events.jsonl`` under
``<work directory>/runs/*/``, the work directory being the parent of
``sources["trace_dir"]`` (it stands until the readers have run). The window's
start is the first timed step's ``t0``, moved from ``perf_counter`` to
``time.time()`` by the difference of the two clocks read here.

``account`` cuts [the process's start, the window's start] into seven pieces that
sum to the whole by construction: ``other`` is what the six named ones leave
(the hand-over between the build and ``train()``, ``train.start``, the first
batch, step 1's own execution and the benchmark's programs inside its
dispatch). None without ``trace_dir``, without a ``compile`` event, or with one
that has no ``phases`` (a program from before PR 54).
"""

from __future__ import annotations

import functools
import glob
import json
import os
import time
from typing import Any, Dict, List, Optional, Tuple

def _events(trace_dir: str) -> List[Dict[str, Any]]:
    out: List[Dict[str, Any]] = []
    for path in sorted(glob.glob(os.path.join(os.path.dirname(trace_dir), "runs", "*", "events.jsonl"))):
        with open(path) as f:
            for line in f:
                try:
                    out.append(json.loads(line))
                except ValueError:
                    continue  # a torn last line
    return out


@functools.lru_cache(maxsize=4)
def _account(trace_dir: str, first_i: int, first_t0: float) -> Optional[Tuple[Dict[str, float], float]]:
    """(the seven pieces, the cache's misses up to the window), one reading of the
    files and the clocks for all eight readers of a run."""
    events = _events(trace_dir)
    record = next((e for e in events if e.get("type") == "compile" and e.get("phases")), None)
    if record is None or not record.get("process_start_t"):
        return None
    phases = record["phases"]
    init = [p for p in phases if p["name"].startswith("init.")]
    dispatch = next((p for p in phases if p["name"] == "train.dispatch"), None)
    if not init or dispatch is None:
        return None
    start = float(record["process_start_t"])
    window_t = first_t0 + (time.time() - time.perf_counter())
    first = min(p["t"] for p in init)
    built = max(p["t"] + p["seconds"] for p in init)
    step = record.get("step_stages") or {}
    parts = {
        "before_trainer": first - start,
        "trainer_build": built - first,
        "step_trace": float(step.get("trace_s", 0.0)),
        "step_lower": float(step.get("lower_s", 0.0)),
        "step_compile_or_load": float(dispatch.get("backend_s", 0.0)),
        "steps_to_window": window_t - (dispatch["t"] + dispatch["seconds"]),
    }
    parts["other"] = (window_t - start) - sum(parts.values())
    misses = int((record.get("stages") or {}).get("cache_misses", 0))
    for e in events:
        if e.get("type") == "step_window" and int(e.get("step", first_i)) < first_i:
            misses += sum(1 for outcome in (e.get("xla_compiled") or {}).values() if outcome == "miss")
    return parts, float(misses)


def account(sources) -> Optional[Tuple[Dict[str, float], float]]:
    trace_dir, timed = sources.get("trace_dir"), sources.get("timed_steps")
    if not trace_dir or not timed:
        return None
    return _account(trace_dir, int(timed[0]["i"]), float(timed[0]["t0"]))


def part(sources, name: str) -> Optional[float]:
    found = account(sources)
    return None if found is None else found[0][name]
