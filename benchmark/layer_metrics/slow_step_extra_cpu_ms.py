"""Process CPU time of the window's slowest step (``slow.proc_cpu_s`` of the
``step_window`` event whose ``step_s_max`` is largest) less the median step's
(``proc_cpu_s`` over ``steps`` of each event). Near 0: the process waited
through whatever made the step late; near the delay: the host was working
through it (every thread of the process counts). The step in which the
harness started its profiler is left out, as in ``step_stall_pct``."""

import statistics

from step_stall_pct import own_events  # run.py puts this directory on sys.path


def read(sources):
    events = [e for e in own_events(sources, "slow") if "proc_cpu_s" in e]
    if not events:
        return None
    slowest = max(events, key=lambda e: e["step_s_max"])
    median = statistics.median(e["proc_cpu_s"] / max(int(e.get("steps", 1)), 1) for e in events)
    return 1e3 * (slowest["slow"]["proc_cpu_s"] - median)
