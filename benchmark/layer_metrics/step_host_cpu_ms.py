"""Process CPU time a step (the loop, the prefetch worker, the runtime's
threads), median over the window: ``proc_cpu_s`` over ``steps`` of each
``step_window`` event. Host work that the device hides today and a smaller
model would not."""

import statistics


def read(sources):
    events = [e for e in sources.get("step_window_events") or [] if "proc_cpu_s" in e]
    if not events:
        return None
    return 1e3 * statistics.median(e["proc_cpu_s"] / max(int(e.get("steps", 1)), 1)
                                   for e in events)
