"""Time inside Python's cyclic collector a step: ``gc_s`` of the window's
``step_window`` events (a ``gc.callbacks`` listener's start/stop pairs, every
generation) over their steps."""


def read(sources):
    events = [e for e in sources.get("step_window_events") or [] if "gc_s" in e]
    steps = sum(int(e.get("steps", 1)) for e in events)
    if not steps:
        return None
    return 1e3 * sum(e["gc_s"] for e in events) / steps
