"""Share of the window its steps spent over the median step: the sum over
the window's steps of ``max(0, step - median step)``, over the window. The
program's loop times every step from one top of the loop to the next
(``step_s_max``, ``step_s_med`` of each ``step_window`` event; with a step a
window, as in every cell, both are that step). Where a window holds several
steps only its slowest is known alone: the others count by what the rest of
the window's elapsed time (its ``goodput`` sums to it) exceeds their medians.

Read in the traced run only, in which the harness starts its profiler
(``start_trace``, about 50 ms of host work) after its own stamp of a step and
inside the program's record of it: that step's event is the harness's doing
and is left out (:func:`own_events`)."""

import statistics


def own_events(sources, field):
    """The window's ``step_window`` events that hold ``field``, without the one
    whose steps include the step in which the harness started its profiler
    (the last of ``timed_steps`` to begin before ``trace_span`` did)."""
    events = [e for e in sources.get("step_window_events") or [] if field in e]
    span = sources.get("trace_span")
    began = [s["i"] for s in sources.get("timed_steps") or [] if span and s["t0"] <= span[0]]
    if began:
        at = max(began)
        events = [e for e in events
                  if not int(e["step"]) - int(e.get("steps", 1)) < at <= int(e["step"])]
    return events


def read(sources):
    events = own_events(sources, "step_s_max")
    if not events:
        return None
    median = statistics.median(e["step_s_med"] for e in events)
    late = 0.0
    for e in events:
        late += max(0.0, e["step_s_max"] - median)
        others = int(e.get("steps", 1)) - 1
        if others > 0:
            elapsed = sum(e["goodput"].values())
            late += max(0.0, elapsed - e["step_s_max"] - others * median)
    w0, w1 = sources["window"]
    return 100.0 * late / (w1 - w0)
