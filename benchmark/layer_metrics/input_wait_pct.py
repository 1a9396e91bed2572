"""Time the step loop waited for a batch (``data_wait_s`` + ``h2d_wait_s`` of
the window's ``step_window`` events) over the window."""


def read(sources):
    events = sources.get("step_window_events")
    if not events:
        return None
    waited = sum(e["goodput"].get("data_wait_s", 0.0) + e["goodput"].get("h2d_wait_s", 0.0)
                 for e in events)
    w0, w1 = sources["window"]
    return 100.0 * waited / (w1 - w0)
