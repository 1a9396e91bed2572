"""XLA compilations (and compile-cache loads) the program counted inside
the window: the sum of ``xla_compiles`` over its ``step_window`` events.
Nothing compiles inside a measured window, so anything but 0 is a fault."""


def read(sources):
    events = [e for e in sources.get("step_window_events") or [] if "xla_compiles" in e]
    if not events:
        return None
    return float(sum(int(e["xla_compiles"]) for e in events))
