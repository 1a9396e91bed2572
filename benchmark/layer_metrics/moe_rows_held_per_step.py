"""Selections that landed on the experts this chip holds, a step, summed over
every routed layer: the program's ``moe_rows_held`` counter on the window's
``step_window`` events over their steps. The routed experts' share of
``train_mfu_pct``'s required FLOPs is counted from it."""


def read(sources):
    events = [e for e in sources.get("step_window_events") or [] if "moe_rows_held" in e]
    steps = sum(int(e.get("steps", 1)) for e in events)
    if not steps:
        return None
    return float(sum(int(e["moe_rows_held"]) for e in events)) / steps
