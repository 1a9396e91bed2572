"""Chunks of a held share that took the whole dropless buffer, a step, summed
over every routed layer: the program's ``moe_chunks_whole`` counter on the
window's ``step_window`` events over their steps. 0 = every chunk's rows fit
the small buffer, so every step multiplied the same tiles whatever its router
sent; a program without the counter gives nothing to read."""


def read(sources):
    events = [e for e in sources.get("step_window_events") or [] if "moe_chunks_whole" in e]
    steps = sum(int(e.get("steps", 1)) for e in events)
    if not steps:
        return None
    return float(sum(int(e["moe_chunks_whole"]) for e in events)) / steps
