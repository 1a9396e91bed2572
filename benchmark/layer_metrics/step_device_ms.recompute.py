"""Device time a step of every operation under ``rematted_computation``: the
forward work the backward pass does again under remat. It overlaps the
other ``step_device_ms`` rows by design (a recomputed ``ffn`` matmul counts
in both), so it is left out when they are added up to the busy time."""

from benchmark.trace_scopes import of_run


def read(sources):
    red = of_run(sources)
    if red is None or red["recompute_s"] <= 0:
        return None
    return 1e3 * red["recompute_s"] / red["steps"]
