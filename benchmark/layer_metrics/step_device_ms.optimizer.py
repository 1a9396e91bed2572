"""Device time a step under ``optimizer`` (update and apply) and
``grad_clip`` (the global norm and the scaling inside it)."""

from benchmark.trace_scopes import step_ms


def read(sources):
    return step_ms(sources, ("optimizer", "grad_clip"))
