"""Device time a step of every operation with ``gmu`` in its name stack: a
gated memory unit's mixer (two projections and the gate on the memory another
layer made; forward, recomputed and backward)."""

from _named_scopes import step_ms_under


def read(sources):
    return step_ms_under(sources, "gmu")
