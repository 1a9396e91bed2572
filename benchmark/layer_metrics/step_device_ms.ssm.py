"""Device time a step of every operation with ``ssm`` in its name stack: a
Mamba mixer whole (its four projections, the convolution, the scan's two
kernels and the layout copies around them; forward, recomputed and backward).
Not a scope of ``trace_scopes.VOCABULARY``."""

from _named_scopes import step_ms_under


def read(sources):
    return step_ms_under(sources, "ssm")
