"""Device time a step of every operation with ``kda`` in its name stack: a
Kimi Delta Attention mixer whole (its projections, convolutions, the norms of q
and k, the decay, the core's kernels and what hands them their layouts, the head
norm, the gate and ``W_o``; forward, recomputed and backward). Not a scope of
``trace_scopes.VOCABULARY``."""

from _named_scopes import step_ms_under


def read(sources):
    return step_ms_under(sources, "kda")
