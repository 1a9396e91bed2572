"""Device time a step under ``hc_mix``: the mixed residual path's maps (the
``[nC, 24]`` projection, sigmoids, Sinkhorn iterations) and its reads and
writes of the streams, forward, recomputed and backward. Not a scope of
``trace_scopes.VOCABULARY``: its operations also count in ``layer`` there."""

from _named_scopes import step_ms_under


def read(sources):
    return step_ms_under(sources, "hc_mix")
