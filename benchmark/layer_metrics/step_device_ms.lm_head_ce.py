"""Device time a step under ``lm_head_ce``: the output head folded into the
chunked cross-entropy, its recomputation and its backward."""

from benchmark.trace_scopes import step_ms


def read(sources):
    return step_ms(sources, ("lm_head_ce",))
