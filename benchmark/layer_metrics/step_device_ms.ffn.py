"""Device time a step under ``ffn``: the SwiGLU matmuls and their epilogues,
forward, recomputed and backward."""

from benchmark.trace_scopes import step_ms


def read(sources):
    return step_ms(sources, ("ffn",))
