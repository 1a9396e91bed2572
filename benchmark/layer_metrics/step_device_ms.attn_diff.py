"""Device time a step of every operation with ``attn_diff`` in its name stack:
differential attention's lambda, the difference of the two softmax maps'
outputs and the 128-wide norm (forward, recomputed and backward)."""

from _named_scopes import step_ms_under


def read(sources):
    return step_ms_under(sources, "attn_diff")
