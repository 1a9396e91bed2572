"""Device time a step of every operation under the scope ``kda_core``: the
delta rule's kernels (``kda_fwd``, ``kda_bwd``) and the XLA operations that hand
them their operands (``beta``'s two layouts, the cotangents' casts); forward,
recomputed and backward. Not a scope of ``trace_scopes.VOCABULARY``."""

from _named_scopes import step_ms_under


def read(sources):
    return step_ms_under(sources, "kda_core")
