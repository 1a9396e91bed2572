"""Device time a step of every operation with ``attn_global`` in its name stack:
a full-attention layer's attention core (its kernels, forward, recomputed and backward, and their layout copies).
Overlaps ``step_device_ms.attn_core`` / ``.attn_proj`` by design; not a scope
of ``trace_scopes.VOCABULARY``."""

from _named_scopes import step_ms_under


def read(sources):
    return step_ms_under(sources, "attn_global")
