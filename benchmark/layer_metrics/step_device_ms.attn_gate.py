"""Device time a step of every operation with ``attn_gate`` in its name stack:
the attention output gate (its projection inside ``attn_qkv``, its sigmoid and product inside ``attn_out``, and their backward).
Overlaps ``step_device_ms.attn_core`` / ``.attn_proj`` by design; not a scope
of ``trace_scopes.VOCABULARY``."""

from _named_scopes import step_ms_under


def read(sources):
    return step_ms_under(sources, "attn_gate")
