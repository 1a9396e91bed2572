"""Device time a step in the attention projections: q/k/v with rope
(``attn_qkv``) and the output projection (``attn_out``), forward, recomputed
and backward."""

from benchmark.trace_scopes import step_ms


def read(sources):
    return step_ms(sources, ("attn_qkv", "attn_out"))
