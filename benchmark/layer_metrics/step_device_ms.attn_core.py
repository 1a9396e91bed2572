"""Device time a step in attention proper: the three flash kernels (forward,
its recomputation under remat, dQ, dK/dV) and anything else whose innermost
scope is ``attn_core`` (the layout changes around the calls)."""

from benchmark.trace_scopes import step_ms


def read(sources):
    return step_ms(sources, ("attn_core", "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"))
