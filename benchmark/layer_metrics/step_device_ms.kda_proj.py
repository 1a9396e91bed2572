"""Device time a step of every operation under the scope ``kda_proj``: a delta-rule
mixer before its core (the three head-wide projections, the q, k, v prologue's
kernels ``short_conv_fwd`` | ``short_conv_bwd``, the decay's low-rank pair with
its softplus, the write strength's projection and sigmoid); forward, recomputed
and backward. Inside ``step_device_ms.kda``, beside ``.kda_core``; where the
mixer is twice as wide as the residual stream this is where the 4096 x 8192
projections go. Not a scope of ``trace_scopes.VOCABULARY``; None in a program
without the scope."""

from _named_scopes import step_ms_under


def read(sources):
    return step_ms_under(sources, "kda_proj")
