"""Share of the chip's bf16 peak the two flash backward kernels reach together in the
full-attention layers (the causal triangle, ``flops/flash_attention.py``): the calls whose name
stack holds ``attn_global``, by ``_attn_kinds.py``."""

from _attn_kinds import kernel_peak_pct


def read(sources):
    return kernel_peak_pct(sources, "attn_global", ('flash_bwd_dq', 'flash_bwd_dkv'))
