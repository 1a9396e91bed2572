"""Share of the chip's bf16 peak the two flash backward kernels reach together
in the full and cross differential-attention layers (q, k 64; v, dO 128; the
causal triangle, ``flops/flash_diff.py``): the calls whose name stack holds
``attn_global``, by ``_diff_flash.py``."""

from _diff_flash import kernel_peak_pct


def read(sources):
    return kernel_peak_pct(sources, ("flash_bwd_dq", "flash_bwd_dkv"))
