"""Share of the chip's bf16 peak the two flash backward kernels reach together
at latent attention's head sizes: ``flash_bwd_dq`` (192 + 128 + 192 a half
square) and ``flash_bwd_dkv`` (192 + 128 + 128 + 192), ``flops/flash_mla.py``."""

from benchmark.flops import flash_mla
from benchmark.trace_scopes import kernel_peak_pct


def read(sources):
    try:
        return kernel_peak_pct(sources, ("flash_bwd_dq", "flash_bwd_dkv"), flash_mla.BY_KERNEL)
    except ValueError:
        return None
