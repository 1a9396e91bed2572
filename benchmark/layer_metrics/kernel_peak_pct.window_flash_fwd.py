"""Share of the chip's bf16 peak the flash forward kernel reaches in the
sliding-window layers (the band's operations, ``flops/flash_window.py``): the calls whose name
stack holds ``attn_window``, by ``_attn_kinds.py``."""

from _attn_kinds import kernel_peak_pct


def read(sources):
    return kernel_peak_pct(sources, "attn_window", ('flash_fwd',))
