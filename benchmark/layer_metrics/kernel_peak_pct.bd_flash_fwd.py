"""Share of the chip's bf16 peak the flash forward kernel reaches under the
block-diffusion mask: the calls whose name stack holds ``attn_blockdiff``
(forward and recomputed), the mask's own pairs (``flops/flash_blockdiff.py``:
``L^2 + L B'`` a head) over their self time, by ``_blockdiff.py``. A plan that
computes tiles the mask leaves dead reads low here first."""

from _blockdiff import kernel_peak_pct


def read(sources):
    return kernel_peak_pct(sources, ("flash_fwd",))
