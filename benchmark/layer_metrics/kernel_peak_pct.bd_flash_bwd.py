"""Share of the chip's bf16 peak the two flash backward kernels (dQ, dK/dV)
reach under the block-diffusion mask: the calls whose name stack holds
``attn_blockdiff``, the mask's own pairs (``flops/flash_blockdiff.py``) at 3
and 4 matmuls a pair over their self time, by ``_blockdiff.py``."""

from _blockdiff import kernel_peak_pct


def read(sources):
    return kernel_peak_pct(sources, ("flash_bwd_dq", "flash_bwd_dkv"))
