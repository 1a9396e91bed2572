"""Share of the chip's bf16 peak the flash forward kernel reaches: its calls
in the whole steps (forward pass and recomputation) times the operations a
causal call executes (``flops/flash_attention.py``) over their self time.
Compute-bound at S 4,096, D 128, so the peak is the right roofline."""

from benchmark.flops import flash_attention
from benchmark.trace_scopes import kernel_peak_pct


def read(sources):
    return kernel_peak_pct(sources, ("flash_fwd",), flash_attention.BY_KERNEL)
