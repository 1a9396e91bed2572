"""Share of the chip's bf16 peak the two flash backward kernels reach
together: calls of ``flash_bwd_dq`` and ``flash_bwd_dkv`` in the whole steps
times the operations each executes (3 and 4 half squares) over their self
time. Compute-bound like the forward."""

from benchmark.flops import flash_attention
from benchmark.trace_scopes import kernel_peak_pct


def read(sources):
    return kernel_peak_pct(sources, ("flash_bwd_dq", "flash_bwd_dkv"),
                           flash_attention.BY_KERNEL)
