"""Share of the chip's bf16 peak the flash forward kernel reaches at latent
attention's head sizes (q, k 192; v 128): its calls in the whole steps times
the operations a causal call executes (``flops/flash_mla.py``) over their self
time. Nothing where the trace's ``flash_fwd`` calls are of another width."""

from benchmark.flops import flash_mla
from benchmark.trace_scopes import kernel_peak_pct


def read(sources):
    try:
        return kernel_peak_pct(sources, ("flash_fwd",), flash_mla.BY_KERNEL)
    except ValueError:
        return None
