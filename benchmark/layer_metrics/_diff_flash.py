"""Share of the chip's bf16 peak the flash kernels reach at differential
attention's head sizes (q, k 64; v, dO 128) in the layers that attend causally
to everything (``models/sambay.py``'s ``F`` and ``C`` layers): the calls whose
name stack holds ``attn_global``, as ``_attn_kinds.py`` finds them, times the
operations a causal 64/128 call executes (``flops/flash_diff.py``) over their
self time. None where there is no trace or no such call, and where the calls
under ``attn_global`` are of another width (``flash_diff`` asserts the width
it reads): another architecture's full-attention layers are
``kernel_peak_pct.global_flash_*``'s.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence

from _attn_kinds import _kernel_calls

from benchmark import trace_reduce
from benchmark.flops import flash_diff


def kernel_peak_pct(sources: Dict[str, Any], kernels: Sequence[str]) -> Optional[float]:
    trace_dir, peaks = sources.get("trace_dir"), sources.get("peaks")
    if not trace_dir or not peaks:
        return None
    try:
        calls = _kernel_calls(trace_reduce.find_xplane(trace_dir))
    except (FileNotFoundError, ValueError, IndexError):
        return None
    flops = seconds = 0.0
    for k in kernels:
        n, secs, dims = calls.get(("attn_global", k), (0.0, 0.0, None))
        if not n or secs <= 0 or not dims or len(dims) != 4:
            return None
        try:
            flops += n * flash_diff.BY_KERNEL[k](*dims)
        except ValueError:
            return None
        seconds += secs
    return 100.0 * flops / seconds / peaks["bf16_flops"]
