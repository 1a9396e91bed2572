"""Readers for a program whose layers run the flash kernels under two masks in
one step (``models/afmoe.py``): every operation of a sliding-window layer's
core carries ``attn_window`` in its name stack and every one of a
full-attention layer's ``attn_global``, around ``attn_core`` and the
kernel's own name.

``trace_scopes.kernel_peak_pct`` counts every call of a kernel's name as a
causal call of the last shape seen, so it cannot tell the two apart. This is
an own pass over ``trace_scopes.read_planes`` and ``trace_reduce.self_times``
(as ``_named_scopes.py`` makes): the ``pallas_call`` operations of the named
kernels whose stack holds the kind's scope, ``[B, H, S, D]`` read off each
call's first result, the operations of ``flops/flash_window.py`` (the band)
or ``flops/flash_attention.py`` (the triangle) over their self time over the
chip's bf16 peak. The window comes from the run's configuration
(``sources["sliding_window"]``, which the traffic kind ``train_job_afmoe``
sets). None where there is no trace, no ``Steps`` line, no such call (a
program without the scopes), or no window to count a band with.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Sequence

from benchmark import trace_reduce, trace_scopes
from benchmark.flops import flash_attention, flash_window


@functools.lru_cache(maxsize=4)
def _kernel_calls(path: str):
    """{(kind scope, kernel): [calls, self seconds, dims of a call's first
    result]}, mean over devices, inside the whole steps."""
    devs = [p for p in trace_scopes.read_planes(path)["devices"]
            if p["lines"].get(trace_reduce.STEPS_LINE)]
    out: Dict[Any, list] = {}
    if not devs:
        return out
    lo = min(s for p in devs for _, s, _ in p["lines"][trace_reduce.STEPS_LINE])
    hi = max(e for p in devs for _, _, e in p["lines"][trace_reduce.STEPS_LINE])
    for p in devs:
        ops = [(m, max(s, lo), min(e, hi)) for m, s, e in p["lines"].get(trace_reduce.OPS_LINE, [])
               if min(e, hi) > max(s, lo)]
        for m, t in trace_reduce.self_times(ops):
            rec = p["events"].get(m, {})
            op_name = rec.get("tf_op") or ""
            if "pallas_call" not in op_name:
                continue
            stack = trace_scopes._SPLIT.split(op_name)
            kind = next((s for s in ("attn_window", "attn_global") if s in stack), None)
            kernel = next((s for s in reversed(stack) if s in flash_attention.BY_KERNEL), None)
            if kind is None or kernel is None:
                continue
            dims = trace_scopes._DIMS.search(rec.get("name", "").partition(" = ")[2])
            entry = out.setdefault((kind, kernel), [0.0, 0.0, None])
            entry[0] += 1.0 / len(devs)
            entry[1] += t / len(devs)
            if dims:
                entry[2] = [int(x) for x in dims.group(1).split(",")]
    return out


def kernel_peak_pct(sources: Dict[str, Any], kind: str, kernels: Sequence[str]) -> Optional[float]:
    trace_dir, peaks = sources.get("trace_dir"), sources.get("peaks")
    window = sources.get("sliding_window")
    if not trace_dir or not peaks or (kind == "attn_window" and not window):
        return None
    try:
        calls = _kernel_calls(trace_reduce.find_xplane(trace_dir))
    except (FileNotFoundError, ValueError, IndexError):
        return None
    flops = seconds = 0.0
    for k in kernels:
        n, secs, dims = calls.get((kind, k), (0.0, 0.0, None))
        if not n or secs <= 0 or not dims or len(dims) != 4:
            return None
        flops += n * (flash_window.BY_KERNEL[k](*dims, window) if kind == "attn_window"
                      else flash_attention.BY_KERNEL[k](*dims))
        seconds += secs
    return 100.0 * flops / seconds / peaks["bf16_flops"]
