"""Readers for a program whose attention runs the flash kernels under the
block-diffusion mask (``models/sdar.py``): every operation of such a core
carries ``attn_blockdiff`` in its name stack, around ``attn_core`` and the
kernel's own name.

An own pass over ``trace_scopes.read_planes`` and ``trace_reduce.self_times``
(as ``_attn_kinds.py`` makes for the window and full layers): the
``pallas_call`` operations of the named kernels whose stack holds the scope,
``[B, H, S, D]`` read off each call's first result, the operations of
``flops/flash_blockdiff.py`` (the mask's pairs, whatever tiles compute them)
over their self time over the chip's bf16 peak. The block length comes from
the run's configuration (``sources["block_length"]``, which the traffic kind
``train_job_sdar`` sets). None where there is no trace, no ``Steps`` line, no
such call (a program without the scope), or no block length to count with.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Sequence

from benchmark import trace_reduce, trace_scopes
from benchmark.flops import flash_blockdiff

SCOPE = "attn_blockdiff"


@functools.lru_cache(maxsize=4)
def _kernel_calls(path: str):
    """{kernel: [calls, self seconds, dims of a call's first result]} under
    ``attn_blockdiff``, mean over devices, inside the whole steps."""
    devs = [p for p in trace_scopes.read_planes(path)["devices"]
            if p["lines"].get(trace_reduce.STEPS_LINE)]
    out: Dict[str, list] = {}
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
            kernel = next((s for s in reversed(stack) if s in flash_blockdiff.BY_KERNEL), None)
            if SCOPE not in stack or kernel is None:
                continue
            dims = trace_scopes._DIMS.search(rec.get("name", "").partition(" = ")[2])
            entry = out.setdefault(kernel, [0.0, 0.0, None])
            entry[0] += 1.0 / len(devs)
            entry[1] += t / len(devs)
            if dims:
                entry[2] = [int(x) for x in dims.group(1).split(",")]
    return out


def kernel_peak_pct(sources: Dict[str, Any], kernels: Sequence[str]) -> Optional[float]:
    trace_dir, peaks = sources.get("trace_dir"), sources.get("peaks")
    block_length = sources.get("block_length")
    if not trace_dir or not peaks or not block_length:
        return None
    try:
        calls = _kernel_calls(trace_reduce.find_xplane(trace_dir))
    except (FileNotFoundError, ValueError, IndexError):
        return None
    flops = seconds = 0.0
    for k in kernels:
        n, secs, dims = calls.get(k, (0.0, 0.0, None))
        if not n or secs <= 0 or not dims or len(dims) != 4:
            return None
        flops += n * flash_blockdiff.BY_KERNEL[k](*dims, block_length)
        seconds += secs
    return 100.0 * flops / seconds / peaks["bf16_flops"]


def window_events(sources: Dict[str, Any], *keys: str):
    """The window's ``step_window`` events that carry every one of ``keys``."""
    return [e for e in sources.get("step_window_events") or [] if all(k in e for k in keys)]
