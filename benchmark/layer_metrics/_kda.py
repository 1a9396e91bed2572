"""Readers for the Kimi Delta Attention kernels of ``ops/kda.py`` (``kda_fwd``;
``kda_bwd``, or several ``kda_bwd_<part>`` whose self times are summed): share
of its roof, ``100 x max(required operations / the bf16 peak, required bytes /
the HBM bandwidth) / the kernel's self time`` a call, both counts from the
call's shapes by ``flops/kda_chunk.py``. **At the cell's call (2 x 8,192 x 32 x
128) the HBM bandwidth binds both passes** (forward 0.99 ms of bytes against
0.49 of operations; backward 1.81 against 0.98), so the share says how far a
kernel is from reading its operands once and writing its results once. Forward
and recomputed calls read under ``kda_fwd``.

An own pass over ``trace_scopes.read_planes`` and ``trace_reduce.self_times``
(as ``_ssm_scan.py`` makes): the ``pallas_call`` operations whose name stack
holds the kernel's name. ``[B, S, H d]`` is read off a call's first result (``o``
in the forward, ``dq`` in the backward); ``H`` and ``d`` are the configuration's
(``sources["kda_heads"]``, ``["kda_head_dim"]``, which the traffic kind hands
over). None where there is no trace, no ``Steps`` line, no such call (a
program without the kernels) or no such source.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional

from benchmark import trace_reduce, trace_scopes
from benchmark.flops import kda_chunk

_PASSES = ("kda_fwd", "kda_bwd")


def _pass_of(stack) -> Optional[tuple]:
    """(pass, the kernel's own name) of a name stack, innermost first."""
    for s in reversed(stack):
        for p in _PASSES:
            if s == p or s.startswith(p + "_"):
                return p, s
    return None


@functools.lru_cache(maxsize=4)
def _kernel_calls(path: str):
    """{pass: [calls, self seconds, (B, S, Hd) of a call]}, mean over devices,
    inside the whole steps; a pass of several kernels is called as often as its
    most frequent one."""
    devs = [p for p in trace_scopes.read_planes(path)["devices"]
            if p["lines"].get(trace_reduce.STEPS_LINE)]
    out: Dict[str, list] = {}
    if not devs:
        return out
    lo = min(s for p in devs for _, s, _ in p["lines"][trace_reduce.STEPS_LINE])
    hi = max(e for p in devs for _, _, e in p["lines"][trace_reduce.STEPS_LINE])
    by_name: Dict[str, Dict[str, float]] = {}
    for p in devs:
        ops = [(m, max(s, lo), min(e, hi)) for m, s, e in p["lines"].get(trace_reduce.OPS_LINE, [])
               if min(e, hi) > max(s, lo)]
        for m, t in trace_reduce.self_times(ops):
            rec = p["events"].get(m, {})
            op_name = rec.get("tf_op") or ""
            # XLA names its small operations on a kernel's results (dbeta's two sums) after
            # the kernel's name stack too: a call is the custom call itself
            if "pallas_call" not in op_name or "custom-call" not in rec.get("name", ""):
                continue
            found = _pass_of(trace_scopes._SPLIT.split(op_name))
            if found is None:
                continue
            which, kernel = found
            entry = out.setdefault(which, [0.0, 0.0, None])
            counts = by_name.setdefault(which, {})
            counts[kernel] = counts.get(kernel, 0.0) + 1.0 / len(devs)
            entry[1] += t / len(devs)
            results = [[int(x) for x in g.split(",")] for g in
                       trace_scopes._DIMS.findall(rec.get("name", "").partition(" = ")[2])]
            if results and len(results[0]) == 3:
                entry[2] = tuple(results[0])
    for which, counts in by_name.items():
        out[which][0] = max(counts.values())
    return out


def kernel_roof_pct(sources: Dict[str, Any], which: str) -> Optional[float]:
    trace_dir, peaks = sources.get("trace_dir"), sources.get("peaks")
    H, d = sources.get("kda_heads"), sources.get("kda_head_dim")
    if not trace_dir or not peaks or not H or not d:
        return None
    try:
        calls = _kernel_calls(trace_reduce.find_xplane(trace_dir))
    except (FileNotFoundError, ValueError, IndexError):
        return None
    n, secs, dims = calls.get(which, (0.0, 0.0, None))
    if not n or secs <= 0 or not dims or dims[2] != H * d:
        return None
    flops, nbytes = kda_chunk.BY_KERNEL[which]
    shape = (dims[0], dims[1], H, d)
    return 100.0 * n * kda_chunk.roof_seconds(flops(*shape), nbytes(*shape), peaks) / secs
