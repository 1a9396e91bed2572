"""Readers for the selective-scan kernels of ``ops/selective_scan.py``
(``ssm_scan_fwd``, ``ssm_scan_bwd``): share of the chip's HBM bandwidth the
bytes a scan *has to* move (``flops/ssm_scan.py``) would need, over the
kernel's self time. A scan has no matmul, so the HBM roof is the only
published one it has; the kernels are bound by the VPU and the exponentials,
so the share is small by nature and says how far from memory-bound they are.

An own pass over ``trace_scopes.read_planes`` and ``trace_reduce.self_times``
(as ``_attn_kinds.py`` makes): the ``pallas_call`` operations whose name stack
holds the kernel's name. ``[B, S, d_inner]`` is read off a call's first result,
``[B, S, d_inner / 1024, 8, 128]`` (``y`` in the forward, ``dc`` in the
backward), and ``N`` off the one result that is ``[.., N, 8, 128]`` with one
more or one fewer leading dimension (the saved states ``[B, S / T, d_inner /
1024, N, 8, 128]`` in the forward, ``dA [B, d_inner / 1024, N, 8, 128]`` in
the backward). None where there is no trace, no ``Steps`` line or no such call
(a program without the kernels), or the results are of another layout.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional

from benchmark import trace_reduce, trace_scopes
from benchmark.flops import ssm_scan

_N_RESULT = {"ssm_scan_fwd": 6, "ssm_scan_bwd": 5}  # rank of the result that holds N at [-3]


@functools.lru_cache(maxsize=4)
def _kernel_calls(path: str):
    """{kernel: [calls, self seconds, (B, S, Di, N) of a call]}, mean over
    devices, inside the whole steps."""
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
            kernel = next((s for s in reversed(stack) if s in ssm_scan.BYTES_BY_KERNEL), None)
            if kernel is None:
                continue
            entry = out.setdefault(kernel, [0.0, 0.0, None])
            entry[0] += 1.0 / len(devs)
            entry[1] += t / len(devs)
            results = [[int(x) for x in g.split(",")] for g in
                       trace_scopes._DIMS.findall(rec.get("name", "").partition(" = ")[2])]
            first = results[0] if results else []
            # the last such result: dA comes after dc and dDelta, which have its rank
            states = next((r for r in reversed(results[1:]) if len(r) == _N_RESULT[kernel]
                           and r[-2:] == [8, 128]), None)
            if len(first) == 5 and first[-2:] == [8, 128] and states:
                entry[2] = (first[0], first[1], first[2] * 8 * 128, states[-3])
    return out


def kernel_hbm_pct(sources: Dict[str, Any], kernel: str) -> Optional[float]:
    trace_dir, peaks = sources.get("trace_dir"), sources.get("peaks")
    if not trace_dir or not peaks:
        return None
    try:
        calls = _kernel_calls(trace_reduce.find_xplane(trace_dir))
    except (FileNotFoundError, ValueError, IndexError):
        return None
    n, secs, dims = calls.get(kernel, (0.0, 0.0, None))
    if not n or secs <= 0 or not dims:
        return None
    return 100.0 * n * ssm_scan.BYTES_BY_KERNEL[kernel](*dims) / secs / peaks["hbm_bytes_per_s"]
