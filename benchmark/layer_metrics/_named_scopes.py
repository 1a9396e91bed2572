"""Device time a step of the operations whose name stack holds a given
``jax.named_scope`` *anywhere*, for scopes the closed vocabulary of
``trace_scopes.py`` lacks (``hc_mix``, ``mtp``): that file attributes an
operation to its innermost vocabulary name, so these would read as ``layer``
or as whatever they enclose. Same clipping to the whole steps and the same
self times as ``trace_scopes.reduce``; None where there is no trace, no
``Steps`` line, or no operation under the scope (a program without it)."""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional

from benchmark import trace_reduce, trace_scopes


@functools.lru_cache(maxsize=4)
def _seconds_by_token(path: str):
    """({token of any name stack: self seconds, mean over devices}, steps)."""
    devs = [p for p in trace_scopes.read_planes(path)["devices"]
            if p["lines"].get(trace_reduce.STEPS_LINE)]
    if not devs:
        return {}, 0
    lo = min(s for p in devs for _, s, _ in p["lines"][trace_reduce.STEPS_LINE])
    hi = max(e for p in devs for _, _, e in p["lines"][trace_reduce.STEPS_LINE])
    out: Dict[str, float] = {}
    for p in devs:
        ops = [(m, max(s, lo), min(e, hi)) for m, s, e in p["lines"].get(trace_reduce.OPS_LINE, [])
               if min(e, hi) > max(s, lo)]
        for m, t in trace_reduce.self_times(ops):
            stack = p["events"].get(m, {}).get("tf_op") or ""
            for token in set(trace_scopes._SPLIT.split(stack)):
                out[token] = out.get(token, 0.0) + t / len(devs)
    return out, len(devs[0]["lines"][trace_reduce.STEPS_LINE])


def step_ms_under(sources: Dict[str, Any], scope: str) -> Optional[float]:
    trace_dir = sources.get("trace_dir")
    if not trace_dir:
        return None
    try:
        seconds, steps = _seconds_by_token(trace_reduce.find_xplane(trace_dir))
    except (FileNotFoundError, ValueError, IndexError):
        return None
    if not steps or scope not in seconds:
        return None
    return 1e3 * seconds[scope] / steps
