"""From a profiler trace (``.xplane.pb``) to numbers: busy intervals per
device, idle share, per-operation totals, the longest idle gaps, and the part
of collective time that no other operation hides.

Read with ``jax.profiler.ProfileData`` and nothing else. The interval-union
arithmetic is the one in the program's ``obs/profile_report.py`` (``_merge``,
``_total``, ``_intersect``, ``_clip``), which had only ever reduced CPU
traces; here it reads the device planes a TPU run writes
(``/device:TPU:<n>``, line ``XLA Ops``). A trace without device planes (a CPU
rehearsal) falls back to host-thread events that carry an ``hlo_op`` stat, so
the control flow can be rehearsed; such a reduction is marked
``device_planes: 0`` and no metric is read from it.
"""

from __future__ import annotations

import glob
import os
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

COLLECTIVE_PREFIXES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                       "collective-permute")
OPS_LINE = "XLA Ops"
STEPS_LINE = "Steps"


# -- interval arithmetic --------------------------------------------------------
def merge(iv: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for lo, hi in sorted(iv):
        if hi <= lo:
            continue
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1] = (out[-1][0], hi)
        else:
            out.append((lo, hi))
    return out


def total(merged: Sequence[Interval]) -> float:
    return sum(hi - lo for lo, hi in merged)


def clip(iv: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in iv if min(b, hi) > max(a, lo)]


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Parts of merged ``a`` not covered by merged ``b``."""
    out: List[Interval] = []
    j = 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def self_times(ops: Sequence[Tuple[str, float, float]]) -> List[Tuple[str, float]]:
    """(name, seconds) per operation, not counting the time of operations
    nested inside it on the same line (a ``while`` holds its body's fusions):
    so the totals add up to the busy time instead of counting a loop twice."""
    out: List[Tuple[str, float]] = []
    stack: List[List[Any]] = []  # [name, end, self seconds]
    for n, s, e in sorted(ops, key=lambda r: (r[1], -r[2])):
        while stack and stack[-1][1] <= s:
            top = stack.pop()
            out.append((top[0], top[2]))
        if stack:
            stack[-1][2] -= min(e, stack[-1][1]) - s
        stack.append([n, e, e - s])
    out.extend((top[0], top[2]) for top in stack)
    return out


def is_collective(name: str) -> bool:
    base = name.lstrip("%").lower()
    return base.startswith(COLLECTIVE_PREFIXES)


def base_name(name: str) -> str:
    """``%fusion.123 = ...`` -> ``fusion``: the trace's name without the
    instruction number, so repeats of one operation add up."""
    n = name.split(" ", 1)[0].lstrip("%")
    head, _, tail = n.rpartition(".")
    return head if head and tail.isdigit() else n


# -- reading ----------------------------------------------------------------------
def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def read_events(path: str) -> Dict[str, Any]:
    """{"devices": {plane: [(name, start_s, end_s)]}, "steps": {plane: [...]},
    "host": [(name, start_s, end_s)], "device_planes": n}."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    devices: Dict[str, List[Tuple[str, float, float]]] = {}
    steps: Dict[str, List[Tuple[str, float, float]]] = {}
    host: List[Tuple[str, float, float]] = []
    fallback: List[Tuple[str, float, float]] = []
    for plane in data.planes:
        is_dev = plane.name.startswith("/device:") and "TPU" in plane.name.upper()
        for line in plane.lines:
            if is_dev and line.name == OPS_LINE:
                devices.setdefault(plane.name, []).extend(
                    (e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
                    for e in line.events)
            elif is_dev and line.name == STEPS_LINE:
                steps.setdefault(plane.name, []).extend(
                    (e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
                    for e in line.events)
            elif plane.name.startswith("/host:"):
                for e in line.events:
                    if e.duration_ns <= 0:
                        continue
                    rec = (e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
                    if any(k == "hlo_op" for k, _ in e.stats):
                        fallback.append(rec)
                    elif not e.name.startswith("$"):
                        host.append(rec)
    n_dev = len(devices)
    if not devices and fallback:
        devices = {"/host:CPU(fallback)": fallback}
    return {"devices": devices, "steps": steps, "host": host, "device_planes": n_dev}


# -- reduction --------------------------------------------------------------------
def reduce_events(ev: Dict[str, Any], window: Optional[Interval] = None,
                  top: int = 10) -> Dict[str, Any]:
    """Busy seconds (mean over devices), window seconds, per-operation totals,
    longest gaps, exposed collective share, and the step count of the
    ``Steps`` line. ``window`` defaults to first-op-start .. last-op-end over
    all devices."""
    devs = ev["devices"]
    if not devs:
        raise ValueError("the trace has no device operations")
    if window is None:
        window = (min(s for ops in devs.values() for _, s, _ in ops),
                  max(e for ops in devs.values() for _, _, e in ops))
    lo, hi = window
    window_s = hi - lo
    busy_per_dev, exposed_per_dev = [], []
    op_totals: Dict[str, float] = {}
    gaps: List[Tuple[str, float]] = []
    for plane, ops in sorted(devs.items()):
        ops = sorted(((n, max(s, lo), min(e, hi)) for n, s, e in ops
                      if min(e, hi) > max(s, lo)), key=lambda r: r[1])
        busy = merge((s, e) for _, s, e in ops)
        busy_per_dev.append(total(busy))
        coll = merge((s, e) for n, s, e in ops if is_collective(n))
        rest = merge((s, e) for n, s, e in ops if not is_collective(n))
        exposed_per_dev.append(total(subtract(coll, rest)))
        for n, t in self_times(ops):
            key = base_name(n)
            op_totals[key] = op_totals.get(key, 0.0) + t / len(devs)
        if plane == sorted(devs)[0]:
            edges = [(lo, "window start")] + [(b, None) for _, b in busy]
            starts = [a for a, _ in busy] + [hi]
            spans = sorted(((g_hi - g_lo, g_lo, g_hi, label)
                            for (g_lo, label), g_hi in zip(edges, starts) if g_hi > g_lo),
                           reverse=True)[:top]
            names_at = {round(s, 9): n for n, s, _ in ops}
            ends_at = {round(e, 9): n for n, _, e in ops}
            for length, g_lo, g_hi, label in spans:  # only the longest are labelled
                before = label or base_name(ends_at.get(round(g_lo, 9), "?"))
                after = ("window end" if g_hi == hi
                         else base_name(names_at.get(round(g_hi, 9), "?")))
                doing = _host_activity(ev["host"], g_lo, g_hi)
                gaps.append((f"after {before} before {after}; host: {doing}", length))
    exposed = sorted(exposed_per_dev)[len(exposed_per_dev) // 2]
    n_steps = 0
    if ev["steps"]:
        first = ev["steps"][sorted(ev["steps"])[0]]
        n_steps = sum(1 for _, s, e in first if s >= lo and e <= hi)
    return {
        "busy_s": sum(busy_per_dev) / len(busy_per_dev),
        "window_s": window_s,
        "busy_s_per_device": busy_per_dev,
        "exposed_collective_s": exposed,
        "device_ops": [[n, t] for n, t in
                       sorted(op_totals.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[n, t] for n, t in sorted(gaps, key=lambda kv: -kv[1])[:top]],
        "steps": n_steps,
        "device_planes": ev["device_planes"],
    }


def _host_activity(host: Sequence[Tuple[str, float, float]], lo: float, hi: float) -> str:
    """The host span (annotation or runtime call) covering most of [lo, hi]."""
    best, best_t = "no span", 0.0
    for n, s, e in host:
        t = min(e, hi) - max(s, lo)
        if t > best_t:
            best, best_t = n, t
    return best[:60]


def reduce_trace(trace_dir: str, window: Optional[Interval] = None) -> Dict[str, Any]:
    return reduce_events(read_events(find_xplane(trace_dir)), window)
