"""From a profiler trace (``.xplane.pb``) to device time per scope of the
program, and host time per phase of its loop.

The program opens a closed vocabulary of ``jax.named_scope``s (and names its
Pallas calls); JAX writes the name stack into each HLO instruction's
``op_name``, and the TPU profiler keeps it on the event's *metadata* as the
stat ``tf_op``; the metadata's own name is the instruction's text, with the
shapes of its result. ``jax.profiler.ProfileData``
(what ``trace_reduce.py`` reads with) exposes an event's own stats only, so
this file decodes the six protobuf messages it needs (XSpace, XPlane, XLine,
XEvent, XEventMetadata, XStat; tsl/profiler/protobuf/xplane.proto) from the
wire format itself: no TensorFlow or xprof import, whatever the machine has.

A name stack looks like
``jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/rematted_computation/layer/attn_core/flash_fwd/pallas_call:``
and the outermost scope is wrapped by the transform
(``jvp(lm_head_ce)/while/...``), so it is split on ``/``, ``(`` and ``)``
and the *innermost* vocabulary name is the operation's scope.

``XLA Ops`` is clipped to the whole events of the ``Steps`` line and reduced
by self time exactly as ``trace_reduce.self_times`` does, so the scopes
(with ``unscoped``) add up to the busy time of those steps. A trace of a
program that opens no scope (the parent of the PR that added them) reads
all of its time as unscoped; nothing here raises on it.
"""

from __future__ import annotations

import functools
import os
import re
import sys
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)  # run as a script: python benchmark/trace_scopes.py <file>

from benchmark import trace_reduce  # noqa: E402

# The program's scopes (README "Reading a profile"), training and serving.
VOCABULARY = (
    "embed", "layer", "norm", "attn_qkv", "attn_core", "attn_out", "ffn",
    "moe_router", "moe_experts", "final_norm", "lm_head_ce",
    "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv", "gmm", "tgmm",
    "grad_accum", "grad_clip", "optimizer", "kv_gather", "sample",
)
UNSCOPED = "unscoped"
REMAT = "rematted_computation"
_SPLIT = re.compile(r"[/()]")
_DIMS = re.compile(r"\[([\d,]+)\]")


def scope_of(op_name: str) -> str:
    """Innermost vocabulary name of a name stack, else ``unscoped``."""
    for token in reversed(_SPLIT.split(op_name)):
        if token in VOCABULARY:
            return token
    return UNSCOPED


def is_recompute(op_name: str) -> bool:
    return REMAT in _SPLIT.split(op_name)


# -- protobuf wire format -----------------------------------------------------------
def _varint(buf, pos: int) -> Tuple[int, int]:
    result = shift = 0
    while True:
        b = buf[pos]
        pos += 1
        result |= (b & 0x7F) << shift
        if b < 0x80:
            return result, pos
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, int, Any]]:
    """(field number, wire type, value) of one message: an int for varint and
    fixed-width fields, a memoryview for length-delimited ones."""
    pos, end = 0, len(buf)
    while pos < end:
        key, pos = _varint(buf, pos)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, pos = _varint(buf, pos)
        elif wire == 2:
            n, pos = _varint(buf, pos)
            value = buf[pos:pos + n]
            pos += n
        elif wire == 1:
            value = int.from_bytes(buf[pos:pos + 8], "little")
            pos += 8
        elif wire == 5:
            value = int.from_bytes(buf[pos:pos + 4], "little")
            pos += 4
        else:
            raise ValueError(f"wire type {wire} at byte {pos}: not an xplane file")
        yield field, wire, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _stat(buf) -> Tuple[int, Any]:
    """(stat metadata id, value): strings as str, ``ref_value`` as ("ref", id)."""
    meta_id, value = 0, None
    for f, _, v in _fields(buf):
        if f == 1:
            meta_id = v
        elif f in (3, 4):
            value = v
        elif f == 5:
            value = _text(v)
        elif f == 7:
            value = ("ref", v)
    return meta_id, value


def _map_entry(buf) -> Tuple[int, Any]:
    key, value = 0, None
    for f, _, v in _fields(buf):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def _event(buf) -> Tuple[int, int, int]:
    meta_id = offset_ps = duration_ps = 0
    for f, _, v in _fields(buf):
        if f == 1:
            meta_id = v
        elif f == 2:
            offset_ps = v
        elif f == 3:
            duration_ps = v
    return meta_id, offset_ps, duration_ps


def _plane(buf, want_lines: Optional[Sequence[str]], want_stats: Sequence[str]) -> Dict[str, Any]:
    """{"name", "lines": {line name: [(metadata id, start_s, end_s)]},
    "events": {metadata id: {"name", "display", <wanted stats>}}}; only the
    lines named in ``want_lines`` (all if None) are decoded."""
    name, lines_raw, meta_raw, stat_names = "", [], [], {}
    for f, _, v in _fields(buf):
        if f == 2:
            name = _text(v)
        elif f == 3:
            lines_raw.append(v)
        elif f == 4:
            meta_raw.append(v)
        elif f == 5:
            key, sm = _map_entry(v)
            for g, _, w in _fields(sm):
                if g == 2:
                    stat_names[key] = _text(w)
    lines: Dict[str, List[Tuple[int, float, float]]] = {}
    for raw in lines_raw:
        line_name, t0_ns, events = "", 0, []
        for f, _, v in _fields(raw):
            if f == 2:
                line_name = _text(v)
            elif f == 3:
                t0_ns = v
            elif f == 4:
                events.append(v)
        if want_lines is not None and line_name not in want_lines:
            continue
        out = lines.setdefault(line_name, [])
        for ev in events:
            meta_id, off_ps, dur_ps = _event(ev)
            start = t0_ns * 1e-9 + off_ps * 1e-12
            out.append((meta_id, start, start + dur_ps * 1e-12))
    used = {m for evs in lines.values() for m, _, _ in evs}
    events_meta: Dict[int, Dict[str, Any]] = {}
    for raw in meta_raw:
        key, em = _map_entry(raw)
        if key not in used or em is None:
            continue
        rec: Dict[str, Any] = {"name": "", "display": ""}
        for f, _, v in _fields(em):
            if f == 2:
                rec["name"] = _text(v)
            elif f == 4:
                rec["display"] = _text(v)
            elif f == 5:
                sid, value = _stat(v)
                sname = stat_names.get(sid)
                if sname in want_stats:
                    if isinstance(value, tuple):
                        value = stat_names.get(value[1], "")
                    rec[sname] = value
        events_meta[key] = rec
    return {"name": name, "lines": lines, "events": events_meta}


def read_planes(path: str) -> Dict[str, Any]:
    """{"devices": [plane], "host": [plane]} of one ``.xplane.pb``: the TPU
    planes' ``XLA Ops`` and ``Steps`` lines, every line of the host plane."""
    with open(path, "rb") as f:
        data = memoryview(f.read())
    devices, host = [], []
    for f_no, _, plane in _fields(data):
        if f_no != 1:
            continue
        pname = next((_text(v) for g, _, v in _fields(plane) if g == 2), "")
        if pname.startswith("/device:") and "TPU" in pname.upper():
            devices.append(_plane(plane, (trace_reduce.OPS_LINE, trace_reduce.STEPS_LINE),
                                  ("tf_op",)))
        elif pname.startswith("/host:"):
            host.append(_plane(plane, None, ()))
    return {"devices": devices, "host": host}


# -- reduction ----------------------------------------------------------------------
@functools.lru_cache(maxsize=4)
def reduce(path: str) -> Dict[str, Any]:
    """Seconds and event counts per scope over the whole steps of the trace,
    mean over devices: {"steps", "window_s", "busy_s", "scope_s", "scope_events",
    "recompute_s", "recompute_by_scope_s", "kernels": {name: {"calls",
    "seconds", "dims"}}, "host_s": {event name: seconds inside the steps'
    window}}. Where the trace has no ``Steps`` line (a serving run: the
    engine's iterations are host annotations) ``steps`` is 0 and the sums run
    over everything on ``XLA Ops``: totals of the traced window, for the
    table ``main`` prints; the readers take nothing from such a trace."""
    planes = read_planes(path)
    out: Dict[str, Any] = {"steps": 0, "window_s": 0.0, "busy_s": 0.0, "scope_s": {},
                           "scope_events": {}, "recompute_s": 0.0,
                           "recompute_by_scope_s": {}, "kernels": {}, "host_s": {}}
    devs = [p for p in planes["devices"] if p["lines"].get(trace_reduce.STEPS_LINE)]
    clip = trace_reduce.STEPS_LINE
    if not devs:
        devs = [p for p in planes["devices"] if p["lines"].get(trace_reduce.OPS_LINE)]
        clip = trace_reduce.OPS_LINE
    if not devs:
        return out
    n = len(devs)
    lo = min(s for p in devs for _, s, _ in p["lines"][clip])
    hi = max(e for p in devs for _, _, e in p["lines"][clip])
    if clip == trace_reduce.STEPS_LINE:
        out["steps"] = len(devs[0]["lines"][clip])
    out["window_s"] = hi - lo

    def add(d: Dict[str, float], k: str, v: float) -> None:
        d[k] = d.get(k, 0.0) + v

    for p in devs:
        meta = p["events"]
        ops = [(m, max(s, lo), min(e, hi))
               for m, s, e in p["lines"].get(trace_reduce.OPS_LINE, [])
               if min(e, hi) > max(s, lo)]
        for m, t in trace_reduce.self_times(ops):
            rec = meta.get(m, {})
            op_name = rec.get("tf_op") or ""
            scope = scope_of(op_name)
            out["busy_s"] += t / n
            add(out["scope_s"], scope, t / n)
            add(out["scope_events"], scope, 1.0 / n)
            if is_recompute(op_name):
                out["recompute_s"] += t / n
                add(out["recompute_by_scope_s"], scope, t / n)
            if "pallas_call" in op_name and scope != UNSCOPED:
                k = out["kernels"].setdefault(scope, {"calls": 0.0, "seconds": 0.0, "dims": None})
                k["calls"] += 1.0 / n
                k["seconds"] += t / n
                # "%flash_fwd.17 = (bf16[4,32,4096,128]{...}, f32[...]) custom-call(...)"
                dims = _DIMS.search(rec.get("name", "").partition(" = ")[2])
                if dims:
                    k["dims"] = [int(x) for x in dims.group(1).split(",")]
    for p in planes["host"]:
        for evs in p["lines"].values():
            for m, s, e in evs:
                if lo <= s < hi and e > s:
                    add(out["host_s"], p["events"].get(m, {}).get("name", ""), e - s)
    return out


# -- what the readers under layer_metrics/ share ------------------------------------
def of_run(sources: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """The reduction of a traced run's file, or None where there is no trace,
    no ``Steps`` line or no device plane (a CPU rehearsal)."""
    trace_dir = sources.get("trace_dir")
    if not trace_dir:
        return None
    try:
        red = reduce(trace_reduce.find_xplane(trace_dir))
    except (FileNotFoundError, ValueError, IndexError):
        return None
    return red if red["steps"] and red["busy_s"] > 0 else None


def step_ms(sources: Dict[str, Any], scopes: Sequence[str]) -> Optional[float]:
    """Device milliseconds a step under ``scopes`` (innermost), or None where
    no operation of the trace carries any of them."""
    red = of_run(sources)
    if red is None or not any(s in red["scope_s"] for s in scopes):
        return None
    return 1e3 * sum(red["scope_s"].get(s, 0.0) for s in scopes) / red["steps"]


def kernel_peak_pct(sources: Dict[str, Any], kernels: Sequence[str], flops_of) -> Optional[float]:
    """Operations the calls of ``kernels`` executed in the whole steps, by
    ``flops_of[kernel](B, Hq, S, D)`` on the dimensions the trace records for
    the call's first output, over their self time, over the chip's bf16 peak."""
    red, peaks = of_run(sources), sources.get("peaks")
    if red is None or not peaks:
        return None
    flops = seconds = 0.0
    for k in kernels:
        rec = red["kernels"].get(k)
        if rec is None or not rec["dims"] or len(rec["dims"]) != 4 or rec["seconds"] <= 0:
            return None
        flops += rec["calls"] * flops_of[k](*rec["dims"])
        seconds += rec["seconds"]
    return 100.0 * flops / seconds / peaks["bf16_flops"]


def main(argv: List[str]) -> int:
    """``python benchmark/trace_scopes.py <file.xplane.pb>``: the table."""
    red = reduce(argv[1])
    n = max(red["steps"], 1)
    print(f"{red['steps']} whole step(s), busy {red['busy_s']:.6f} s of {red['window_s']:.6f} s"
          + ("" if red["steps"] else ": no Steps line, so the rows below are totals of the window"))
    for scope, secs in sorted(red["scope_s"].items(), key=lambda kv: -kv[1]):
        print(f"scope {scope:16s} {1e3 * secs / n:10.3f} ms/step {100 * secs / red['busy_s']:6.2f}%  "
              f"{red['scope_events'][scope] / n:8.1f} events/step  "
              f"recomputed {1e3 * red['recompute_by_scope_s'].get(scope, 0.0) / n:8.3f} ms/step")
    print(f"recompute {1e3 * red['recompute_s'] / n:.3f} ms/step")
    for k, rec in sorted(red["kernels"].items()):
        print(f"kernel {k}: {rec['calls'] / n:.1f} calls/step, {1e3 * rec['seconds'] / n:.3f} ms/step, "
              f"first output {rec['dims']}")
    for name, secs in sorted(red["host_s"].items(), key=lambda kv: -kv[1])[:25]:
        print(f"host {1e3 * secs / n:10.3f} ms/step  {name}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
