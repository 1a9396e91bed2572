"""graftprof: profile-driven step-time attribution from jax.profiler dumps.

MFU is one analytic number (obs/flops.py); this module answers where the
OTHER fraction of the step goes. It parses the Chrome-trace JSON that
``jax.profiler`` drops under ``<dump>/plugins/profile/<session>/
<host>.trace.json(.gz)`` — stdlib only, torn-file tolerant in the same
spirit as obs/events.py (a crash mid-dump loses the tail events, never
the report) — and attributes each training step's wall time into:

  compute   union of XLA op intervals classified as compute, split into
            families: matmul (dot/convolution/gemm), flash (attention
            kernels), gmm (grouped expert GEMMs), other
  comm      collectives by kind (all-gather / reduce-scatter /
            all-reduce / all-to-all / collective-permute / send / recv);
            the headline ``comm_frac`` counts only EXPOSED comm (not
            hidden under compute)
  host      infeed / outfeed / host transfer ops
  idle      step duration not covered by any device op

plus an **overlap fraction** from a concurrent-interval sweep: the share
of collective time that ran concurrently with compute (1.0 = perfectly
hidden, 0.0 = fully exposed). By construction, per step::

    compute_frac + comm_frac + host_frac + idle_frac == 1.0

(compute counts its full union; comm only its exposed remainder; host
only time outside both; idle is the uncovered residual.)

Steps come from ``jax.profiler.StepTraceAnnotation`` spans (the trainer
wraps every dispatch: ``args.step_num``); a trace with no step markers
is attributed as one synthetic step spanning its device ops. Multi-
device (and multi-host: several ``<host>.trace.json.gz`` in a session)
traces compute fractions per device lane and average them, so a report
from an 8-chip trace reads the same as a 1-chip one.

The optional ``analytic`` join turns time shares into achieved-vs-
analytic rates: matmul/flash families get achieved FLOP/s against the
obs/flops.py analytic cost, and collective kinds get achieved bytes/s
against the PR 12 collective-census budgets
(analysis/budgets/<config>.json). See analysis/prof.py for the CLI and
train/trainer.py for the auto-report on every profile capture.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re
from typing import Any, Dict, List, Optional, Tuple

REPORT_VERSION = 1
SUMMARY_FILENAME = "prof_summary.json"

# Fraction gauge / event-field names, in reporting order.
PROF_FIELDS = ("prof_compute_frac", "prof_comm_frac",
               "prof_overlap_frac", "prof_idle_frac")

# Collective op-name prefixes (HLO thunk names; ``-start`` async
# variants match by prefix, ``-done`` waits fold into the same kind).
COMM_KINDS = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "ragged-all-to-all", "collective-permute", "collective-broadcast",
    "send", "recv",
)

_NUM_SUFFIX = re.compile(r"[._]\d+$")
_DONE_SUFFIX = re.compile(r"-done$")


def base_op_name(name: str) -> str:
    """``%all-gather-start.12`` -> ``all-gather-start`` — the stable op
    identity the table aggregates on."""
    base = str(name).strip().lstrip("%").lower()
    while True:
        stripped = _NUM_SUFFIX.sub("", base)
        if stripped == base:
            return base
        base = stripped


def classify_op(name: str) -> Tuple[str, str]:
    """(category, family) for one op base name.

    category in {compute, comm, host}; family is the compute family
    (matmul/flash/gmm/other) or the collective kind or "host".
    """
    base = base_op_name(name)
    kind = _DONE_SUFFIX.sub("", base)
    for k in COMM_KINDS:
        if kind == k or kind.startswith(k + "-"):
            return "comm", k
    if base.startswith(("infeed", "outfeed")) or "host-transfer" in base:
        return "host", "host"
    if base.startswith(("dot", "convolution")) or "gemm" in base \
            or "matmul" in base:
        return "compute", "matmul"
    if "flash" in base or "attention" in base:
        return "compute", "flash"
    if "gmm" in base or "megablox" in base or "grouped" in base:
        return "compute", "gmm"
    return "compute", "other"


# -- trace file discovery -------------------------------------------------


def find_trace_files(path: str) -> List[str]:
    """Trace files for a dump dir, run dir, session dir, or direct file.

    A run dir contains ``profile/``; a dump dir contains
    ``plugins/profile/<session>/``; only the NEWEST session is used (a
    run that captured twice reports the latest window).
    """
    if os.path.isfile(path):
        return [path]
    if not os.path.isdir(path):
        return []
    roots = [path]
    sub = os.path.join(path, "profile")
    if os.path.isdir(sub):
        roots.append(sub)
    for root in roots:
        sessions = sorted(glob.glob(os.path.join(root, "plugins", "profile", "*")))
        sessions = [s for s in sessions if os.path.isdir(s)]
        if sessions:
            newest = max(sessions, key=os.path.getmtime)
            files = sorted(glob.glob(os.path.join(newest, "*.trace.json.gz"))
                           + glob.glob(os.path.join(newest, "*.trace.json")))
            if files:
                return files
        # A session dir (or plain dir of dumps) passed directly.
        files = sorted(glob.glob(os.path.join(root, "*.trace.json.gz"))
                       + glob.glob(os.path.join(root, "*.trace.json")))
        if files:
            return files
    return []


def _read_text(path: str) -> str:
    """Read a trace file, tolerating a torn gzip tail (crash mid-dump):
    whatever decompressed cleanly is returned."""
    if path.endswith(".gz"):
        chunks: List[bytes] = []
        try:
            with gzip.open(path, "rb") as f:
                while True:
                    chunk = f.read(1 << 20)
                    if not chunk:
                        break
                    chunks.append(chunk)
        except (EOFError, OSError, gzip.BadGzipFile):
            pass  # keep the prefix that decompressed
        return b"".join(chunks).decode("utf-8", errors="replace")
    with open(path, "r", encoding="utf-8", errors="replace") as f:
        return f.read()


def load_trace_events(path: str) -> Tuple[List[Dict[str, Any]], bool]:
    """(events, torn). A file that parses whole is not torn; otherwise
    complete event objects are salvaged from the ``traceEvents`` array
    one ``raw_decode`` at a time and the file is flagged torn — same
    reader ethos as obs/events.iter_events (skip the torn tail, keep
    everything before it)."""
    text = _read_text(path)
    try:
        doc = json.loads(text)
        events = doc.get("traceEvents", []) if isinstance(doc, dict) else doc
        return [e for e in events if isinstance(e, dict)], False
    except json.JSONDecodeError:
        pass
    # Salvage: locate the traceEvents array (or a bare array) and decode
    # objects until the torn tail refuses to parse.
    start = text.find('"traceEvents"')
    if start >= 0:
        start = text.find("[", start)
    elif text.lstrip().startswith("["):
        start = text.find("[")
    if start < 0:
        return [], True
    dec = json.JSONDecoder()
    events = []
    i = start + 1
    n = len(text)
    while i < n:
        while i < n and text[i] in " \t\r\n,":
            i += 1
        if i >= n or text[i] != "{":
            break
        try:
            obj, end = dec.raw_decode(text, i)
        except json.JSONDecodeError:
            break
        if isinstance(obj, dict):
            events.append(obj)
        i = end
    return events, True


# -- interval sweep -------------------------------------------------------


def _merge(iv: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    if not iv:
        return []
    iv = sorted(iv)
    out = [iv[0]]
    for s, e in iv[1:]:
        ls, le = out[-1]
        if s <= le:
            out[-1] = (ls, max(le, e))
        else:
            out.append((s, e))
    return out


def _total(merged: List[Tuple[float, float]]) -> float:
    return sum(e - s for s, e in merged)


def _intersect(a: List[Tuple[float, float]],
               b: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    i = j = 0
    while i < len(a) and j < len(b):
        s = max(a[i][0], b[j][0])
        e = min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _clip(iv: List[Tuple[float, float]], lo: float,
          hi: float) -> List[Tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in iv
            if max(s, lo) < min(e, hi)]


# -- attribution ----------------------------------------------------------


def _collect(trace_files: List[str]):
    """Flatten files into (device ops, step windows, torn_any).

    Device ops are X events that either carry ``args.hlo_op`` (CPU
    backend: ops run on host-pid executor threads) or sit on an "XLA
    Ops" lane of a ``/device:...`` pid (TPU/GPU). Device identity is
    ``(file_idx, pid)`` — pids from different hosts' dumps collide.
    Step windows come from X events with ``args.step_num``
    (StepTraceAnnotation), merged per step number across files.
    """
    ops: List[Dict[str, Any]] = []
    step_bounds: Dict[int, Tuple[float, float]] = {}
    torn_any = False
    for idx, path in enumerate(trace_files):
        events, torn = load_trace_events(path)
        torn_any = torn_any or torn
        proc_name: Dict[Any, str] = {}
        thread_name: Dict[Tuple[Any, Any], str] = {}
        for ev in events:
            if ev.get("ph") != "M":
                continue
            if ev.get("name") == "process_name":
                proc_name[ev.get("pid")] = str(
                    (ev.get("args") or {}).get("name", ""))
            elif ev.get("name") == "thread_name":
                thread_name[(ev.get("pid"), ev.get("tid"))] = str(
                    (ev.get("args") or {}).get("name", ""))
        for ev in events:
            if ev.get("ph") != "X":
                continue
            args = ev.get("args") or {}
            try:
                ts = float(ev.get("ts", 0.0))
                dur = float(ev.get("dur", 0.0))
            except (TypeError, ValueError):
                continue
            if "step_num" in args:
                try:
                    step = int(args["step_num"])
                except (TypeError, ValueError):
                    continue
                lo, hi = step_bounds.get(step, (ts, ts + dur))
                step_bounds[step] = (min(lo, ts), max(hi, ts + dur))
                continue
            if dur <= 0:
                continue
            is_device = "/device:" in proc_name.get(ev.get("pid"), "") \
                and "xla ops" in thread_name.get(
                    (ev.get("pid"), ev.get("tid")), "").lower()
            if "hlo_op" not in args and not is_device:
                continue
            name = str(args.get("hlo_op") or ev.get("name") or "?")
            cat, fam = classify_op(name)
            ops.append({"name": base_op_name(name), "cat": cat,
                        "fam": fam, "ts": ts, "end": ts + dur,
                        "dur": dur, "dev": (idx, ev.get("pid"))})
    return ops, step_bounds, torn_any


def attribute(trace_files: List[str],
              analytic: Optional[Dict[str, Any]] = None,
              top_k: int = 12) -> Optional[Dict[str, Any]]:
    """Parse + attribute. Returns the report dict, or None when the
    files contain no device ops at all (nothing to attribute)."""
    ops, step_bounds, torn = _collect(trace_files)
    if not ops:
        return None
    if not step_bounds:
        # No StepTraceAnnotation in the capture window: one synthetic
        # step spanning the device ops, so the fractions still read.
        step_bounds = {0: (min(o["ts"] for o in ops),
                           max(o["end"] for o in ops))}
    devices = sorted({o["dev"] for o in ops})
    by_dev: Dict[Any, List[Dict[str, Any]]] = {d: [] for d in devices}
    for o in ops:
        by_dev[o["dev"]].append(o)

    steps: List[Dict[str, Any]] = []
    for step in sorted(step_bounds):
        lo, hi = step_bounds[step]
        dur_us = hi - lo
        if dur_us <= 0:
            continue
        acc = {k: 0.0 for k in ("compute", "comm", "comm_exposed",
                                "host", "idle", "overlap", "busy")}
        fam_us: Dict[str, float] = {}
        kind_us: Dict[str, float] = {}
        for dev in devices:
            comp_iv, comm_iv, host_iv = [], [], []
            for o in by_dev[dev]:
                s, e = max(o["ts"], lo), min(o["end"], hi)
                if s >= e:
                    continue
                if o["cat"] == "comm":
                    comm_iv.append((s, e))
                    kind_us[o["fam"]] = kind_us.get(o["fam"], 0.0) + (e - s)
                elif o["cat"] == "host":
                    host_iv.append((s, e))
                else:
                    comp_iv.append((s, e))
                    fam_us[o["fam"]] = fam_us.get(o["fam"], 0.0) + (e - s)
            comp = _merge(comp_iv)
            comm = _merge(comm_iv)
            both = _merge(comp + comm)
            busy = _merge(comp + comm + host_iv)
            compute_s = _total(comp)
            comm_s = _total(comm)
            overlap_s = _total(_intersect(comp, comm))
            acc["compute"] += compute_s
            acc["comm"] += comm_s
            acc["overlap"] += overlap_s
            acc["comm_exposed"] += comm_s - overlap_s
            acc["host"] += _total(busy) - _total(both)
            acc["busy"] += _total(busy)
            acc["idle"] += dur_us - _total(busy)
        nd = len(devices)
        denom = dur_us * nd
        steps.append({
            "step": step,
            "dur_s": round(dur_us / 1e6, 6),
            "compute_s": round(acc["compute"] / nd / 1e6, 6),
            "comm_s": round(acc["comm"] / nd / 1e6, 6),
            "comm_exposed_s": round(acc["comm_exposed"] / nd / 1e6, 6),
            "host_s": round(acc["host"] / nd / 1e6, 6),
            "idle_s": round(acc["idle"] / nd / 1e6, 6),
            "overlap_s": round(acc["overlap"] / nd / 1e6, 6),
            "compute_frac": acc["compute"] / denom,
            "comm_frac": acc["comm_exposed"] / denom,
            "comm_total_frac": acc["comm"] / denom,
            "host_frac": acc["host"] / denom,
            "idle_frac": acc["idle"] / denom,
            "overlap_frac": (acc["overlap"] / acc["comm"]
                             if acc["comm"] > 0 else 0.0),
            "compute_by_family": {k: round(v / nd / 1e6, 6)
                                  for k, v in sorted(fam_us.items())},
            "comm_by_kind": {k: round(v / nd / 1e6, 6)
                             for k, v in sorted(kind_us.items())},
        })
    if not steps:
        return None

    # Duration-weighted aggregate: totals over totals, so long steps
    # dominate exactly as they do the wall clock. Fractions come from
    # the UNROUNDED per-step fracs (each exact by construction), so
    # compute+comm+host+idle still sums to 1.0 here, not 1.0±rounding.
    tot_dur = sum(s["dur_s"] for s in steps)
    agg: Dict[str, Any] = {"n_steps": len(steps),
                           "dur_s": round(tot_dur, 6)}
    for key in ("compute", "comm", "comm_exposed", "host", "idle",
                "overlap"):
        agg[key + "_s"] = round(sum(s[key + "_s"] for s in steps), 6)
    wsum = sum(s["dur_s"] for s in steps)
    for frac in ("compute_frac", "comm_frac", "comm_total_frac",
                 "host_frac", "idle_frac"):
        agg[frac] = sum(s[frac] * s["dur_s"] for s in steps) / wsum
    comm_w = sum(s["comm_total_frac"] * s["dur_s"] for s in steps)
    agg["overlap_frac"] = (
        sum(s["overlap_frac"] * s["comm_total_frac"] * s["dur_s"]
            for s in steps) / comm_w if comm_w > 0 else 0.0)

    report = {
        "version": REPORT_VERSION,
        "trace_files": [os.path.basename(p) for p in trace_files],
        "torn": torn,
        "n_devices": len(devices),
        "steps": steps,
        "aggregate": agg,
        "ops": _op_table(ops, step_bounds, len(devices), top_k),
        "families": _family_table(steps, analytic),
    }
    if analytic:
        report["analytic"] = {k: v for k, v in analytic.items()
                              if isinstance(v, (int, float, dict))}
    return report


def _op_table(ops, step_bounds, n_devices: int,
              top_k: int) -> List[Dict[str, Any]]:
    """Top-k ops by total time inside step windows, per-device-averaged
    share of step wall time attached."""
    windows = _merge(list(step_bounds.values()))
    tot_dur_us = _total(windows)
    by_name: Dict[str, Dict[str, Any]] = {}
    for o in ops:
        clipped = _total(_clip([(o["ts"], o["end"])], windows[0][0],
                               windows[-1][1])) if windows else o["dur"]
        if clipped <= 0:
            continue
        row = by_name.setdefault(o["name"], {
            "op": o["name"], "family": o["fam"], "category": o["cat"],
            "count": 0, "total_us": 0.0})
        row["count"] += 1
        row["total_us"] += clipped
    rows = sorted(by_name.values(), key=lambda r: -r["total_us"])[:top_k]
    out = []
    for r in rows:
        out.append({
            "op": r["op"], "family": r["family"],
            "category": r["category"], "count": r["count"],
            "total_s": round(r["total_us"] / 1e6, 6),
            "mean_us": round(r["total_us"] / r["count"], 2),
            "frac": (round(r["total_us"] / (tot_dur_us * n_devices), 6)
                     if tot_dur_us > 0 else 0.0),
        })
    return out


def _family_table(steps, analytic) -> Dict[str, Any]:
    """Per-family totals with achieved-vs-analytic joins: FLOP/s for the
    matmul/flash compute families (obs/flops.py analytic split), bytes/s
    for collective kinds (collective-census budgets)."""
    fam_s: Dict[str, float] = {}
    kind_s: Dict[str, float] = {}
    for st in steps:
        for k, v in st["compute_by_family"].items():
            fam_s[k] = fam_s.get(k, 0.0) + v
        for k, v in st["comm_by_kind"].items():
            kind_s[k] = kind_s.get(k, 0.0) + v
    n_steps = len(steps)
    an = analytic or {}
    toks = float(an.get("tokens_per_step") or 0.0)
    fam_flops = {
        "matmul": float(an.get("matmul_flops_per_token") or 0.0) * toks,
        "flash": float(an.get("attn_flops_per_token") or 0.0) * toks,
    }
    out: Dict[str, Any] = {"compute": {}, "comm": {}}
    for fam, secs in sorted(fam_s.items()):
        row: Dict[str, Any] = {"total_s": round(secs, 6)}
        flops_step = fam_flops.get(fam, 0.0)
        if flops_step > 0 and secs > 0:
            row["analytic_flops_per_step"] = flops_step
            # Global analytic FLOPs over summed per-device-mean seconds
            # = per-device achieved rate x device count: a fleet number
            # comparable against peak_flops_per_chip * n_chips.
            row["achieved_flops_per_s"] = round(flops_step * n_steps / secs, 3)
        out["compute"][fam] = row
    bytes_by_kind = dict(an.get("collective_bytes_per_step") or {})
    for kind, secs in sorted(kind_s.items()):
        row = {"total_s": round(secs, 6)}
        b = float(bytes_by_kind.get(kind) or 0.0)
        if b > 0 and secs > 0:
            row["bytes_per_step"] = b
            row["achieved_bytes_per_s"] = round(b * n_steps / secs, 3)
        out["comm"][kind] = row
    return out


# -- entry points ---------------------------------------------------------


def generate_report(dump_or_file: str,
                    analytic: Optional[Dict[str, Any]] = None,
                    top_k: int = 12) -> Optional[Dict[str, Any]]:
    """Find trace files under ``dump_or_file`` and attribute them.
    Returns None when no trace files (or no device ops) are found."""
    files = find_trace_files(dump_or_file)
    if not files:
        return None
    report = attribute(files, analytic=analytic, top_k=top_k)
    if report is not None:
        report["dump"] = os.path.abspath(dump_or_file)
    return report


def prof_fields(report: Dict[str, Any], digits: int = 4) -> Dict[str, float]:
    """The four headline fractions under their gauge / event-field
    names (PROF_FIELDS)."""
    agg = report["aggregate"]
    return {
        "prof_compute_frac": round(agg["compute_frac"], digits),
        "prof_comm_frac": round(agg["comm_frac"], digits),
        "prof_overlap_frac": round(agg["overlap_frac"], digits),
        "prof_idle_frac": round(agg["idle_frac"], digits),
    }


def format_report(report: Dict[str, Any]) -> List[str]:
    """key=value lines (scripts/trace_report.py idiom): header, per-step
    table, aggregate, family joins, top-k op table."""
    agg = report["aggregate"]
    lines = [
        f"graftprof=1 files={len(report['trace_files'])} "
        f"torn={int(report['torn'])} devices={report['n_devices']} "
        f"steps={agg['n_steps']}"
    ]
    for st in report["steps"]:
        lines.append(
            f"step={st['step']} dur_ms={round(st['dur_s'] * 1e3, 3)} "
            f"compute_frac={round(st['compute_frac'], 4)} "
            f"comm_frac={round(st['comm_frac'], 4)} "
            f"host_frac={round(st['host_frac'], 4)} "
            f"idle_frac={round(st['idle_frac'], 4)} "
            f"overlap_frac={round(st['overlap_frac'], 4)} "
            f"comm_total_frac={round(st['comm_total_frac'], 4)}")
    lines.append(
        f"aggregate=1 dur_ms={round(agg['dur_s'] * 1e3, 3)} "
        f"compute_frac={round(agg['compute_frac'], 4)} "
        f"comm_frac={round(agg['comm_frac'], 4)} "
        f"host_frac={round(agg['host_frac'], 4)} "
        f"idle_frac={round(agg['idle_frac'], 4)} "
        f"overlap_frac={round(agg['overlap_frac'], 4)} "
        f"comm_total_frac={round(agg['comm_total_frac'], 4)}")
    fams = report.get("families") or {}
    for fam, row in (fams.get("compute") or {}).items():
        extra = ""
        if "achieved_flops_per_s" in row:
            extra = (f" achieved_tflops="
                     f"{round(row['achieved_flops_per_s'] / 1e12, 3)}")
        lines.append(f"family={fam} total_ms="
                     f"{round(row['total_s'] * 1e3, 3)}{extra}")
    for kind, row in (fams.get("comm") or {}).items():
        extra = ""
        if "achieved_bytes_per_s" in row:
            extra = (f" bytes_per_step={int(row['bytes_per_step'])} "
                     f"achieved_gbps="
                     f"{round(row['achieved_bytes_per_s'] / 1e9, 3)}")
        lines.append(f"comm_kind={kind} total_ms="
                     f"{round(row['total_s'] * 1e3, 3)}{extra}")
    for op in report.get("ops") or []:
        lines.append(
            f"op={op['op']} family={op['family']} count={op['count']} "
            f"total_ms={round(op['total_s'] * 1e3, 3)} "
            f"mean_us={op['mean_us']} frac={round(op['frac'], 4)}")
    return lines


def write_summary(report: Dict[str, Any], path: str) -> str:
    """Atomic JSON summary write (temp + rename, the repo-wide pattern:
    readers never see a torn summary)."""
    tmp = path + ".tmp"
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(report, f, indent=1, sort_keys=True)
        f.write("\n")
    os.replace(tmp, path)
    return path
