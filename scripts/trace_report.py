#!/usr/bin/env python
"""Merge chrome-trace dumps into per-request span trees and reports.

Input: one or more chrome trace-event JSON files as produced by the
tracing ring buffers (obs/trace.py) — the router's ``GET /trace``, each
replica's ``GET /trace``, and the trainer's ``trace_step<N>.json`` /
``trace.json`` exports. Spans from different processes share a
wall-clock timeline and are joined by the ``trace_id`` each span
carries in its args (minted by the router, propagated via the
``X-Trace-Id`` header), so a single request's ``route`` span on the
router nests the ``queue_wait`` / ``engine.prefill_chunk`` / ``decode`` spans
recorded on whichever replica served it:

    python scripts/trace_report.py router_trace.json \
        replica0_trace.json replica1_trace.json --top 3

Prints, in ``key=value`` form:
  * an accounting line — how many requests completed, how many
    ``route`` spans never matched a replica-side ``request`` span
    (anything non-zero there means a replica dropped its ring or died),
    and how many requests were disaggregated handoffs (a prefill
    replica's and a decode replica's ``request`` spans joined under one
    trace id, with the ``kv_transfer`` push between them);
  * per-component TTFT breakdown percentiles (queue_wait, prefill,
    decode, route overhead) across all completed requests;
  * the top-k slowest requests, each with its indented span tree;
  * trainer step-time attribution — per-phase totals of the loop's live
    phases (train.data_get / train.dispatch / train.loss_sync /
    train.log_window / checkpoint_save / eval: the names a jax.profiler
    trace of the same run carries), and inside train.data_get the waits
    the prefetch worker measured (data_wait / h2d_wait), next to the MFU
    the ``step_window`` instants reported — when a trainer trace file is
    among the inputs;
  * with ``--run-dir <run>``: the run's own trace exports join the
    inputs automatically, and when the run holds a jax.profiler dump
    (``<run>/profile/``) the graftprof op-level attribution
    (obs/profile_report.py: compute/comm/host/idle fractions, overlap,
    top-k ops) is appended — ledger-, span-, and op-level views of the
    same step window from one command.

Stdlib-only: runs on dumped JSON anywhere, no repo install needed (the
graftprof fold imports the in-repo package via a repo-root fallback and
degrades to a note if unavailable).
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
from typing import Any, Dict, List, Optional

# Trainer phase span names (obs/trace.py phase(): the loop's live spans,
# which do not overlap), then the prefetch worker's waits, recorded
# after the fact inside train.data_get and so left out of the booked sum.
TRAIN_PHASES = ("train.data_get", "train.dispatch", "train.loss_sync",
                "train.log_window", "checkpoint_save", "eval")
TRAIN_WAITS = ("data_wait", "h2d_wait")
# Request-path component span names emitted by serve/engine.py +
# serve/router.py (+ the prefill->decode KV push from infer/server.py
# in a disaggregated fleet).
REQUEST_COMPONENTS = ("queue_wait", "engine.prefill_chunk", "decode",
                      "kv_transfer")
# Wall-clock slack (µs) tolerated when nesting spans from different
# processes: their timelines share one wall anchor but not one clock.
EPS_US = 500.0


def load_trace(path: str) -> Dict[str, Any]:
    with open(path) as fh:
        doc = json.load(fh)
    if isinstance(doc, list):  # bare event-array form is also legal
        doc = {"traceEvents": doc, "metadata": {}}
    return doc


def service_of(doc: Dict[str, Any], fallback: str) -> str:
    svc = (doc.get("metadata") or {}).get("service")
    if svc:
        return str(svc)
    for ev in doc.get("traceEvents", []):
        if ev.get("ph") == "M" and ev.get("name") == "process_name":
            return str((ev.get("args") or {}).get("name", fallback))
    return fallback


def collect(paths: List[str]):
    """Flatten files into (spans, instants, per-file stats)."""
    spans: List[Dict[str, Any]] = []
    instants: List[Dict[str, Any]] = []
    stats: List[Dict[str, Any]] = []
    for path in paths:
        doc = load_trace(path)
        svc = service_of(doc, path)
        meta = doc.get("metadata") or {}
        stats.append({"file": path, "service": svc,
                      "dropped": int(meta.get("dropped", 0)),
                      "events": len(doc.get("traceEvents", []))})
        for ev in doc.get("traceEvents", []):
            if ev.get("ph") == "X":
                spans.append({"name": ev.get("name", "?"),
                              "ts": float(ev.get("ts", 0.0)),
                              "dur": float(ev.get("dur", 0.0)),
                              "service": svc,
                              "args": ev.get("args") or {}})
            elif ev.get("ph") == "i":
                instants.append({"name": ev.get("name", "?"),
                                 "ts": float(ev.get("ts", 0.0)),
                                 "service": svc,
                                 "args": ev.get("args") or {}})
    return spans, instants, stats


def by_trace_id(events: List[Dict[str, Any]]) -> Dict[str, list]:
    groups: Dict[str, list] = {}
    for ev in events:
        tid = ev["args"].get("trace_id")
        if tid:
            groups.setdefault(str(tid), []).append(ev)
    return groups


def build_tree(spans: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """Nest one request's spans by time containment (stack walk over
    spans sorted by start, longest-first on ties). Returns roots; each
    node gains a ``children`` list."""
    order = sorted(spans, key=lambda s: (s["ts"], -s["dur"]))
    roots: List[Dict[str, Any]] = []
    stack: List[Dict[str, Any]] = []
    for s in order:
        s = dict(s, children=[])
        while stack and stack[-1]["ts"] + stack[-1]["dur"] + EPS_US < \
                s["ts"] + s["dur"]:
            stack.pop()
        if stack:
            stack[-1]["children"].append(s)
        else:
            roots.append(s)
        stack.append(s)
    return roots


def render_tree(node: Dict[str, Any], t0: float, depth: int = 0) -> List[str]:
    extra = " ".join(
        f"{k}={v}" for k, v in sorted(node["args"].items())
        if k != "trace_id" and isinstance(v, (int, float, str)))
    line = ("  " * (depth + 1)
            + f"span={node['name']} service={node['service']} "
            + f"start_ms={round((node['ts'] - t0) / 1e3, 2)} "
            + f"dur_ms={round(node['dur'] / 1e3, 2)}"
            + (f" {extra}" if extra else ""))
    out = [line]
    for c in node["children"]:
        out.extend(render_tree(c, t0, depth + 1))
    return out


def pct(vals: List[float], p: float, digits: int = 2) -> Optional[float]:
    if not vals:
        return None
    vals = sorted(vals)
    return round(vals[min(len(vals) - 1, int(p * len(vals)))], digits)


def _fmt(v) -> str:
    return "unknown" if v is None else str(v)


def request_report(spans, top: int) -> List[str]:
    groups = by_trace_id(spans)
    # A request is "complete" when a replica recorded its terminal
    # `request` span; `route` spans with no matching request span mean
    # the replica side was lost (ring overwrite, crash, still running).
    # A disaggregated handoff records TWO request spans under one trace
    # id — the prefill replica's prefill-only pass, then the decode
    # replica's full request — so the terminal span is the LATEST-ending
    # one; the earlier ones are the handoff legs, joined in the same
    # tree with the `kv_transfer` push between them.
    complete: Dict[str, Dict[str, Any]] = {}
    routed_only = 0
    handoffs = 0
    kv_pushes = 0
    for tid, evs in groups.items():
        req = [e for e in evs if e["name"] == "request"]
        route = [e for e in evs if e["name"] == "route"]
        if req:
            complete[tid] = {
                "evs": evs,
                "req": max(req, key=lambda e: e["ts"] + e["dur"]),
                "route": route[0] if route else None}
            if len(req) > 1:
                handoffs += 1
            if any(e["name"] == "kv_transfer" for e in evs):
                kv_pushes += 1
        elif route:
            routed_only += 1
    lines = [f"requests_complete={len(complete)} "
             f"route_unmatched={routed_only} "
             f"handoffs={handoffs} kv_transfers={kv_pushes} "
             f"trace_ids_seen={len(groups)}"]

    comp_ms: Dict[str, List[float]] = {}
    totals: List[tuple] = []
    for tid, g in complete.items():
        per = {}
        for e in g["evs"]:
            if e["name"] in REQUEST_COMPONENTS:
                key = ("prefill" if e["name"] == "engine.prefill_chunk"
                       else e["name"])
                per[key] = per.get(key, 0.0) + e["dur"] / 1e3
        if g["route"] is not None:
            # Router-side time not booked on the replica: network,
            # header shuffling, stream piping.
            per["route_overhead"] = max(
                0.0, (g["route"]["dur"] - g["req"]["dur"]) / 1e3)
        for k, v in per.items():
            comp_ms.setdefault(k, []).append(v)
        ttft = per.get("queue_wait", 0.0) + per.get("prefill", 0.0)
        comp_ms.setdefault("ttft", []).append(ttft)
        totals.append((g["req"]["dur"] / 1e3, tid, g))
    for name in ("ttft", "queue_wait", "prefill", "kv_transfer", "decode",
                 "route_overhead"):
        vals = comp_ms.get(name, [])
        if not vals:
            continue
        lines.append(f"component={name} count={len(vals)} "
                     f"p50_ms={_fmt(pct(vals, 0.50))} "
                     f"p95_ms={_fmt(pct(vals, 0.95))} "
                     f"max_ms={_fmt(round(max(vals), 2))}")

    totals.sort(reverse=True)
    for rank, (dur_ms, tid, g) in enumerate(totals[:max(top, 0)], 1):
        root_evs = g["evs"]
        lines.append(f"slow_rank={rank} trace_id={tid} "
                     f"total_ms={round(dur_ms, 2)} "
                     f"replica={g['req']['service']}")
        t0 = min(e["ts"] for e in root_evs)
        for root in build_tree(root_evs):
            lines.extend(render_tree(root, t0))
    return lines


def trainer_report(spans, instants) -> List[str]:
    # Group by service: a multi-host run exports one trace per host
    # (heartbeat_p<idx> naming on the run dir side), and summing phase
    # time across hosts would double-book wall clock that elapsed in
    # parallel. Single-host traces produce one group and no service= key.
    svc_spans: Dict[str, List[Dict[str, Any]]] = {}
    for s in spans:
        if s["name"] in TRAIN_PHASES or s["name"] in TRAIN_WAITS:
            svc_spans.setdefault(s["service"], []).append(s)
    if not svc_spans:
        return []
    multi = len(svc_spans) > 1
    lines: List[str] = []
    for svc in sorted(svc_spans):
        phase_s: Dict[str, float] = {}
        t_min, t_max = None, None
        for s in svc_spans[svc]:
            phase_s[s["name"]] = phase_s.get(s["name"], 0.0) + s["dur"] / 1e6
            lo, hi = s["ts"], s["ts"] + s["dur"]
            t_min = lo if t_min is None else min(t_min, lo)
            t_max = hi if t_max is None else max(t_max, hi)
        wall = (t_max - t_min) / 1e6 if t_max is not None else 0.0
        wins = [i for i in instants if i["name"] == "step_window"
                and (not multi or i["service"] == svc)]
        mfus = [float(i["args"]["mfu"]) for i in wins
                if isinstance(i["args"].get("mfu"), (int, float))]
        booked = sum(v for k, v in phase_s.items() if k in TRAIN_PHASES)
        tag = f"service={svc} " if multi else ""
        lines.append(
            f"trainer_attribution=1 {tag}"
            f"windows={len(wins)} "
            f"mfu_mean={_fmt(round(sum(mfus) / len(mfus), 4) if mfus else None)} "
            f"booked_s={round(booked, 3)} "
            f"span_wall_s={round(wall, 3)}")
        for name in TRAIN_PHASES + TRAIN_WAITS:
            if name not in phase_s:
                continue
            lines.append(
                f"phase={name} {tag}total_s={round(phase_s[name], 3)} "
                f"share={round(phase_s[name] / booked, 4) if booked else 0.0}")
    return lines


def graftprof_report(run_dir: str) -> List[str]:
    """graftprof fold: when the run dir holds a jax.profiler dump
    (``<run_dir>/profile/plugins/profile/...``), append the op-level
    attribution (obs/profile_report.py) under the span-level one, so a
    single command shows ledger-, span-, and op-level views of the same
    step window. Quiet when there is no dump; degrades to a note when
    the package is not importable (this script runs uninstalled — the
    repo-root fallback covers in-tree use)."""
    try:
        try:
            from mlx_cuda_distributed_pretraining_tpu.obs import (
                profile_report)
        except ImportError:
            sys.path.insert(0, os.path.dirname(
                os.path.dirname(os.path.abspath(__file__))))
            from mlx_cuda_distributed_pretraining_tpu.obs import (
                profile_report)
    except ImportError:
        return ["graftprof=0 reason=package_unavailable"]
    try:
        rep = profile_report.generate_report(run_dir)
    except Exception as e:  # noqa: BLE001 - fold is best-effort
        return [f"graftprof=0 reason=error detail={type(e).__name__}"]
    if rep is None:
        return []
    return profile_report.format_report(rep)


def run_dir_traces(run_dir: str) -> List[str]:
    """Span-trace exports a trainer run dir is known to hold."""
    out: List[str] = []
    for pat in ("trace.json", "trace_p*.json", "trace_step*.json"):
        out.extend(sorted(glob.glob(os.path.join(run_dir, pat))))
    return out


def report(paths: List[str], top: int = 5,
           run_dir: Optional[str] = None) -> List[str]:
    spans, instants, stats = collect(paths)
    lines = []
    for st in stats:
        lines.append(f"trace_file={st['file']} service={st['service']} "
                     f"events={st['events']} dropped={st['dropped']}")
    lines.extend(request_report(spans, top))
    lines.extend(trainer_report(spans, instants))
    if run_dir:
        lines.extend(graftprof_report(run_dir))
    return lines


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("traces", nargs="*",
                   help="chrome trace JSON files (/trace dumps, trainer "
                        "trace_step*.json)")
    p.add_argument("--run-dir", default=None,
                   help="trainer run dir: its trace.json/trace_step*.json "
                        "exports join the inputs, and a jax.profiler dump "
                        "under <run-dir>/profile gets the graftprof "
                        "op-level attribution appended")
    p.add_argument("--top", type=int, default=5,
                   help="how many slowest requests to print as span trees")
    a = p.parse_args(argv)
    traces = list(a.traces)
    if a.run_dir:
        traces.extend(t for t in run_dir_traces(a.run_dir)
                      if t not in traces)
    if not traces and not a.run_dir:
        p.error("give trace files and/or --run-dir")
    for line in report(traces, top=a.top, run_dir=a.run_dir):
        print(line)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
