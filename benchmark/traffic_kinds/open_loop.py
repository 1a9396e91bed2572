"""Traffic kind ``open_loop``: requests are sent on a schedule fixed by the
mix's rate whether or not earlier ones have finished, as independent users
send them. Load starts ``ramp_s`` before the window opens (counted as set-up)
so the window sees a settled queue; every request *due* inside the window is
counted, and the run goes on after the window (at most ``drain_s``) until those
have finished. Latency runs from the due instant, so a stall of the server or
of this generator is charged to the requests it delayed.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List

from benchmark import loadgen, synthetic
from benchmark.traffic_kinds import serving


def _discipline(served: "serving.Served", requests: List[Dict[str, Any]], t0: float
                ) -> Dict[str, Any]:
    ctx, mix, client = served.ctx, served.ctx.mix, served.client
    due = synthetic.poisson_arrivals(mix, ctx.seconds, ctx.seed)
    w0 = t0 + float(mix.get("ramp_s", 0.0))
    w1 = w0 + ctx.seconds
    trace_at = w1 - float(mix["trace_s"]) if ctx.trace else None
    hard_stop = w1 + float(mix["drain_s"])
    setup_s, trace_span, iters = None, None, {}
    counted: List[Dict[str, Any]] = []
    i = 0
    while True:
        now = time.perf_counter()
        if setup_s is None and now >= w0:
            setup_s = ctx.since_process_start()
            served.poll(force=True)
        if trace_at is not None and trace_span is None and now >= trace_at:
            ctx.start_trace(ctx.trace_path())
            served.poll(force=True)
            trace_span = [time.perf_counter(), 0.0]
            iters["start"] = served.snapshots[-1]["busy_iterations"]
        if trace_span is not None and not trace_span[1] and now >= w1:
            served.poll(force=True)
            iters["stop"] = served.snapshots[-1]["busy_iterations"]
            trace_span[1] = time.perf_counter()
            ctx.stop_trace(background=True)
        while i < len(due) and t0 + due[i] <= now:
            req = requests[i % len(requests)]
            rec = client.launch(served.body(req), t0 + due[i], tag=req)
            if w0 <= rec["due"] < w1:
                counted.append(rec)
            i += 1
        if i >= len(due) and now >= w1 and all(r["end"] is not None for r in counted):
            break
        if now >= hard_stop:
            break
        next_due = t0 + due[i] if i < len(due) else now + 0.05
        client.pump(min(max(next_due - now, 0.0), 0.02))
        if now < w1:
            served.poll()
    end = time.perf_counter()
    for rec in client.abandon():
        if rec in counted:
            rec["error"] = f"unfinished {mix['drain_s']} s after the window closed"
    lat = serving.latency_summary(ctx, counted, end)
    backlog = [s.get("queue_depth", 0) + s.get("batch_occupancy", 0)
               for s in served.snapshots if w0 <= s["t"] <= w1]
    ctx.say(f"open loop: {mix['rate_per_s']} requests/s fixed; {len(counted)} due in the "
            f"window, {i} sent in all; requests in the system at window start "
            f"{backlog[0] if backlog else '?'} and end {backlog[-1] if backlog else '?'}, "
            f"highest {max(backlog) if backlog else '?'}")
    return {
        "counted": counted, "setup_s": setup_s,
        "end_to_end": {"ttft_p95_ms": loadgen.percentile(lat["ttft_ms"], 0.95),
                       "itl_p95_ms": loadgen.percentile(lat["itl_ms"], 0.95)},
        "sources": {"window": (w0, w1), "latency": lat, "trace_dir": ctx.trace_path()
                    if trace_span else None, "trace_span": trace_span,
                    "trace_iterations": iters, "backlog": backlog},
    }


def run(ctx) -> Dict[str, Any]:
    return serving.drive(ctx, _discipline)
