"""Traffic kind ``closed_loop``: ``clients`` callers, each sending its next
request the instant its last one completes, as offline jobs do. There is no
rate: the queue is always full, so what is measured is what the system
completes. Load runs ``ramp_s`` before the window opens (set-up) so the slots
are full and past their first prefill burst. The rate is over all the work of
the window: every token a client received inside it, of requests that ended
in it, before it or not at all. Requests that *complete* inside the window are
the ones counted as attempted and checked; what is still in flight when it
closes is abandoned, neither counted nor failed. The generator's lag here is
how long a client's next request left after its last one ended.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List

from benchmark.traffic_kinds import serving


def _discipline(served: "serving.Served", requests: List[Dict[str, Any]], t0: float
                ) -> Dict[str, Any]:
    ctx, mix, client = served.ctx, served.ctx.mix, served.client
    w0 = t0 + float(mix.get("ramp_s", 0.0))
    w1 = w0 + ctx.seconds
    trace_at = w1 - float(mix["trace_s"]) if ctx.trace else None
    setup_s, trace_span, iters = None, None, {}
    nxt = 0
    lag_ms: List[float] = []  # a client's next request after its last one ended

    def launch():
        nonlocal nxt
        req = requests[nxt % len(requests)]
        nxt += 1
        now = time.perf_counter()
        client.launch(served.body(req), now, tag=req)

    for _ in range(int(mix["clients"])):
        launch()
    seen = 0
    while True:
        now = time.perf_counter()
        if now >= w1:
            break
        if setup_s is None and now >= w0:
            setup_s = ctx.since_process_start()
            served.poll(force=True)
        if trace_at is not None and trace_span is None and now >= trace_at:
            ctx.start_trace(ctx.trace_path())
            served.poll(force=True)
            trace_span = [time.perf_counter(), 0.0]
            iters["start"] = served.snapshots[-1]["busy_iterations"]
        client.pump(0.02)
        while seen < len(client.done):
            ended = client.done[seen]["end"]
            seen += 1
            launch()
            if ended is not None and ended >= w0:
                lag_ms.append(1e3 * (time.perf_counter() - ended))
        served.poll()
    if trace_span is not None:
        served.poll(force=True)
        iters["stop"] = served.snapshots[-1]["busy_iterations"]
        trace_span[1] = time.perf_counter()
        ctx.stop_trace(background=True)
    in_flight = client.abandon()
    counted = [r for r in client.done if r["end"] is not None and w0 <= r["end"] < w1]
    tokens = sum(1 for r in client.done + in_flight if not r["error"]
                 for t in r["token_times"] if w0 <= t < w1)
    rate = tokens / ctx.seconds
    ctx.say(f"closed loop: {mix['clients']} clients; {tokens} output tokens received in the "
            f"window, {rate:.1f} tokens/s; {len(counted)} requests completed in it with "
            f"{sum(len(r['token_ids']) for r in counted if not r['error'])} tokens; "
            f"{nxt} sent in all")
    serving.latency_summary(ctx, [r for r in counted if not r["error"]], w1)
    return {
        "counted": counted, "setup_s": setup_s,
        "end_to_end": {"serve_out_tokens_per_s": rate},
        "sources": {"window": (w0, w1), "trace_dir": ctx.trace_path() if trace_span else None,
                    "trace_span": trace_span, "trace_iterations": iters,
                    "latency": {"lag_ms": lag_ms} if lag_ms else None},
    }


def run(ctx) -> Dict[str, Any]:
    return serving.drive(ctx, _discipline)
