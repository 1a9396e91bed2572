#!/usr/bin/env python
"""Concurrent HTTP load generator for the inference server.

Drives N worker threads against ``POST /generate`` (infer/server.py) and
prints one JSON summary line: request counts by status (200 / 429 / 504 /
other), end-to-end latency percentiles, TTFT and per-token decode
latency percentiles (p50/p95/p99 — the numbers that separate a paged
pool from a slotted one under mixed-length traffic), client-side token
throughput, and the server's /metrics snapshot after the run.
Stdlib-only, so it runs anywhere the repo does:

    python scripts/load_gen.py --url http://127.0.0.1:8400 \
        --concurrency 8 --requests 64 --max-tokens 32

Point it at a ``--engine locked`` server and then a ``--engine batch``
one to see continuous batching under identical offered load.

Shared-prefix workload (``--shared-prefix-tokens N --prefix-groups G``):
every request's prompt starts with one of G fixed ~N-token prefixes
(the byte-fallback tokenizer is ~1 token/char), modelling templated
traffic — system prompts, few-shot headers. Against a prefix-caching
server the summary splits TTFT p50/p95 by cache hit vs miss (the server
reports ``prefix_cached_tokens`` per request) and adds the aggregate
``cache_hit_rate``; against the router (serve/router.py) each group is
consistently hashed to one replica, so hits land where the blocks live.

Mixed flood (``--mix prefill-heavy:decode-heavy``): interleaves traffic
classes with opposite resource profiles — ``prefill-heavy`` sends a long
unique prompt and asks for a few tokens (compute-bound, the disaggregated
fleet's prefill-pool diet), ``decode-heavy`` a short prompt with a long
generation (bandwidth-bound; its TTFT is what prefill interference
destroys on a homogeneous replica). Class weights repeat via ``*N``
(``prefill-heavy*2:decode-heavy``); shapes via ``--mix-*`` flags. The
summary gains per-class TTFT and TPOT (per-output-token decode latency)
p50/p95/p99, the numbers that score a prefill/decode fleet against a
homogeneous baseline.

Per-request tracing (``--trace-out FILE``): writes one CSV row per
request with the server-minted trace id and the server-side TTFT
breakdown (queue_ms / prefill_ms / decode_ms) that the batch engine
attaches to every response. Join the ``trace_id`` column against the
chrome traces dumped by the router's and replicas' ``/trace``
endpoints (scripts/trace_report.py does the merge) to see where each
slow request actually spent its time.
"""

from __future__ import annotations

import argparse
import csv
import json
import threading
import time
import urllib.error
import urllib.request

TRACE_FIELDS = ("trace_id", "status", "latency_s", "ttft_ms", "queue_ms",
                "prefill_ms", "decode_ms", "tokens", "prompt_tokens",
                "cached_tokens", "cls")

# --mix class shapes: (prompt tokens, generated tokens). ~1 token/char
# under the byte-fallback tokenizer; prompts are unique per request (the
# request id leads) so prefill work is real, not a prefix-cache hit.
MIX_SHAPES = {
    "prefill-heavy": (512, 8),
    "decode-heavy": (16, 128),
}


def parse_mix(spec: str) -> list:
    """``a:b*2:c`` -> ["a", "b", "b", "c"] (the round-robin schedule)."""
    classes = []
    for part in spec.split(":"):
        part = part.strip()
        if not part:
            continue
        name, _, weight = part.partition("*")
        classes.extend([name] * max(1, int(weight or 1)))
    if not classes:
        raise ValueError(f"empty --mix spec {spec!r}")
    return classes


def class_prompt(cls: str, i: int, tokens: int) -> str:
    """Unique ~``tokens``-token prompt for request ``i`` of class
    ``cls``: the id comes FIRST so no two prompts share a KV block —
    prefill cost is genuine, not amortized by the prefix cache."""
    stem = f"[{cls} {i}] measure the fleet under mixed load; "
    reps = -(-tokens // len(stem))
    return (stem * reps)[:tokens]


def _one_request(url: str, body: dict, timeout: float) -> dict:
    data = json.dumps(body).encode()
    req = urllib.request.Request(url.rstrip("/") + "/generate", data=data,
                                 headers={"Content-Type": "application/json"})
    t0 = time.monotonic()
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            out = json.loads(resp.read())
            # The batch engine reports server-side TTFT; per-token decode
            # latency is the post-first-token time spread over the rest.
            ttft = out.get("ttft_ms")
            return {"status": resp.status, "latency_s": time.monotonic() - t0,
                    "tokens": int(out.get("tokens", 0)),
                    "ttft_s": ttft / 1e3 if ttft is not None else None,
                    "prompt_tokens": float(out.get("prompt_tokens", 0.0)),
                    "cached_tokens": float(
                        out.get("prefix_cached_tokens", 0.0)),
                    "trace_id": out.get("trace_id"),
                    "queue_ms": out.get("queue_ms"),
                    "prefill_ms": out.get("prefill_ms"),
                    "decode_ms": out.get("decode_ms")}
    except urllib.error.HTTPError as e:
        return {"status": e.code, "latency_s": time.monotonic() - t0,
                "tokens": 0, "ttft_s": None, "prompt_tokens": 0.0,
                "cached_tokens": 0.0, "trace_id": None, "queue_ms": None,
                "prefill_ms": None, "decode_ms": None}
    except Exception as e:  # noqa: BLE001 - count it, keep loading
        return {"status": f"error:{type(e).__name__}",
                "latency_s": time.monotonic() - t0, "tokens": 0,
                "ttft_s": None, "prompt_tokens": 0.0, "cached_tokens": 0.0,
                "trace_id": None, "queue_ms": None, "prefill_ms": None,
                "decode_ms": None}


def group_prefix(group: int, tokens: int) -> str:
    """Deterministic ~``tokens``-token shared prefix for one group (the
    byte-fallback tokenizer maps ~1 token per char)."""
    stem = f"[group {group}] shared context block; "
    reps = -(-tokens // len(stem))
    return (stem * reps)[:tokens]


def run_load(url: str, concurrency: int, requests: int, prompt: str,
             max_tokens: int, temperature: float, deadline_s: float | None,
             timeout: float, shared_prefix_tokens: int = 0,
             prefix_groups: int = 1, trace_out: str | None = None,
             mix: str | None = None,
             mix_shapes: dict | None = None,
             alerts_url: str | None = None) -> dict:
    results: list = []
    lock = threading.Lock()
    counter = iter(range(requests))
    schedule = parse_mix(mix) if mix else None
    shapes = {**MIX_SHAPES, **(mix_shapes or {})}

    def worker():
        while True:
            with lock:
                i = next(counter, None)
            if i is None:
                return
            cls = None
            if schedule is not None:
                cls = schedule[i % len(schedule)]
                if cls not in shapes:
                    raise ValueError(f"unknown --mix class {cls!r} "
                                     f"(known: {sorted(shapes)})")
                p_toks, g_toks = shapes[cls]
                body = {"prompt": class_prompt(cls, i, p_toks),
                        "max_tokens": g_toks,
                        "temperature": temperature, "seed": i}
            else:
                head = (group_prefix(i % max(prefix_groups, 1),
                                     shared_prefix_tokens)
                        if shared_prefix_tokens > 0 else "")
                body = {"prompt": f"{head}{prompt} [{i}]",
                        "max_tokens": max_tokens,
                        "temperature": temperature, "seed": i}
            if deadline_s is not None:
                body["deadline_s"] = deadline_s
            r = _one_request(url, body, timeout)
            r["cls"] = cls
            with lock:
                results.append(r)

    threads = [threading.Thread(target=worker, daemon=True)
               for _ in range(concurrency)]
    t0 = time.monotonic()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.monotonic() - t0

    by_status: dict = {}
    for r in results:
        by_status[str(r["status"])] = by_status.get(str(r["status"]), 0) + 1
    # Chaos-drill rollup: every request must land in exactly one bucket
    # (ok + refused + expired + error == requests — nothing hung). 429
    # and 504 are the CLEAN degradation outcomes; "error" is anything
    # else (5xx, connection failures, client timeouts).
    outcomes = {"ok": 0, "429": 0, "504": 0, "error": 0}
    for r in results:
        s = r["status"]
        key = ("ok" if s == 200 else str(s) if s in (429, 504) else "error")
        outcomes[key] += 1
    ok = [r for r in results if r["status"] == 200]
    lats = sorted(r["latency_s"] for r in ok)
    ttfts = sorted(r["ttft_s"] for r in ok if r["ttft_s"] is not None)
    # Per-token decode latency per request: everything after the first
    # token, normalized by the tokens it produced. Falls back to
    # whole-request normalization when the server (locked engine) does
    # not report TTFT.
    per_tok = sorted(
        ((r["latency_s"] - r["ttft_s"]) / max(r["tokens"] - 1, 1)
         if r["ttft_s"] is not None
         else r["latency_s"] / max(r["tokens"], 1))
        for r in ok if r["tokens"] > 0)

    def pct(vals, p: float, digits: int = 3) -> float | None:
        if not vals:
            return None
        return round(vals[min(len(vals) - 1, int(p * len(vals)))], digits)

    toks = sum(r["tokens"] for r in results)
    summary = {
        "url": url, "concurrency": concurrency, "requests": requests,
        "max_tokens": max_tokens, "wall_s": round(wall, 2),
        "by_status": by_status,
        "outcomes": outcomes,
        "completed": len(results),
        "ok": by_status.get("200", 0),
        "latency_p50_s": pct(lats, 0.50), "latency_p90_s": pct(lats, 0.90),
        "latency_p95_s": pct(lats, 0.95), "latency_p99_s": pct(lats, 0.99),
        "latency_max_s": round(lats[-1], 3) if lats else None,
        "ttft_p50_s": pct(ttfts, 0.50), "ttft_p95_s": pct(ttfts, 0.95),
        "ttft_p99_s": pct(ttfts, 0.99),
        "tok_latency_p50_s": pct(per_tok, 0.50, 5),
        "tok_latency_p95_s": pct(per_tok, 0.95, 5),
        "tok_latency_p99_s": pct(per_tok, 0.99, 5),
        "client_tok_s": round(toks / wall, 1) if wall > 0 else None,
    }
    if shared_prefix_tokens > 0:
        # Hit = the server adopted cached prefix blocks for the request.
        hit_t = sorted(r["ttft_s"] for r in ok
                       if r["ttft_s"] is not None and r["cached_tokens"] > 0)
        miss_t = sorted(r["ttft_s"] for r in ok
                        if r["ttft_s"] is not None
                        and r["cached_tokens"] == 0)
        offered = sum(r["prompt_tokens"] for r in ok)
        cached = sum(r["cached_tokens"] for r in ok)
        summary.update({
            "shared_prefix_tokens": shared_prefix_tokens,
            "prefix_groups": prefix_groups,
            "cache_hits": len(hit_t), "cache_misses": len(miss_t),
            "cache_hit_rate": (round(cached / offered, 4) if offered else 0.0),
            "ttft_hit_p50_s": pct(hit_t, 0.50),
            "ttft_hit_p95_s": pct(hit_t, 0.95),
            "ttft_miss_p50_s": pct(miss_t, 0.50),
            "ttft_miss_p95_s": pct(miss_t, 0.95),
        })
    if schedule is not None:
        # Per-class TTFT/TPOT tails: decode-heavy TTFT p99 is THE number
        # disaggregation exists to protect (prefill interference lands
        # there first); prefill-heavy TTFT tracks prompt-pass throughput.
        def tpot(r) -> float | None:
            if r["tokens"] <= 0:
                return None
            if r["ttft_s"] is not None:
                return (r["latency_s"] - r["ttft_s"]) / max(r["tokens"] - 1,
                                                            1)
            return r["latency_s"] / max(r["tokens"], 1)

        per_class = {}
        for cls in dict.fromkeys(schedule):
            rs = [r for r in results if r["cls"] == cls]
            ok_c = [r for r in rs if r["status"] == 200]
            t = sorted(r["ttft_s"] for r in ok_c if r["ttft_s"] is not None)
            d = sorted(v for v in (tpot(r) for r in ok_c) if v is not None)
            p_toks, g_toks = shapes[cls]
            per_class[cls] = {
                "requests": len(rs), "ok": len(ok_c),
                "prompt_tokens": p_toks, "gen_tokens": g_toks,
                "ttft_p50_s": pct(t, 0.50), "ttft_p95_s": pct(t, 0.95),
                "ttft_p99_s": pct(t, 0.99),
                "tpot_p50_s": pct(d, 0.50, 5), "tpot_p95_s": pct(d, 0.95, 5),
                "tpot_p99_s": pct(d, 0.99, 5),
            }
        summary["mix"] = per_class
    if trace_out:
        # One row per request, in completion order. ttft_ms mirrors the
        # server value; queue/prefill/decode are the server's own
        # monotonic-stamp breakdown, so the columns sum to ~latency
        # minus network + client overhead.
        with open(trace_out, "w", newline="") as fh:
            w = csv.DictWriter(fh, fieldnames=TRACE_FIELDS,
                               extrasaction="ignore")
            w.writeheader()
            for r in results:
                row = dict(r)
                row["latency_s"] = round(r["latency_s"], 4)
                row["ttft_ms"] = (round(r["ttft_s"] * 1e3, 2)
                                  if r["ttft_s"] is not None else "")
                for k in ("trace_id", "queue_ms", "prefill_ms", "decode_ms",
                          "cls"):
                    if row.get(k) is None:
                        row[k] = ""
                w.writerow(row)
        summary["trace_out"] = trace_out
        summary["traced_requests"] = sum(
            1 for r in results if r.get("trace_id"))
    try:
        with urllib.request.urlopen(url.rstrip("/") + "/metrics",
                                    timeout=10) as resp:
            summary["server_metrics"] = json.loads(resp.read())
    except Exception:  # noqa: BLE001 - summary is still useful without it
        pass
    # graftscope rollup next to the outcome counts: which SLO rules were
    # firing when the run ended. Same tolerance as server_metrics — no
    # collector (or no /alerts route on the target), no keys.
    try:
        with urllib.request.urlopen(
                (alerts_url or url).rstrip("/") + "/alerts",
                timeout=10) as resp:
            doc = json.loads(resp.read())
        firing = sorted(str(al.get("rule", "?"))
                        for al in doc.get("alerts", [])
                        if isinstance(al, dict)
                        and al.get("state") == "firing")
        summary["alerts_firing"] = len(firing)
        summary["alerts_firing_rules"] = firing
    except Exception:  # noqa: BLE001 - alerts are optional evidence
        pass
    return summary


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--url", default="http://127.0.0.1:8400")
    p.add_argument("--concurrency", type=int, default=8)
    p.add_argument("--requests", type=int, default=32)
    p.add_argument("--prompt", default="The quick brown fox")
    p.add_argument("--max-tokens", type=int, default=32)
    p.add_argument("--temperature", type=float, default=0.0)
    p.add_argument("--deadline-s", type=float, default=None,
                   help="per-request deadline passed to the batch engine")
    p.add_argument("--timeout", type=float, default=300.0,
                   help="client-side HTTP timeout per request")
    p.add_argument("--shared-prefix-tokens", type=int, default=0,
                   help="prepend a ~N-token group-shared prefix to every "
                        "prompt (0 = off); TTFT is then split by prefix-"
                        "cache hit vs miss")
    p.add_argument("--prefix-groups", type=int, default=1,
                   help="number of distinct shared prefixes the requests "
                        "rotate through")
    p.add_argument("--trace-out", default=None, metavar="FILE",
                   help="write a per-request CSV (trace_id + server-side "
                        "queue/prefill/decode breakdown) to FILE")
    p.add_argument("--mix", default=None, metavar="SPEC",
                   help="mixed flood: colon-separated traffic classes "
                        "round-robined across requests, e.g. "
                        "'prefill-heavy:decode-heavy' (weights via *N); "
                        "overrides --prompt/--max-tokens and reports "
                        "per-class TTFT/TPOT p50/p95/p99")
    p.add_argument("--mix-prefill-prompt", type=int, default=512,
                   help="prefill-heavy class: ~prompt tokens per request")
    p.add_argument("--mix-prefill-gen", type=int, default=8,
                   help="prefill-heavy class: generated tokens per request")
    p.add_argument("--mix-decode-prompt", type=int, default=16,
                   help="decode-heavy class: ~prompt tokens per request")
    p.add_argument("--mix-decode-gen", type=int, default=128,
                   help="decode-heavy class: generated tokens per request")
    p.add_argument("--alerts-url", default=None,
                   help="graftscope collector base URL for the end-of-run "
                        "firing-alert count (default: --url, which only "
                        "answers when the target itself serves /alerts)")
    a = p.parse_args(argv)
    summary = run_load(a.url, a.concurrency, a.requests, a.prompt,
                       a.max_tokens, a.temperature, a.deadline_s, a.timeout,
                       shared_prefix_tokens=a.shared_prefix_tokens,
                       prefix_groups=a.prefix_groups, trace_out=a.trace_out,
                       mix=a.mix, mix_shapes={
                           "prefill-heavy": (a.mix_prefill_prompt,
                                             a.mix_prefill_gen),
                           "decode-heavy": (a.mix_decode_prompt,
                                            a.mix_decode_gen)},
                       alerts_url=a.alerts_url)
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
