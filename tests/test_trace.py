"""End-to-end tracing (obs/trace.py and its integrations).

Three layers:
  1. Recorder semantics — bounded ring with drop accounting, disabled
     path allocating nothing, deterministic per-trace-id sampling,
     Chrome trace-event document shape.
  2. Serving fleet — one X-Trace-Id names a request across the
     router->replica hop (real in-process HTTP servers), /trace dumps
     merge into per-request span trees with every completed request
     accounted for, and the response body carries the server-side
     queue/prefill/decode breakdown that load_gen's --trace-out CSV and
     the TTFT histograms are built from.
  3. Trainer — per-phase span sums reconcile with the goodput ledger on
     a short CPU run (the spans carry the ledger's own numbers, so the
     match is by construction, and the test pins that construction).
"""

import json
import math
import os
import urllib.request

import jax
import pytest
from conftest import load_script

from mlx_cuda_distributed_pretraining_tpu.config import Config, DataConfig
from mlx_cuda_distributed_pretraining_tpu.infer.server import (
    InferenceService,
    serve,
)
from mlx_cuda_distributed_pretraining_tpu.models import llama
from mlx_cuda_distributed_pretraining_tpu.models.llama import LlamaArgs
from mlx_cuda_distributed_pretraining_tpu.obs.metrics import (
    quantile_from_buckets,
)
from mlx_cuda_distributed_pretraining_tpu.obs.trace import (
    TRACE_HEADER,
    Tracer,
    merge_chrome_traces,
    new_trace_id,
    sampled,
)
from mlx_cuda_distributed_pretraining_tpu.serve import (
    BatchEngine,
    EngineConfig,
    Router,
    serve_router,
)
from mlx_cuda_distributed_pretraining_tpu.tokenizer import TokenizerManager

TOK = TokenizerManager(DataConfig())
ARGS = LlamaArgs(
    vocab_size=TOK.vocab_size, hidden_size=32, intermediate_size=64,
    num_layers=2, num_heads=4, num_kv_heads=2, head_dim=8,
    max_position_embeddings=128,
)
PARAMS = llama.init_params(jax.random.PRNGKey(0), ARGS)


# -- recorder semantics (no device) -------------------------------------------

def test_disabled_tracer_is_allocation_free_and_silent():
    tr = Tracer("t", enabled=False)
    a = tr.span("x")
    b = tr.span("y", trace_id=new_trace_id())
    assert a is b  # the shared null singleton, no Span allocated
    assert a.end() == 0.0
    with tr.span("z"):
        pass
    tr.complete("w", 0.5)
    tr.instant("i")
    assert tr.stats() == {"recorded": 0, "dropped": 0, "buffered": 0}
    assert tr.chrome_trace()["traceEvents"][0]["ph"] == "M"  # metadata only
    assert len(tr.chrome_trace()["traceEvents"]) == 1


def test_ring_overwrites_oldest_and_counts_drops():
    tr = Tracer("t", capacity=4)
    for i in range(10):
        tr.complete(f"s{i}", 0.001)
    st = tr.stats()
    assert st == {"recorded": 10, "dropped": 6, "buffered": 4}
    names = [e["name"] for e in tr.chrome_events() if e["ph"] == "X"]
    assert names == ["s6", "s7", "s8", "s9"]  # newest 4, oldest first
    doc = tr.chrome_trace()
    assert doc["metadata"]["dropped"] == 6
    # drain empties the ring but keeps lifetime counters
    assert len(tr.drain()) == 4
    assert tr.stats() == {"recorded": 10, "dropped": 6, "buffered": 0}


def test_span_records_once_and_complete_places_by_end_mono():
    tr = Tracer("t")
    with tr.span("ctx", step=1):
        pass
    s = tr.span("manual", trace_id="f" * 32)
    s.end(extra=7)
    s.end()  # idempotent: second end records nothing
    tr.complete("booked", 0.25, end_mono=10.0)
    evs = [e for e in tr.chrome_events() if e["ph"] == "X"]
    assert [e["name"] for e in evs] == ["ctx", "manual", "booked"]
    assert evs[0]["args"] == {"step": 1}
    assert evs[1]["args"] == {"extra": 7, "trace_id": "f" * 32}
    booked = evs[2]
    assert booked["dur"] == 250_000  # the identical measured duration
    # placed ending at end_mono: ts = wall(end_mono - dur)
    assert booked["ts"] == tr._wall_us(10.0 - 0.25)
    assert tr.stats()["recorded"] == 3


def test_sampling_is_deterministic_per_trace_id():
    assert sampled("anything", 1.0) and not sampled("anything", 0.0)
    assert sampled("not-hex!", 0.5)  # malformed ids err toward tracing
    ids = [new_trace_id() for _ in range(200)]
    kept = [t for t in ids if sampled(t, 0.5)]
    assert 0 < len(kept) < len(ids)  # a fraction, not all-or-nothing
    # every process holding the same id reaches the same verdict
    for t in ids:
        assert sampled(t, 0.5) == sampled(t, 0.5)
    tr = Tracer("t", sample=0.0)
    assert tr.span("s", trace_id=ids[0]).end() == 0.0
    tr.complete("s", 0.1, trace_id=ids[0])
    assert tr.stats()["recorded"] == 0
    # spans WITHOUT a trace id (trainer phases) are always recorded
    tr.complete("phase", 0.1)
    assert tr.stats()["recorded"] == 1


def test_merge_chrome_traces_concatenates_timelines():
    a, b = Tracer("a"), Tracer("b")
    a.complete("x", 0.01)
    b.complete("y", 0.01)
    merged = merge_chrome_traces([a.chrome_trace(), b.chrome_trace()])
    names = {e["name"] for e in merged["traceEvents"] if e.get("ph") == "X"}
    assert names == {"x", "y"}
    procs = {e["args"]["name"] for e in merged["traceEvents"]
             if e.get("ph") == "M"}
    assert procs == {"a", "b"}


def test_quantile_from_buckets_upper_bound_estimate():
    rows = [[1.0, 5], [5.0, 9], ["+Inf", 10]]
    assert quantile_from_buckets(rows, 10, 0.5) == 1.0
    assert quantile_from_buckets(rows, 10, 0.9) == 5.0
    # observations past the last finite bound report that bound
    assert quantile_from_buckets(rows, 10, 0.99) == 5.0
    assert quantile_from_buckets(rows, 0, 0.5) is None
    assert quantile_from_buckets([], 10, 0.5) is None


# -- serving fleet ------------------------------------------------------------

def _engine(**kw):
    cfg = EngineConfig(**{"num_slots": 2, "max_len": 128,
                          "prefill_chunk": 16, **kw})
    return BatchEngine(PARAMS, ARGS, TOK, cfg)


def _replica(**kw):
    service = InferenceService(PARAMS, ARGS, TOK, run_name="tiny")
    service.engine = _engine(**kw).start()
    httpd = serve(service, port=0)
    return service, httpd, f"http://127.0.0.1:{httpd.server_address[1]}"


def _get_json(url, timeout=30.0):
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return json.loads(resp.read())


def test_engine_tracing_spans_breakdown_and_ttft_histograms():
    eng = _engine(trace=True).start()
    try:
        out = eng.generate("the quick brown fox", max_tokens=6,
                           temperature=0.0, timeout=300.0)
    finally:
        eng.stop()
    # response carries the minted id + the monotonic-stamp breakdown
    assert len(out["trace_id"]) == 32
    assert out["queue_ms"] >= 0.0
    assert out["prefill_ms"] >= 0.0 and out["decode_ms"] >= 0.0
    assert out["ttft_ms"] == pytest.approx(
        out["queue_ms"] + out["prefill_ms"], abs=0.1)
    # spans cover the request lifecycle, all keyed by the one id
    spans = [e for e in eng.tracer.chrome_events() if e.get("ph") == "X"]
    by_name = {}
    for e in spans:
        by_name.setdefault(e["name"], []).append(e)
    for name in ("queue_wait", "engine.prefill_chunk", "decode", "request"):
        assert name in by_name, f"missing span {name}"
        assert all(e["args"]["trace_id"] == out["trace_id"]
                   for e in by_name[name])
    # stream_emit instants mark SSE pushes only, so a buffered generate
    # records just the admission marker
    instants = {e["name"] for e in eng.tracer.chrome_events()
                if e.get("ph") == "i"}
    assert "kv_alloc" in instants
    # the terminal request span nests the component spans (one timeline)
    req = by_name["request"][0]
    for name in ("queue_wait", "engine.prefill_chunk", "decode"):
        for e in by_name[name]:
            assert e["ts"] >= req["ts"] - 1000
            assert e["ts"] + e["dur"] <= req["ts"] + req["dur"] + 1000
    # the same components feed the bounded histograms
    snap = eng.metrics_registry.snapshot()
    assert snap["serve_ttft_ms"]["series"][0]["count"] >= 1
    comps = {s["labels"]["component"]
             for s in snap["serve_ttft_component_ms"]["series"]}
    assert {"queue", "prefill", "decode"} <= comps
    assert eng._ttft_quantiles().keys() == {
        "ttft_ms_p50", "ttft_ms_p95", "ttft_ms_p99",
        "ttft_ms_sum", "ttft_ms_count"}


def test_engine_tracing_disabled_records_nothing_on_hot_path():
    eng = _engine().start()  # trace defaults off
    try:
        out = eng.generate("the quick brown fox", max_tokens=4,
                           temperature=0.0, timeout=300.0)
    finally:
        eng.stop()
    assert not eng.cfg.trace
    assert eng.tracer.stats() == {"recorded": 0, "dropped": 0, "buffered": 0}
    # ids and the TTFT breakdown still flow — they cost no span objects
    assert len(out["trace_id"]) == 32
    assert out["queue_ms"] >= 0.0


def test_router_propagates_one_trace_id_and_report_merges(tmp_path):
    sa, ha, ua = _replica(trace=True)
    sb, hb, ub = _replica(trace=True)
    router = Router([ua, ub], poll_interval_s=0.1, retries=2, trace=True)
    rhttpd = serve_router(router, port=0)
    url = f"http://127.0.0.1:{rhttpd.server_address[1]}"
    try:
        # flood through the router with load_gen, CSV capture on
        load_gen = load_script("load_gen")
        csv_path = str(tmp_path / "requests.csv")
        summary = load_gen.run_load(
            url, concurrency=2, requests=5, prompt="the quick brown fox",
            max_tokens=4, temperature=0.0, deadline_s=None, timeout=300.0,
            trace_out=csv_path)
        assert summary["ok"] == 5 and summary["traced_requests"] == 5
        # plus one request with a client-minted id: it must survive the
        # router hop and come back in both body and response header
        mine = new_trace_id()
        req = urllib.request.Request(
            url + "/generate",
            data=json.dumps({"prompt": "trace me", "max_tokens": 4,
                             "temperature": 0.0}).encode(),
            headers={"Content-Type": "application/json",
                     TRACE_HEADER: mine})
        with urllib.request.urlopen(req, timeout=300.0) as resp:
            assert resp.headers.get(TRACE_HEADER) == mine
            out = json.loads(resp.read())
        assert out["trace_id"] == mine

        # CSV: one row per request, trace ids filled, breakdown numeric
        with open(csv_path) as f:
            lines = [ln.strip().split(",") for ln in f if ln.strip()]
        header, rows = lines[0], lines[1:]
        assert header[:2] == ["trace_id", "status"] and len(rows) == 5
        idx = {k: i for i, k in enumerate(header)}
        for row in rows:
            assert len(row[idx["trace_id"]]) == 32
            assert float(row[idx["queue_ms"]]) >= 0.0
            assert float(row[idx["prefill_ms"]]) >= 0.0

        # dump every ring and merge by id
        paths = []
        for name, u in (("router", url), ("r0", ua), ("r1", ub)):
            doc = _get_json(u + "/trace")
            p = tmp_path / f"{name}.json"
            p.write_text(json.dumps(doc))
            paths.append(str(p))
        docs = [json.loads(open(p).read()) for p in paths]
        route_ids = {e["args"]["trace_id"]
                     for e in docs[0]["traceEvents"] if e.get("ph") == "X"}
        request_ids = {e["args"]["trace_id"]
                       for d in docs[1:] for e in d["traceEvents"]
                       if e.get("ph") == "X" and e["name"] == "request"}
        csv_ids = {row[idx["trace_id"]] for row in rows} | {mine}
        # one id names each request on BOTH sides of the hop
        assert csv_ids <= route_ids
        assert csv_ids <= request_ids

        report = load_script("trace_report").report(paths, top=2)
        acct = next(ln for ln in report
                    if ln.startswith("requests_complete="))
        assert "requests_complete=6" in acct
        assert "route_unmatched=0" in acct  # every request accounted for
        assert any(ln.startswith("component=queue_wait") for ln in report)
        assert any(ln.startswith("component=prefill") for ln in report)
        # the slowest-request tree nests replica spans under the router's
        i_route = next(i for i, ln in enumerate(report)
                       if ln.lstrip().startswith("span=route"))
        assert report[i_route].startswith("  span=route")
        i_req = next(i for i, ln in enumerate(report[i_route:])
                     if ln.lstrip().startswith("span=request")) + i_route
        assert report[i_req].startswith("    span=request")
    finally:
        rhttpd.shutdown()
        rhttpd.server_close()
        router.stop()
        for s, h in ((sa, ha), (sb, hb)):
            s.close()
            h.shutdown()
            h.server_close()


# -- trainer ------------------------------------------------------------------

def _tiny_cfg_dict(tmp_path, name, iters, **extra):
    train = tmp_path / "train.jsonl"
    if not train.exists():
        with open(train, "w") as f:
            for _ in range(40):
                f.write(json.dumps(
                    {"text": "the quick brown fox jumps over the lazy dog "
                             * 4}) + "\n")
    d = {
        "name": name,
        "overwrite": True,
        "data": {
            "input_file": str(train),
            "preprocessing": {"max_context_size": 64},
            "tokenizer": {"normal_vocab_size": 256},
        },
        "model": {
            "architecture": "llama",
            "dimensions": {"hidden_size": 32, "intermediate_size": 64,
                           "num_layers": 2},
            "attention": {"num_heads": 4, "num_kv_heads": 2, "head_dim": 8},
        },
        "training": {
            "hyperparameters": {"batch_size": 4, "learning_rate": 1e-2,
                                "iters": iters},
            "scheduler": {"type": "cosine", "min_lr_ratio": 0.1},
            "optimization": {"optimizer": "adamw"},
        },
        "logging": {
            "steps": {"logging_interval": 5, "checkpoint_interval": 3,
                      "validation_interval": 0},
        },
        "system": {"seed": 0, "device": "cpu"},
    }
    for k, v in extra.items():
        node = d
        parts = k.split(".")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = v
    return d


def test_trainer_spans_reconcile_with_goodput_ledger(tmp_path):
    """The tentpole invariant: per-component span sums match the goodput
    ledger's cumulative totals (the spans carry the ledger's own
    durations, so within 5% is conservative)."""
    from mlx_cuda_distributed_pretraining_tpu.train.trainer import Trainer

    cfg = Config.from_dict(_tiny_cfg_dict(
        tmp_path, "traced", iters=7,
        **{"logging.trace": {"enabled": True, "capacity": 65536}}))
    tr = Trainer(cfg, runs_root=str(tmp_path / "runs"), quiet=True)
    tr.train()
    assert tr.tracer.enabled and tr.tracer.stats()["dropped"] == 0

    per_span = {}
    events = tr.tracer.chrome_events()
    for e in events:
        if e.get("ph") == "X":
            per_span[e["name"]] = per_span.get(e["name"], 0.0) \
                + e["dur"] / 1e6
    # totals() folds only on close_window; time booked after the last
    # window closed (the final checkpoint) still sits in the open window.
    totals = tr.goodput.totals()
    for comp, v in tr.goodput.window_view().items():
        totals[comp] = totals.get(comp, 0.0) + v
    # the live phases book their own duration into the ledger (the first
    # dispatch under compile_s); the prefetch worker's waits are mirrored
    span_of = {"compile_s": "train.dispatch", "dispatch_s": "train.dispatch",
               "ckpt_save_s": "checkpoint_save", "eval_s": "eval",
               "data_wait_s": "data_wait", "h2d_wait_s": "h2d_wait"}
    by_span = {}
    for comp, booked in totals.items():
        if comp in span_of:
            by_span[span_of[comp]] = by_span.get(span_of[comp], 0.0) + booked
    checked = 0
    for name, booked in by_span.items():
        if booked < 1e-3:
            continue  # skip sub-ms noise
        assert per_span.get(name, 0.0) == pytest.approx(
            booked, rel=0.05), f"{name} spans diverge from the ledger"
        checked += 1
    assert checked >= 2  # at least dispatch + checkpoint_save on any CPU run
    assert per_span.get("train.dispatch", 0.0) > 0.0
    assert per_span.get("checkpoint_save", 0.0) > 0.0
    # no span name is recorded by both mechanisms
    assert not {"dispatch", "compile", "ckpt_save"} & set(per_span)
    assert per_span.get("train.data_get", 0.0) >= per_span.get("data_wait", 0.0)
    assert "train.log_window" in per_span
    # one step_window instant per closed window, carrying tok/s
    wins = [e for e in events
            if e.get("ph") == "i" and e["name"] == "step_window"]
    assert wins and all("tok_s" in w["args"] for w in wins)

    # the ring was exported to the run dir at exit, loadable as-is
    out = os.path.join(tr.run_dir, "trace.json")
    assert os.path.isfile(out)
    doc = json.load(open(out))
    assert doc["displayTimeUnit"] == "ms"
    assert any(e.get("name") == "train.dispatch" for e in doc["traceEvents"])
    # and trace_report's attribution section reads it
    report = load_script("trace_report").report([out])
    assert any(ln.startswith("trainer_attribution=1") for ln in report)
    assert any(ln.startswith("phase=train.dispatch") for ln in report)
    for ln in report:
        if ln.startswith("phase="):
            assert not math.isnan(float(ln.split("total_s=")[1].split()[0]))
