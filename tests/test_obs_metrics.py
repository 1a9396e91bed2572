"""Unified telemetry tests: metrics registry, FLOPs/MFU/goodput
accounting, structured event log, Prometheus exposition, and the
supervisor hang watchdog.

Fast tests cover each obs/ primitive in isolation plus one CPU trainer
smoke run asserting the acceptance contract: every window line reports
``mfu=`` and a goodput breakdown summing to window wall time, and the
live ``/metrics`` scrape agrees with the final ``events.jsonl`` tallies.
The slow test stalls a synthetic child and proves the watchdog
SIGTERMs + restarts it with the lost time booked as ``restart_lost_s``.
"""

import json
import os
import re
import socket
import sys
import textwrap
import threading
import time
import urllib.request

import pytest

from mlx_cuda_distributed_pretraining_tpu.obs.events import (
    EventLog,
    append_event,
    events_path,
    heartbeat_path,
    iter_events,
    read_heartbeat,
    replay_into,
    tally,
    write_heartbeat,
)
from mlx_cuda_distributed_pretraining_tpu.obs.flops import (
    GOODPUT_COMPONENTS,
    GoodputLedger,
    flops_per_token,
    mfu,
    model_flops_per_token,
    peak_flops_per_chip,
)
from mlx_cuda_distributed_pretraining_tpu.obs.metrics import MetricsRegistry
from mlx_cuda_distributed_pretraining_tpu.obs.prometheus import (
    MetricsServer,
    render_prometheus,
    start_metrics_server,
)


# -- metrics registry -------------------------------------------------------

def test_registry_counter_gauge_histogram():
    reg = MetricsRegistry()
    c = reg.counter("req_total", "requests")
    c.inc()
    c.inc(2.5)
    assert c.value() == 3.5
    g = reg.gauge("depth", "queue depth")
    g.set(7)
    g.set(3)
    assert g.value() == 3.0
    h = reg.histogram("lat_seconds", "latency", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    snap = reg.snapshot()
    s = snap["lat_seconds"]["series"][0]
    assert s["count"] == 3 and s["sum"] == pytest.approx(5.55)
    # cumulative buckets: <=0.1 holds 1, <=1.0 holds 2, +Inf holds 3
    assert s["buckets"] == [[0.1, 1], [1.0, 2], ["+Inf", 3]]


def test_registry_kind_and_sign_errors():
    reg = MetricsRegistry()
    c = reg.counter("c", "")
    with pytest.raises(TypeError):
        c.set(1.0)
    with pytest.raises(ValueError):
        c.inc(-1.0)
    with pytest.raises(TypeError):
        reg.gauge("c", "")  # name already registered as a counter


def test_registry_labels_and_series_bound():
    reg = MetricsRegistry(max_series_per_metric=3)
    c = reg.counter("by_kind_total", "")
    for kind in ("a", "b", "c", "d", "e"):
        c.inc(kind=kind)
    snap = reg.snapshot()
    assert len(snap["by_kind_total"]["series"]) == 3
    assert snap["_dropped_series"] == 2
    # existing series keep accepting increments at the bound
    c.inc(kind="a")
    assert c.value(kind="a") == 2.0


def test_registry_thread_concurrency():
    reg = MetricsRegistry()
    c = reg.counter("hits_total", "")
    g = reg.gauge("level", "")

    def work(n):
        for i in range(500):
            c.inc()
            g.set(i)
            if i % 100 == 0:
                reg.snapshot()

    threads = [threading.Thread(target=work, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value() == 8 * 500


def test_registry_flat_view():
    reg = MetricsRegistry()
    reg.counter("a_total", "").inc(2)
    reg.counter("b_total", "").inc(1, kind="x")
    reg.histogram("h", "").observe(1.0)
    flat = reg.flat()
    assert flat["a_total"] == 2.0
    assert flat["b_total{kind=x}"] == 1.0
    assert "h" not in flat  # histograms stay out of the scalar view


# -- FLOPs / MFU / goodput --------------------------------------------------

def test_flops_per_token_hand_check():
    # 6N + 6*L*S*d_attn with N=1e6, L=4, S=128, d_attn=64:
    # 6e6 + 6*4*128*64 = 6,000,000 + 196,608
    assert flops_per_token(1_000_000, 4, 128, 64) == 6_196_608.0


def test_model_flops_per_token_uses_heads_times_head_dim():
    class M:
        num_layers = 2
        num_heads = 4
        head_dim = 8

        hidden_size = 16
        misc = {"tie_word_embeddings": False}

    assert model_flops_per_token(M, 1000, 64) == \
        flops_per_token(1000, 2, 64, 32)
    # an untied input table is a lookup, not a matmul: left out of N once
    # the vocabulary is known (a tied table is the output head and stays)
    assert model_flops_per_token(M, 1000, 64, vocab_size=10) == \
        flops_per_token(1000 - 10 * 16, 2, 64, 32)
    M.misc = {"tie_word_embeddings": True}
    assert model_flops_per_token(M, 1000, 64, vocab_size=10) == \
        flops_per_token(1000, 2, 64, 32)


def test_trainer_mfu_counts_like_the_benchmark():
    """The 4-layer Mistral-7B cut of benchmark cell 1: the trainer's count
    agrees with benchmark/flops/llama_dense.py within the norm gains."""
    class M:
        num_layers, num_heads, head_dim, hidden_size = 4, 32, 128, 4096
        intermediate_size = 14336
        misc = {"tie_word_embeddings": False}

    n_params = 1_140_887_552  # PERF.md section 4
    got = model_flops_per_token(M, n_params, 4096, vocab_size=32768)
    assert got == pytest.approx(6.442e9, rel=1e-3)  # PERF.md section 6, PR 23
    assert got < 0.9 * flops_per_token(n_params, 4, 4096, 4096)  # the old count
    # and the job's chips are the mesh's: one device reads 8x an 8-device host
    assert mfu(1000.0, got, 197e12, 1) == pytest.approx(8 * mfu(1000.0, got, 197e12, 8))


def test_peak_flops_detection_and_env_override(monkeypatch):
    assert peak_flops_per_chip("TPU v5 lite") == 197e12
    assert peak_flops_per_chip("TPU v5p chip") == 459e12
    assert peak_flops_per_chip("cpu") is None
    # an accelerator missing from the table is an error, not "unknown"
    with pytest.raises(ValueError, match="no peak FLOP/s listed"):
        peak_flops_per_chip("NVIDIA H100 80GB")
    monkeypatch.setenv("GRAFT_PEAK_FLOPS", "123e12")
    assert peak_flops_per_chip("cpu") == 123e12
    monkeypatch.setenv("GRAFT_PEAK_FLOPS", "not-a-number")
    assert peak_flops_per_chip("cpu") is None


def test_mfu_value_and_unknown():
    # 1000 tok/s * 1e9 FLOPs/tok over 2 chips of 1e12 → 0.5
    assert mfu(1000.0, 1e9, 1e12, 2) == pytest.approx(0.5)
    assert mfu(1000.0, 1e9, None, 2) is None
    assert mfu(1000.0, 1e9, 0.0, 2) is None


def test_goodput_ledger_residual_and_totals():
    led = GoodputLedger()
    led.add("dispatch_s", 3.0)
    led.add("data_wait_s", 1.0)
    led.add("ckpt_save_s", -5.0)  # negative clamps to zero
    with pytest.raises(KeyError):
        led.add("nonsense_s", 1.0)
    win = led.close_window(10.0)
    assert win["dispatch_s"] == 3.0
    assert win["other_s"] == pytest.approx(6.0)
    assert sum(win.values()) == pytest.approx(10.0)
    # window reset; booked time beyond elapsed clamps the residual at 0
    led.add("dispatch_s", 9.0)
    win2 = led.close_window(4.0)
    assert win2["other_s"] == 0.0
    totals = led.totals()
    assert totals["dispatch_s"] == pytest.approx(12.0)
    assert set(GOODPUT_COMPONENTS) < set(totals)


# -- event log --------------------------------------------------------------

def test_events_round_trip_and_torn_line(tmp_path):
    path = str(tmp_path / "events.jsonl")
    log = EventLog(path)
    log.append("run_start", name="t", total_steps=10)
    log.append("step_window", step=5, steps=5, toks=320, loss=2.0,
               goodput={"dispatch_s": 1.0})
    log.close()
    append_event(path, "fault", kind="hang", stalled_s=3.0)
    with open(path, "a") as f:
        f.write('{"v":1,"type":"truncat')  # crash mid-append
    evs = list(iter_events(path))
    assert [e["type"] for e in evs] == ["run_start", "step_window", "fault"]
    assert all(e["v"] == 1 and "t" in e for e in evs)


def test_replay_rebuilds_registry_and_matches_tally(tmp_path):
    path = str(tmp_path / "events.jsonl")
    log = EventLog(path)
    log.append("run_start", name="t")
    log.append("step_window", step=5, steps=5, toks=100,
               goodput={"dispatch_s": 2.0, "other_s": 1.0})
    log.append("step_window", step=10, steps=5, toks=100,
               goodput={"dispatch_s": 3.0})
    log.append("checkpoint_save", step=10, seconds=0.5)
    log.append("eval", loss=2.0, seconds=0.1)
    log.append("fault", kind="hang", stalled_s=9.0)
    log.append("restart", lost_s=12.5, resume="10")
    log.close()

    reg = MetricsRegistry()
    assert replay_into(reg, path) == 7
    assert reg.counter("train_steps_total").value() == 10.0
    assert reg.counter("train_tokens_total").value() == 200.0
    assert reg.counter("checkpoint_saves_total").value() == 1.0
    assert reg.counter("eval_runs_total").value() == 1.0
    assert reg.counter("faults_total").value(kind="hang") == 1.0
    assert reg.counter("restarts_total").value() == 1.0
    gp = reg.counter("goodput_seconds_total")
    assert gp.value(component="dispatch_s") == 5.0
    assert gp.value(component="restart_lost_s") == 12.5

    t = tally(path)
    assert t["steps"] == 10 and t["toks"] == 200
    assert t["checkpoint_saves"] == 1 and t["evals"] == 1
    assert t["faults"] == 1 and t["restarts"] == 1 and t["events"] == 7


def test_replay_missing_file_is_zero(tmp_path):
    assert replay_into(MetricsRegistry(), str(tmp_path / "none.jsonl")) == 0


def test_heartbeat_write_read_atomic(tmp_path):
    hb_path = str(tmp_path / "heartbeat.json")
    write_heartbeat(hb_path, step=42)
    hb = read_heartbeat(hb_path)
    assert hb["step"] == 42 and hb["pid"] == os.getpid()
    assert abs(hb["t"] - time.time()) < 5.0
    assert not os.path.exists(hb_path + ".tmp")
    with open(hb_path, "w") as f:
        f.write("{torn")
    assert read_heartbeat(hb_path) is None
    assert read_heartbeat(str(tmp_path / "absent.json")) is None


# -- prometheus exposition --------------------------------------------------

def test_render_prometheus_format():
    reg = MetricsRegistry()
    reg.counter("req_total", "requests served").inc(3, code="200")
    reg.gauge("depth", "queue depth").set(1.5)
    reg.histogram("lat", "latency", buckets=(0.1, 1.0)).observe(0.5)
    text = render_prometheus(reg.snapshot())
    assert "# HELP req_total requests served" in text
    assert "# TYPE req_total counter" in text
    assert 'req_total{code="200"} 3' in text
    assert "depth 1.5" in text
    assert 'lat_bucket{le="0.1"} 0' in text
    assert 'lat_bucket{le="1"} 1' in text
    assert 'lat_bucket{le="+Inf"} 1' in text
    assert "lat_sum 0.5" in text and "lat_count 1" in text
    assert text.rstrip().endswith("telemetry_dropped_series_total 0")


def test_metrics_server_scrape_and_health():
    reg = MetricsRegistry()
    reg.counter("scraped_total", "").inc(9)
    srv = MetricsServer(reg, port=0)  # OS-assigned port
    try:
        base = f"http://127.0.0.1:{srv.port}"
        text = urllib.request.urlopen(f"{base}/metrics", timeout=5).read().decode()
        assert "scraped_total 9" in text
        assert urllib.request.urlopen(f"{base}/healthz", timeout=5).status == 200
        snap = json.loads(
            urllib.request.urlopen(f"{base}/snapshot", timeout=5).read())
        assert snap["scraped_total"]["series"][0]["value"] == 9.0
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(f"{base}/nope", timeout=5)
    finally:
        srv.shutdown()


def test_start_metrics_server_survives_port_conflict():
    reg = MetricsRegistry()
    first = start_metrics_server(reg, 0, host="127.0.0.1")
    assert first is not None
    try:
        second = start_metrics_server(reg, first.port, host="127.0.0.1")
        assert second is None  # port taken → None, never an exception
    finally:
        first.shutdown()


# -- trainer integration (CPU smoke) ---------------------------------------

def _write_jsonl(path, texts):
    with open(path, "w") as f:
        for t in texts:
            f.write(json.dumps({"text": t}) + "\n")


def _tiny_config(tmp_path, name="telemetry", iters=15, **extra):
    from mlx_cuda_distributed_pretraining_tpu.config import Config

    train = tmp_path / "train.jsonl"
    val = tmp_path / "val.jsonl"
    corpus = ["the quick brown fox jumps over the lazy dog " * 4] * 40
    _write_jsonl(train, corpus)
    _write_jsonl(val, corpus[:10])
    d = {
        "name": name,
        "overwrite": True,
        "data": {
            "input_file": str(train),
            "validation_file": str(val),
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
            "steps": {"logging_interval": 5, "checkpoint_interval": 15,
                      "validation_interval": 10},
        },
        "system": {"seed": 0, "device": "cpu"},
    }
    for k, v in extra.items():
        node = d
        for p in k.split(".")[:-1]:
            node = node.setdefault(p, {})
        node[k.split(".")[-1]] = v
    return Config.from_dict(d)


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _parse_prom(text):
    out = {}
    for line in text.splitlines():
        if line.startswith("#") or not line.strip():
            continue
        name, val = line.rsplit(" ", 1)
        out[name] = float(val)
    return out


def test_trainer_telemetry_end_to_end(tmp_path):
    """The acceptance contract: mfu + goodput on every window line (sum
    within 5% of window wall time), Prometheus counters matching the
    events.jsonl tallies, heartbeat + event stream on disk."""
    from mlx_cuda_distributed_pretraining_tpu.train.trainer import Trainer

    port = _free_port()
    cfg = _tiny_config(tmp_path, iters=15, **{"logging.metrics_port": port})
    tr = Trainer(cfg, runs_root=str(tmp_path / "runs"), quiet=True)
    try:
        result = tr.train()
        assert result["steps"] == 15

        # -- log lines: mfu + goodput breakdown on every window ------------
        window_lines = [
            ln for ln in open(os.path.join(tr.run_dir, "log.txt"))
            if ln.startswith("Step") and "loss=" in ln
            and "validation" not in ln]
        assert window_lines
        gp_keys = ("compile_s", "data_wait_s", "h2d_wait_s", "dispatch_s",
                   "ckpt_save_s", "eval_s", "other_s")
        for ln in window_lines:
            assert "mfu=unknown" in ln  # CPU: peak undetectable
            kv = dict(re.findall(r"([\w/]+)=([0-9.eE+-]+|unknown)", ln))
            for k in gp_keys:
                assert k in kv, f"missing {k} in: {ln}"
            toks, tok_s = float(kv["toks"]), float(kv["tok/s"])
            elapsed = toks / tok_s
            booked = sum(float(kv[k]) for k in gp_keys)
            # components + residual sum to window wall time (5% covers
            # the log-line float rounding)
            assert booked == pytest.approx(elapsed, rel=0.05), ln

        # -- live scrape agrees with the durable event log -----------------
        assert tr._metrics_server is not None
        url = f"http://127.0.0.1:{tr._metrics_server.port}/metrics"
        prom = _parse_prom(
            urllib.request.urlopen(url, timeout=5).read().decode())
        t = tally(events_path(tr.run_dir))
        assert prom["train_steps_total"] == t["steps"] == 15
        assert prom["train_tokens_total"] == t["toks"] > 0
        assert prom["checkpoint_saves_total"] == t["checkpoint_saves"] >= 2
        assert prom["eval_runs_total"] == t["evals"] >= 1
        assert prom["train_step"] == 15

        # -- event stream + heartbeat --------------------------------------
        types = [e["type"] for e in iter_events(events_path(tr.run_dir))]
        assert types[0] == "run_start"
        assert types[-1] == "run_end"
        assert "compile" in types and "step_window" in types
        assert "checkpoint_save" in types and "eval" in types
        win = next(e for e in iter_events(events_path(tr.run_dir))
                   if e["type"] == "step_window")
        assert win["mfu"] is None  # CPU
        assert sum(win["goodput"].values()) > 0
        hb = read_heartbeat(heartbeat_path(tr.run_dir))
        assert hb and hb["step"] == 15
    finally:
        if tr._metrics_server is not None:
            tr._metrics_server.shutdown()


def test_trainer_reports_the_flash_plan(tmp_path):
    """Which path the step's flash kernels, forward and backward, were traced
    to (ops/flash_attention.py flash_plan) is written once: a log line after
    the first compile, and ``flash_plan`` on the first step_window event alone."""
    from mlx_cuda_distributed_pretraining_tpu.train.trainer import Trainer

    cfg = _tiny_config(tmp_path, name="flashplan", iters=10,
                       **{"model.attention.attention_type": "flash",
                          "system.fused_ce_chunk": 64})
    tr = Trainer(cfg, runs_root=str(tmp_path / "runs"), quiet=True)
    tr.train()
    windows = [e for e in iter_events(events_path(tr.run_dir))
               if e["type"] == "step_window"]
    assert len(windows) == 2
    plan = windows[0]["flash_plan"]
    assert plan["resident"] > 0 and plan["streamed"] == 0 and plan["reference"] == 0
    # one dQ and one dK/dV call a traced forward that is differentiated
    assert plan["bwd_dq_resident"] == plan["bwd_dkv_resident"] > 0
    assert plan["bwd_dq_streamed"] == plan["bwd_dkv_streamed"] == 0
    assert "flash_plan" not in windows[1]
    # beside it, whether the fused CE computes the head's gradients in its
    # forward walk (ops/fused_ce.py): the train step's one call does, and the
    # validation before step 1 walked the forward only
    ce_plan = windows[0]["fused_ce_plan"]
    # (between that walk's matmuls XLA's chain, at a vocabulary that is no
    # whole number of 128-lane registers)
    assert ce_plan == {"grad_in_forward": 1, "forward_only": 1,
                       "softmax_grad_kernel": 0, "softmax_grad_xla": 1}
    assert "fused_ce_plan" not in windows[1]
    # and how many expert layers it dispatches and combines by gathers
    # (models/moe.py): a dense model has none
    moe_plan = windows[0]["moe_plan"]
    assert moe_plan == {"dispatch_gather": 0, "combine_gather": 0, "chunk_loop_tail": 0,
                        "chunk_two_sizes": 0, "chunk_trips_small": 0, "chunk_trips_whole": 0,
                        "token_sum_kernel": 0, "token_sum_xla": 0}
    assert "moe_plan" not in windows[1]
    # nor a grouped matmul kernel (ops/grouped_matmul.py)
    gmm_plan = windows[0]["gmm_plan"]
    assert {"gmm_resident", "tgmm_resident"} <= set(gmm_plan)
    assert not any(gmm_plan.values()) and "gmm_plan" not in windows[1]
    with open(tr.logger.log_path) as f:
        lines = [ln for ln in f if "flash plan" in ln]
    assert len(lines) == 1 and "grouped matmuls (kernel calls traced" in lines[0]
    assert all(f"{key}={n}" in lines[0]
               for key, n in {**plan, **ce_plan, **moe_plan, **gmm_plan}.items())


def test_trainer_registry_replays_on_construction(tmp_path):
    """A second Trainer on the same run dir rebuilds its counters from
    events.jsonl — Prometheus totals survive process death."""
    from mlx_cuda_distributed_pretraining_tpu.train.trainer import Trainer

    cfg = _tiny_config(tmp_path, name="replayed", iters=10)
    tr = Trainer(cfg, runs_root=str(tmp_path / "runs"), quiet=True)
    tr.train()
    t = tally(events_path(tr.run_dir))
    assert t["steps"] == 10

    cfg2 = _tiny_config(tmp_path, name="replayed", iters=10,
                        **{"overwrite": False,
                           "resume.checkpoint": "latest"})
    tr2 = Trainer(cfg2, runs_root=str(tmp_path / "runs"), quiet=True)
    assert tr2.metrics.counter("train_steps_total").value() >= 10.0


# -- hang watchdog ----------------------------------------------------------

def test_watchdog_last_progress_floors_stale_heartbeat(tmp_path):
    """A heartbeat left behind by a PREVIOUS child must not count against
    a freshly spawned one."""
    from mlx_cuda_distributed_pretraining_tpu.train.supervisor import Supervisor

    run_dir = str(tmp_path)
    write_heartbeat(heartbeat_path(run_dir), step=3)
    sup = Supervisor(lambda tag: ["true"], run_dir, log=lambda m: None)
    spawn_after = time.time() + 100
    assert sup._last_progress(spawn_after) == spawn_after
    spawn_before = time.time() - 100
    assert sup._last_progress(spawn_before) > spawn_before  # hb is newer


@pytest.mark.slow
def test_watchdog_restarts_hung_child_and_books_lost_time(tmp_path):
    """Synthetic hang: run 1 writes one heartbeat then stalls (trapping
    SIGTERM → exit 0, the nastiest case: a hang must count as a crash
    even on a clean exit code); run 2 completes. The supervisor must
    SIGTERM + restart, log fault/restart events, and the replayed
    registry must carry the lost wall clock as restart_lost_s."""
    from mlx_cuda_distributed_pretraining_tpu.train.supervisor import Supervisor

    run_dir = tmp_path / "run"
    (run_dir / "checkpoints").mkdir(parents=True)
    marker = tmp_path / "attempts.txt"
    child = tmp_path / "child.py"
    child.write_text(textwrap.dedent(f"""
        import json, os, signal, sys, time
        marker = {str(marker)!r}
        n = int(open(marker).read()) if os.path.exists(marker) else 0
        open(marker, "w").write(str(n + 1))
        hb = {str(run_dir / "heartbeat.json")!r}
        tmp = hb + ".tmp"
        with open(tmp, "w") as f:
            json.dump({{"t": time.time(), "step": n, "pid": os.getpid()}}, f)
        os.replace(tmp, hb)
        if n == 0:
            signal.signal(signal.SIGTERM, lambda *a: sys.exit(0))
            time.sleep(300)  # hang: heartbeat never advances again
        sys.exit(0)
    """))

    sup = Supervisor(
        lambda tag: [sys.executable, str(child)],
        str(run_dir),
        backoff_base=0.05, backoff_max=0.05,
        hang_timeout_s=1.5, hang_kill_grace_s=5.0,
        log=lambda m: None,
    )
    rc = sup.run()
    assert rc == 0
    assert sup.hangs == 1 and sup.restarts == 1
    assert int(marker.read_text()) == 2

    evs = list(iter_events(events_path(str(run_dir))))
    fault = next(e for e in evs if e["type"] == "fault")
    assert fault["kind"] == "hang" and fault["stalled_s"] > 1.5
    restart = next(e for e in evs if e["type"] == "restart")
    assert restart["lost_s"] > 0
    post = next(e for e in evs if e["type"] == "postmortem")
    assert post["hang"] is True and post["rc"] == 0  # clean-exit hang

    reg = MetricsRegistry()
    replay_into(reg, events_path(str(run_dir)))
    assert reg.counter("faults_total").value(kind="hang") == 1.0
    assert reg.counter("restarts_total").value() == 1.0
    lost = reg.counter("goodput_seconds_total").value(
        component="restart_lost_s")
    assert lost == pytest.approx(restart["lost_s"])


# -- events.jsonl rotation (logging.events.max_bytes) -----------------------

def test_events_rotation_bounds_live_file_and_replay_reads_pair(tmp_path):
    from mlx_cuda_distributed_pretraining_tpu.obs.events import (
        rotated_events_path)

    path = str(tmp_path / "events.jsonl")
    cap = 600
    log = EventLog(path, now=lambda: 1000.0, max_bytes=cap)
    for i in range(30):
        log.append("step_window", step=(i + 1) * 5, steps=5, toks=10)
    log.close()
    rotated = rotated_events_path(path)
    assert os.path.exists(rotated)
    # rotation happens between complete lines, so both generations stay
    # under the cap (the live file strictly, the rotated one too)
    assert os.path.getsize(path) <= cap
    assert os.path.getsize(rotated) <= cap
    # readers see a contiguous SUFFIX of history ending at the newest
    # event — older generations age out by design, nothing interleaves
    evs = list(iter_events(path))
    steps = [e["step"] for e in evs]
    assert steps == list(range(steps[0], 151, 5)) and steps[-1] == 150
    assert 2 <= len(evs) < 30
    # a torn tail on the live file is still skipped, not fatal
    with open(path, "a") as f:
        f.write('{"v":1,"type":"torn')
    assert [e["step"] for e in iter_events(path)] == steps
    # replay_into rebuilds from the pair: 5 steps per surviving window
    reg = MetricsRegistry()
    assert replay_into(reg, path) == len(evs)
    assert reg.counter("train_steps_total").value() == 5.0 * len(evs)


def test_events_max_bytes_zero_never_rotates(tmp_path):
    from mlx_cuda_distributed_pretraining_tpu.obs.events import (
        rotated_events_path)

    path = str(tmp_path / "events.jsonl")
    log = EventLog(path, max_bytes=0)
    for i in range(50):
        log.append("step_window", step=i, steps=1, toks=1)
    log.close()
    assert not os.path.exists(rotated_events_path(path))
    assert len(list(iter_events(path))) == 50


def test_logging_config_events_max_bytes_key():
    from mlx_cuda_distributed_pretraining_tpu.config import LoggingConfig

    assert LoggingConfig().events_max_bytes == 0
    cfg = LoggingConfig(events={"max_bytes": 1 << 20})
    assert cfg.events_max_bytes == 1 << 20


# -- TTFT histogram exposition pins -----------------------------------------

def test_ttft_prometheus_text_format_pin():
    """The serve_ttft_ms exposition shape external scrapers (graftscope,
    real Prometheus) parse: every LATENCY_MS_BUCKETS le line in order,
    cumulative counts, then _sum and _count. A bucket-boundary or
    formatting change must be a deliberate one."""
    from mlx_cuda_distributed_pretraining_tpu.obs.metrics import (
        LATENCY_MS_BUCKETS)

    reg = MetricsRegistry()
    h = reg.histogram("serve_ttft_ms", "time to first token (ms)",
                      buckets=LATENCY_MS_BUCKETS)
    for v in (3.0, 40.0, 800.0):
        h.observe(v)
    text = render_prometheus(reg.snapshot())
    lines = [ln for ln in text.splitlines()
             if ln.startswith("serve_ttft_ms")]
    want_cum = {1.0: 0, 2.5: 0, 5.0: 1, 10.0: 1, 25.0: 1, 50.0: 2,
                100.0: 2, 250.0: 2, 500.0: 2, 1000.0: 3, 2500.0: 3,
                5000.0: 3, 10000.0: 3, 30000.0: 3}
    expected = ['serve_ttft_ms_bucket{le="%g"} %d' % (le, want_cum[le])
                for le in LATENCY_MS_BUCKETS]
    expected += ['serve_ttft_ms_bucket{le="+Inf"} 3',
                 "serve_ttft_ms_sum 843",
                 "serve_ttft_ms_count 3"]
    assert lines == expected
    assert "# TYPE serve_ttft_ms histogram" in text


def test_engine_json_metrics_include_ttft_sum_and_count():
    """BatchEngine._ttft_quantiles feeds the JSON /metrics surface: the
    quantile keys alone cannot recover a mean, so sum/count ride along
    (graftscope and port-less scrapers compute averages from them)."""
    from types import SimpleNamespace

    from mlx_cuda_distributed_pretraining_tpu.obs.metrics import (
        LATENCY_MS_BUCKETS)
    from mlx_cuda_distributed_pretraining_tpu.serve.engine import (
        BatchEngine)

    reg = MetricsRegistry()
    h = reg.histogram("serve_ttft_ms", "", buckets=LATENCY_MS_BUCKETS)
    for v in (10.0, 20.0, 400.0):
        h.observe(v)
    stub = SimpleNamespace(metrics_registry=reg)
    out = BatchEngine._ttft_quantiles(stub)
    assert set(out) == {"ttft_ms_p50", "ttft_ms_p95", "ttft_ms_p99",
                        "ttft_ms_sum", "ttft_ms_count"}
    assert out["ttft_ms_sum"] == 430.0 and out["ttft_ms_count"] == 3
    # empty histogram: the whole block stays absent (no fake zeros)
    assert BatchEngine._ttft_quantiles(
        SimpleNamespace(metrics_registry=MetricsRegistry())) == {}
