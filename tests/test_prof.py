"""graftprof tests: trace parsing, step-time attribution, the CLI, the
profiler helper, and per-host exposition.

The golden fixture is a hand-built Chrome trace (two annotated steps,
an overlapping collective+matmul pair, an infeed slice, and a torn
tail) whose attribution is known exactly — per ISSUE 14 it pins the
parser's numbers, not just their sum. The slow test captures a real
2-step ``jax.profiler`` window on CPU and asserts the report parses it
with fractions summing to ~1.
"""

import gzip
import json
import os

import pytest
from conftest import load_script

from mlx_cuda_distributed_pretraining_tpu.obs.profile_report import (
    PROF_FIELDS,
    attribute,
    base_op_name,
    classify_op,
    find_trace_files,
    format_report,
    generate_report,
    load_trace_events,
    prof_fields,
    write_summary,
)
from mlx_cuda_distributed_pretraining_tpu.obs.profiler import ProfileCapture
from mlx_cuda_distributed_pretraining_tpu.obs.prometheus import (
    render_prometheus,
)


# -- fixture --------------------------------------------------------------
# Timeline (µs), one device. Step 1 = [1000, 2000), step 2 = [2000, 3000):
#   dot.1                 [1000, 1400)  compute/matmul
#   flash fusion          [1400, 1600)  compute/flash
#   all-gather-start.1    [1200, 1500)  comm, FULLY under compute
#   infeed.1              [1900, 1950)  host
#   dot.2                 [2000, 2400)  compute/matmul
#   reduce-scatter.2      [2300, 2800)  comm, 100µs under compute
# Exact attribution:
#   step 1: compute .6  comm_exposed 0.0  host .05  idle .35
#           comm_total .3  overlap 300/300 = 1.0
#   step 2: compute .4  comm_exposed .4   host .0   idle .2
#           comm_total .5  overlap 100/500 = 0.2
#   aggregate (equal durations): compute .5  comm .2  host .025
#           idle .275  comm_total .4  overlap 400/800 = 0.5

def _op(name, ts, dur, tid=2):
    return {"ph": "X", "name": name.lstrip("%"), "ts": ts, "dur": dur,
            "pid": 7, "tid": tid, "args": {"hlo_op": name}}


def _fixture_events():
    return [
        {"ph": "M", "name": "process_name", "pid": 7,
         "args": {"name": "/host:CPU"}},
        {"ph": "X", "name": "train", "ts": 1000, "dur": 1000,
         "pid": 7, "tid": 9, "args": {"step_num": "1"}},
        {"ph": "X", "name": "train", "ts": 2000, "dur": 1000,
         "pid": 7, "tid": 9, "args": {"step_num": "2"}},
        _op("%dot.1", 1000, 400),
        _op("%fusion.flash_attention.3", 1400, 200),
        _op("%all-gather-start.1", 1200, 300, tid=3),
        _op("%infeed.1", 1900, 50),
        _op("%dot.2", 2000, 400),
        _op("%reduce-scatter.2", 2300, 500, tid=3),
    ]


def _write_trace(path, events, torn=False):
    text = json.dumps({"displayTimeUnit": "ns",
                       "traceEvents": events})
    if torn:
        # Cut inside the final event object: the salvage reader must
        # keep every complete event and flag the file torn.
        cut = text.rfind('{"ph"')
        assert cut > 0
        text = text[:cut + 25]
    data = text.encode()
    if path.endswith(".gz"):
        data = gzip.compress(data)
    with open(path, "wb") as f:
        f.write(data)
    return path


def _make_dump(root, torn=False, fname="host.trace.json.gz"):
    """Lay out <root>/plugins/profile/<session>/<fname> like jax does."""
    sess = os.path.join(root, "plugins", "profile", "2026_08_05_00_00_00")
    os.makedirs(sess, exist_ok=True)
    events = _fixture_events()
    if torn:
        events = events + [_op("%sacrificial-op.9", 2950, 40)]
    return _write_trace(os.path.join(sess, fname), events, torn=torn)


GOLD_STEP1 = dict(compute_frac=0.6, comm_frac=0.0, host_frac=0.05,
                  idle_frac=0.35, comm_total_frac=0.3, overlap_frac=1.0)
GOLD_STEP2 = dict(compute_frac=0.4, comm_frac=0.4, host_frac=0.0,
                  idle_frac=0.2, comm_total_frac=0.5, overlap_frac=0.2)
GOLD_AGG = dict(compute_frac=0.5, comm_frac=0.2, host_frac=0.025,
                idle_frac=0.275, comm_total_frac=0.4, overlap_frac=0.5)


def _check(golden, actual):
    for k, v in golden.items():
        assert actual[k] == pytest.approx(v, abs=1e-9), (k, actual)


# -- classification -------------------------------------------------------

def test_base_op_name_and_classify():
    assert base_op_name("%all-gather-start.12") == "all-gather-start"
    assert base_op_name("%dot.3.1") == "dot"
    assert classify_op("%all-gather-start.1") == ("comm", "all-gather")
    assert classify_op("all-gather-done.1") == ("comm", "all-gather")
    assert classify_op("%reduce-scatter.5") == ("comm", "reduce-scatter")
    assert classify_op("%all-reduce.2") == ("comm", "all-reduce")
    assert classify_op("%collective-permute-start.1") == (
        "comm", "collective-permute")
    assert classify_op("%dot.7") == ("compute", "matmul")
    assert classify_op("%convolution.1") == ("compute", "matmul")
    assert classify_op("%fusion.flash_attention.2") == ("compute", "flash")
    assert classify_op("%gmm.1") == ("compute", "gmm")
    assert classify_op("%infeed.1") == ("host", "host")
    assert classify_op("%fusion.99") == ("compute", "other")


# -- golden attribution ---------------------------------------------------

def test_golden_attribution_exact(tmp_path):
    _make_dump(str(tmp_path))
    report = generate_report(str(tmp_path))
    assert report is not None
    assert report["torn"] is False
    assert report["n_devices"] == 1
    assert [s["step"] for s in report["steps"]] == [1, 2]
    _check(GOLD_STEP1, report["steps"][0])
    _check(GOLD_STEP2, report["steps"][1])
    _check(GOLD_AGG, report["aggregate"])
    # Seconds columns pin the same numbers in absolute form.
    s1 = report["steps"][0]
    assert s1["compute_s"] == pytest.approx(600e-6)
    assert s1["comm_s"] == pytest.approx(300e-6)
    assert s1["overlap_s"] == pytest.approx(300e-6)
    assert s1["host_s"] == pytest.approx(50e-6)
    assert s1["compute_by_family"] == {
        "flash": pytest.approx(200e-6), "matmul": pytest.approx(400e-6)}
    assert s1["comm_by_kind"] == {"all-gather": pytest.approx(300e-6)}
    assert report["steps"][1]["comm_by_kind"] == {
        "reduce-scatter": pytest.approx(500e-6)}


def test_fractions_sum_to_one(tmp_path):
    _make_dump(str(tmp_path))
    report = generate_report(str(tmp_path))
    for scope in report["steps"] + [report["aggregate"]]:
        total = (scope["compute_frac"] + scope["comm_frac"]
                 + scope["host_frac"] + scope["idle_frac"])
        assert total == pytest.approx(1.0, abs=0.02)


def test_op_table_and_families(tmp_path):
    _make_dump(str(tmp_path))
    report = generate_report(str(tmp_path), analytic={
        "tokens_per_step": 1000.0,
        "matmul_flops_per_token": 6e6,
        "attn_flops_per_token": 1e6,
        "collective_bytes_per_step": {"reduce-scatter": 4096.0},
    })
    ops = {o["op"]: o for o in report["ops"]}
    assert ops["dot"]["count"] == 2
    assert ops["dot"]["total_s"] == pytest.approx(800e-6)
    # dot occupies 800µs of the 2000µs covered by step windows.
    assert ops["dot"]["frac"] == pytest.approx(0.4)
    assert ops["reduce-scatter"]["category"] == "comm"
    fams = report["families"]
    # achieved = flops_per_step * n_steps / family_seconds
    assert fams["compute"]["matmul"]["achieved_flops_per_s"] == \
        pytest.approx(6e6 * 1000 * 2 / 800e-6)
    assert fams["compute"]["flash"]["achieved_flops_per_s"] == \
        pytest.approx(1e6 * 1000 * 2 / 200e-6)
    assert fams["comm"]["reduce-scatter"]["achieved_bytes_per_s"] == \
        pytest.approx(4096.0 * 2 / 500e-6)
    # all-gather has no pinned bytes: time-only row, no rate invented.
    assert "achieved_bytes_per_s" not in fams["comm"]["all-gather"]


def test_torn_tail_tolerated(tmp_path):
    _make_dump(str(tmp_path), torn=True)
    report = generate_report(str(tmp_path))
    assert report["torn"] is True
    # Every complete event survives; the truncated sacrificial op does
    # not — attribution equals the untorn goldens exactly.
    _check(GOLD_STEP1, report["steps"][0])
    _check(GOLD_STEP2, report["steps"][1])
    _check(GOLD_AGG, report["aggregate"])


def test_load_trace_events_plain_json(tmp_path):
    p = _write_trace(str(tmp_path / "t.trace.json"), _fixture_events())
    events, torn = load_trace_events(p)
    assert not torn and len(events) == len(_fixture_events())


def test_truncated_gzip_does_not_raise(tmp_path):
    full = gzip.compress(json.dumps(
        {"traceEvents": _fixture_events()}).encode())
    p = str(tmp_path / "t.trace.json.gz")
    with open(p, "wb") as f:
        f.write(full[:len(full) - 8])  # lose the gzip trailer + tail
    events, torn = load_trace_events(p)  # must not raise
    assert isinstance(events, list)


def test_no_steps_synthesizes_one_window(tmp_path):
    events = [e for e in _fixture_events()
              if "step_num" not in (e.get("args") or {})]
    p = _write_trace(str(tmp_path / "t.trace.json"), events)
    report = attribute([p])
    assert [s["step"] for s in report["steps"]] == [0]
    agg = report["aggregate"]
    total = (agg["compute_frac"] + agg["comm_frac"]
             + agg["host_frac"] + agg["idle_frac"])
    assert total == pytest.approx(1.0, abs=1e-9)


def test_find_trace_files_variants(tmp_path):
    trace = _make_dump(str(tmp_path / "profile"))
    # run dir (contains profile/), dump dir, session dir, direct file
    assert find_trace_files(str(tmp_path)) == [trace]
    assert find_trace_files(str(tmp_path / "profile")) == [trace]
    assert find_trace_files(os.path.dirname(trace)) == [trace]
    assert find_trace_files(trace) == [trace]
    assert find_trace_files(str(tmp_path / "missing")) == []


def test_multi_host_files_average(tmp_path):
    # Same fixture from two "hosts" (same pids!): device identity is
    # (file, pid), so fractions average to the single-host goldens
    # instead of double-counting one lane.
    _make_dump(str(tmp_path), fname="host0.trace.json.gz")
    _make_dump(str(tmp_path), fname="host1.trace.json.gz")
    report = generate_report(str(tmp_path))
    assert report["n_devices"] == 2
    _check(GOLD_AGG, report["aggregate"])


def test_prof_fields_and_format(tmp_path):
    _make_dump(str(tmp_path))
    report = generate_report(str(tmp_path))
    fields = prof_fields(report)
    assert set(fields) == set(PROF_FIELDS)
    assert fields["prof_compute_frac"] == pytest.approx(0.5)
    assert fields["prof_overlap_frac"] == pytest.approx(0.5)
    lines = format_report(report)
    assert lines[0].startswith("graftprof=1")
    assert any(l.startswith("aggregate=1") for l in lines)
    assert any(l.startswith("op=dot") for l in lines)
    out = write_summary(report, str(tmp_path / "prof_summary.json"))
    with open(out) as f:
        assert json.load(f)["aggregate"]["n_steps"] == 2


# -- CLI ------------------------------------------------------------------

def _make_run_dir(tmp_path):
    run = tmp_path / "run"
    _make_dump(str(run / "profile"))
    with open(run / "events.jsonl", "w") as f:
        f.write(json.dumps({"v": 1, "type": "run_start", "t": 1.0,
                            "name": "model-config-sample",
                            "n_params": 1000, "flops_per_token": 7000.0,
                            "peak_flops": None, "n_chips": 1}) + "\n")
        f.write(json.dumps({"v": 1, "type": "step_window", "t": 2.0,
                            "step": 10, "steps": 10, "toks": 10000,
                            "loss": 1.0, "tok_s": 5.0,
                            "mfu": None}) + "\n")
    return run


def test_cli_prints_table_and_writes_summary(tmp_path, capsys):
    from mlx_cuda_distributed_pretraining_tpu.analysis import prof

    run = _make_run_dir(tmp_path)
    assert prof.main([str(run)]) == 0
    out = capsys.readouterr().out
    assert "aggregate=1" in out
    assert "overlap_frac=0.5" in out
    summary = run / "prof_summary.json"
    assert summary.is_file()
    with open(summary) as f:
        doc = json.load(f)
    _check(GOLD_AGG, doc["aggregate"])
    # Analytic join recovered from the run dir's own events.jsonl:
    # 6N = 6000, attention residual = 1000, 1000 tokens/step.
    an = doc["analytic"]
    assert an["matmul_flops_per_token"] == pytest.approx(6000.0)
    assert an["attn_flops_per_token"] == pytest.approx(1000.0)
    assert an["tokens_per_step"] == pytest.approx(1000.0)


def test_cli_budget_join(tmp_path, capsys):
    from mlx_cuda_distributed_pretraining_tpu.analysis import prof

    run = _make_run_dir(tmp_path)
    budget = tmp_path / "budget.json"
    with open(budget, "w") as f:
        json.dump({"programs": {"train_step": {"collectives": {
            "all-gather": {"bytes": 8192, "count": 2}}}}}, f)
    assert prof.main([str(run), "--budgets", str(budget)]) == 0
    with open(run / "prof_summary.json") as f:
        doc = json.load(f)
    ag = doc["families"]["comm"]["all-gather"]
    assert ag["achieved_bytes_per_s"] == pytest.approx(8192 * 2 / 300e-6)


def test_cli_no_trace_exits_2(tmp_path, capsys):
    from mlx_cuda_distributed_pretraining_tpu.analysis import prof

    empty = tmp_path / "empty"
    empty.mkdir()
    assert prof.main([str(empty)]) == 2
    assert "no profiler trace" in capsys.readouterr().err


# -- profiler helper ------------------------------------------------------

def test_profile_capture_idempotent(tmp_path, monkeypatch):
    import jax.profiler

    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d: calls.append(("start", d)))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append(("stop", None)))
    logs = []
    cap = ProfileCapture(str(tmp_path / "dump"), log=logs.append,
                         summary_path=str(tmp_path / "s.json"))
    assert cap.start(5) is True
    assert cap.active
    assert cap.start(6) is False          # second start: no-op
    assert [c[0] for c in calls] == ["start"]
    assert cap.stop(7) is None            # empty dump -> no report
    assert not cap.active
    assert cap.stop(8) is None            # second stop: no-op
    assert [c[0] for c in calls] == ["start", "stop"]
    assert any("trace started at step 5" in l for l in logs)
    assert any("trace written to" in l for l in logs)


def test_profile_capture_reports_on_stop(tmp_path, monkeypatch):
    import jax.profiler

    dump = tmp_path / "dump"

    def fake_stop():
        _make_dump(str(dump))  # "the profiler" writes its files on stop

    monkeypatch.setattr(jax.profiler, "start_trace", lambda d: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", fake_stop)
    synced = []
    cap = ProfileCapture(
        str(dump), sync=lambda: synced.append(1),
        analytic_fn=lambda: {"tokens_per_step": 1000.0,
                             "matmul_flops_per_token": 6e6},
        summary_path=str(tmp_path / "prof_summary.json"))
    assert cap.start() is True
    report = cap.stop(42)
    assert synced == [1]
    _check(GOLD_AGG, report["aggregate"])
    assert cap.last_report is report
    assert (tmp_path / "prof_summary.json").is_file()


def test_profile_capture_report_disabled(tmp_path, monkeypatch):
    import jax.profiler

    dump = tmp_path / "dump"
    monkeypatch.setattr(jax.profiler, "start_trace", lambda d: None)
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: _make_dump(str(dump)))
    cap = ProfileCapture(str(dump), report=False)
    cap.start()
    assert cap.stop() is None             # attribution switched off


def test_profile_capture_start_failure_is_soft(tmp_path, monkeypatch):
    import jax.profiler

    def boom(d):
        raise RuntimeError("profiler busy")

    monkeypatch.setattr(jax.profiler, "start_trace", boom)
    logs = []
    cap = ProfileCapture(str(tmp_path / "d"), log=logs.append)
    assert cap.start() is False
    assert not cap.active
    assert any("unavailable" in l for l in logs)


# -- per-host exposition --------------------------------------------------

def test_render_prometheus_process_index_stamp():
    snap = {"train_step": {"kind": "gauge", "help": "s",
                           "series": [{"labels": {}, "value": 7}]}}
    text = render_prometheus(snap, process_index=3)
    assert "process_index 3" in text
    assert "# TYPE process_index gauge" in text
    assert "process_index" not in render_prometheus(snap)


# -- trace_report fold ----------------------------------------------------

def test_trace_report_folds_graftprof(tmp_path, capsys):
    mod = load_script("trace_report")
    run = _make_run_dir(tmp_path)
    lines = mod.graftprof_report(str(run))
    assert lines and lines[0].startswith("graftprof=1")
    assert any(l.startswith("aggregate=1") for l in lines)
    # No dump -> quiet, not an error.
    empty = tmp_path / "empty"
    empty.mkdir()
    assert mod.graftprof_report(str(empty)) == []
    # --run-dir end to end through main().
    assert mod.main([ "--run-dir", str(run)]) == 0
    assert "graftprof=1" in capsys.readouterr().out


# -- trainer auto-report (slow) -------------------------------------------

@pytest.mark.slow
def test_trainer_profile_window_auto_report(tmp_path):
    """A profile window ends -> the trainer runs attribution itself:
    graftprof log line, prof_summary.json, prof gauges on /metrics
    snapshots, and one profile_report event that holds the fractions."""
    from tests.test_trainer import _tiny_config  # reuse the tiny corpus
    from mlx_cuda_distributed_pretraining_tpu.train.trainer import Trainer

    cfg = _tiny_config(tmp_path, name="profrep", iters=8,
                       **{"logging.steps.validation_interval": 0,
                          "logging.profile_start": 2,
                          "logging.profile_stop": 4})
    tr = Trainer(cfg, runs_root=str(tmp_path / "runs"), quiet=True)
    tr.train()
    log = open(os.path.join(tr.run_dir, "log.txt")).read()
    assert "graftprof: steps=" in log
    summary = os.path.join(tr.run_dir, "prof_summary.json")
    assert os.path.isfile(summary)
    with open(summary) as f:
        agg = json.load(f)["aggregate"]
    total = (agg["compute_frac"] + agg["comm_frac"]
             + agg["host_frac"] + agg["idle_frac"])
    assert total == pytest.approx(1.0, abs=0.02)
    snap = tr.metrics.snapshot()
    for name in PROF_FIELDS:
        assert name in snap, name
    events = [json.loads(l) for l in
              open(os.path.join(tr.run_dir, "events.jsonl"))]
    reports = [e for e in events if e["type"] == "profile_report"]
    assert len(reports) == 1 and all(name in reports[0] for name in PROF_FIELDS)


# -- real capture (slow) --------------------------------------------------

@pytest.mark.slow
def test_real_two_step_profile_window(tmp_path):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def step(x):
        return x @ x + jnp.sum(x)

    x = jnp.ones((256, 256))
    step(x).block_until_ready()  # compile outside the window

    cap = ProfileCapture(str(tmp_path / "dump"),
                         summary_path=str(tmp_path / "prof_summary.json"))
    assert cap.start() is True
    for i in range(2):
        with jax.profiler.StepTraceAnnotation("train", step_num=i):
            x = step(x)
    x.block_until_ready()
    report = cap.stop()
    assert report is not None
    agg = report["aggregate"]
    total = (agg["compute_frac"] + agg["comm_frac"]
             + agg["host_frac"] + agg["idle_frac"])
    assert total == pytest.approx(1.0, abs=0.02)
    assert agg["compute_frac"] > 0
    assert (tmp_path / "prof_summary.json").is_file()
