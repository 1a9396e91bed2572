"""A late step accounts for itself: the host counters (obs/hoststats.py), the
loop's step records (obs/steprecord.py, train/trainer.py), and the benchmark's
readers of the new ``step_window`` fields."""

import gc
import importlib.util
import json
import math
import os
import sys
import time

import pytest

from mlx_cuda_distributed_pretraining_tpu.obs import hoststats
from mlx_cuda_distributed_pretraining_tpu.obs.events import events_path, iter_events
from mlx_cuda_distributed_pretraining_tpu.obs.steprecord import PHASES, StepRecords

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- the counters ------------------------------------------------------------------
def test_step_totals_never_run_backwards():
    a = hoststats.step_totals()
    _busy(0.02)   # of this thread's CPU clock: a wall's 0.02 s may hold less than half of it
    b = hoststats.step_totals()
    assert len(a) == len(hoststats.STEP_FIELDS)
    assert all(y >= x for x, y in zip(a, b))
    thread, proc = (b[i] - a[i] for i in (0, 1))
    assert thread >= 0.01 and proc >= thread - 0.005   # the busy loop was this thread's


def test_a_forced_collection_shows_between_two_reads():
    before = hoststats.gc_totals()
    junk = []
    for _ in range(20000):
        cell = []
        cell.append(cell)
        junk.append(cell)
    del junk
    gc.collect()
    after = hoststats.gc_totals()
    assert after[2] - before[2] >= 1            # a collection of the oldest generation
    assert after[5] > before[5]                 # and seconds inside it
    fields = hoststats.window_fields({"gc": before, "machine": {}, "hbm": None},
                                     {"gc": after, "machine": {}, "hbm": None})
    assert fields["gc_n"] >= 1 and fields["gc_s"] > 0
    assert "machine" not in fields and "hbm" not in fields


def test_machine_totals_differences_are_not_negative():
    a = hoststats.machine_totals()
    time.sleep(0.02)
    b = hoststats.machine_totals()
    assert set(a) == set(b)
    for k, v in b.items():
        assert v is None or v >= a[k], k
    assert b["cpus"] == os.cpu_count()


def test_every_absent_source_reads_none(monkeypatch, tmp_path):
    monkeypatch.setattr(hoststats, "_STAT", str(tmp_path / "no-stat"))
    monkeypatch.setattr(hoststats, "_PSI_CPU", str(tmp_path / "no-pressure"))
    m = hoststats.machine_totals()
    assert m.pop("cpus") == os.cpu_count()
    assert m and all(v is None for v in m.values())
    # a backend without allocator statistics (the CPU's) reads None, not an error
    assert hoststats.hbm_totals() is None
    fields = hoststats.window_fields(hoststats.window_totals(), hoststats.window_totals())
    assert "hbm" not in fields and fields["machine"] == {"cpus": os.cpu_count()}


def test_machine_totals_parse_the_kernels_files(monkeypatch, tmp_path):
    (tmp_path / "stat").write_text("cpu  1000 20 300 40000 500 6 70 800 0 0\ncpu0 1 2 3\n")
    (tmp_path / "cpu").write_text("some avg10=0.00 avg60=0.00 avg300=0.00 total=123\n"
                                  "full avg10=0.00 avg60=0.00 avg300=0.00 total=0\n")
    monkeypatch.setattr(hoststats, "_STAT", str(tmp_path / "stat"))
    monkeypatch.setattr(hoststats, "_PSI_CPU", str(tmp_path / "cpu"))
    m = hoststats.machine_totals()
    tick = os.sysconf("SC_CLK_TCK")
    assert m == {"busy_s": pytest.approx((1000 + 20 + 300 + 6 + 70) / tick),
                 "steal_s": pytest.approx(800 / tick), "cpus": os.cpu_count(), "psi_cpu_us": 123}


def test_a_stat_line_of_zeros_is_no_source(monkeypatch, tmp_path):
    """The chip machine's sandboxed kernel: the file is there and counts nothing."""
    (tmp_path / "stat").write_text("cpu  0 0 0 0 0 0 0 0 0 0\n")
    monkeypatch.setattr(hoststats, "_STAT", str(tmp_path / "stat"))
    for _ in range(2):   # the second read finds the source closed
        m = hoststats.machine_totals()
        assert m["busy_s"] is None and m["steal_s"] is None and m["cpus"] == os.cpu_count()
    assert hoststats._fds[str(tmp_path / "stat")] == -1


def test_window_fields_differences_and_levels():
    before = {"gc": (1, 0, 0, 0.5, 0.0, 0.0),
              "machine": {"busy_s": 10.0, "steal_s": 1.0, "cpus": 8, "psi_cpu_us": None},
              "hbm": {"peak": 5, "reserved": 9, "allocs": 100}}
    after = {"gc": (3, 1, 0, 0.75, 0.25, 0.0),
             "machine": {"busy_s": 12.5, "steal_s": 1.0, "cpus": 8, "psi_cpu_us": 4},
             "hbm": {"peak": 7, "reserved": 9, "allocs": 130}}
    f = hoststats.window_fields(before, after)
    assert (f["gc_n"], f["gc_s"]) == (3, 0.5)
    assert f["machine"] == {"busy_s": 2.5, "steal_s": 0.0, "cpus": 8}   # psi_cpu_us was not read before
    assert f["hbm"] == {"peak": 7, "reserved": 9, "allocs": 30}


# -- the records -------------------------------------------------------------------
class Clock:
    """``time`` as obs/steprecord.py and obs/trace.py read it, with a wall clock
    that only the test moves: a step takes the seconds the test says, and a busy
    machine adds none. Everything else (the CPU clocks) is the machine's."""

    def __init__(self):
        self.now = 0.0

    def perf_counter(self):
        return self.now

    def passes(self, seconds):
        self.now += seconds

    def __getattr__(self, name):
        return getattr(time, name)


@pytest.fixture
def clock(monkeypatch):
    """The records' ``time`` (obs/steprecord.py) on a clock the test moves."""
    from mlx_cuda_distributed_pretraining_tpu.obs import steprecord

    clock = Clock()
    monkeypatch.setattr(steprecord, "time", clock)
    return clock


def _steps_of(records, clock, walls, first=0, compiles_at=()):
    """Turn the records through steps of these wall times; the closed records."""
    out, compiles = [], 0
    for i, wall in enumerate(walls, start=first + 1):
        out.append(records.turn(i, i == 1, compiles))
        clock.passes(wall)
        records.note(dispatch_s=wall)
        compiles += i in compiles_at
    return out[1:] + [records.close(compiles)]


def test_records_name_the_late_step_and_leave_compiles_out_of_the_median(clock):
    records = StepRecords()
    recs = _steps_of(records, clock, [0.08] + [0.01] * 6 + [0.08, 0.01, 0.08], compiles_at=(10,))
    assert recs[0]["first_dispatch"] and not recs[0]["in_median"] and "x_median" not in recs[0]
    assert all(r["in_median"] for r in recs[1:9])
    assert "x_median" not in recs[StepRecords.MEDIAN_FROM]          # five steps before it says anything
    assert recs[7]["x_median"] == 8.0 > StepRecords.STALL_FACTOR   # the late step
    assert recs[8]["x_median"] == 1.0
    assert recs[9]["xla_compiles"] == 1 and not recs[9]["in_median"] and "x_median" not in recs[9]
    w = records.window()
    assert w["slow_step"] in (1, 8, 10) and w["step_s_max"] == w["slow"]["wall_s"] >= 0.08
    assert w["step_s_med"] == 0.01
    assert w["proc_cpu_s"] == pytest.approx(sum(r["proc_cpu_s"] for r in recs), abs=1e-5)
    assert w["nivcsw"] == sum(r["nivcsw"] for r in recs)
    assert records.window() == {}


def test_a_capture_stopped_inside_the_step_is_no_stall(clock):
    records = StepRecords()
    _steps_of(records, clock, [0.01] * 6, first=1)
    assert records.turn(8, False, 0) is None and records.close(0)["step"] == 8
    assert records.turn(9, False, 0) is None   # closed before work beside the step: none was open
    clock.passes(0.1)
    rec = records.close(0, side_s=0.095)   # the loop stopped a profiler inside the step
    assert rec["side_s"] == 0.095 and rec["x_median"] == 0.5 < StepRecords.STALL_FACTOR


def test_the_median_is_over_the_newest_steps(clock):
    records = StepRecords()
    records.MEDIAN_OVER = 6
    _steps_of(records, clock, [0.02] * 6 + [0.002] * 6, first=1)
    assert records.median() == pytest.approx(0.002)   # the six slow steps have left it


# -- the loop ----------------------------------------------------------------------
LATE_STEP, LATE_S = 9, 0.3
EVERY_S = 0.03   # every step takes this long


def _busy(seconds):
    """Work through ``seconds`` of this thread's CPU clock: a loop timed by the wall
    gets a fraction of it on a busy machine (0.14 s of 0.35 under six test workers)."""
    end = time.thread_time() + seconds
    while time.thread_time() < end:
        pass


@pytest.fixture(scope="module", params=["sleep", "busy"])
def late_run(request, tmp_path_factory):
    """A tiny run whose wrapped step takes 0.3 s more at one step, waiting or
    working, on a wall clock the fixture moves: the records (obs/steprecord.py)
    and the ring's phases and spans (obs/trace.py) read it. A step that works
    does so through real seconds of this thread's CPU clock."""
    from mlx_cuda_distributed_pretraining_tpu.obs import steprecord, trace
    from mlx_cuda_distributed_pretraining_tpu.train.trainer import Trainer
    from tests.test_trainer import _tiny_config

    tmp = tmp_path_factory.mktemp(request.param)
    cfg = _tiny_config(tmp, name=request.param, iters=12, **{
        "logging.trace": {"enabled": True},
        "logging.steps": {"logging_interval": 1, "checkpoint_interval": 0,
                          "validation_interval": 0}})
    clock = Clock()
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(steprecord, "time", clock)
        patch.setattr(trace, "time", clock)
        tr = Trainer(cfg, runs_root=str(tmp / "runs"), quiet=True)
        inner, calls = tr.train_step, []

        def step(state, batch):
            calls.append(1)
            clock.passes(EVERY_S)
            if len(calls) == LATE_STEP:
                clock.passes(LATE_S)
                if request.param == "busy":
                    _busy(LATE_S)
            return inner(state, batch)

        tr.train_step = step
        assert tr.train()["steps"] == 12
    assert clock.now == pytest.approx(12 * EVERY_S + LATE_S)
    events = list(iter_events(events_path(tr.run_dir)))
    with open(os.path.join(tr.run_dir, "log.txt")) as f:
        log = f.read()
    with open(os.path.join(tr.run_dir, "trace.json")) as f:
        spans = json.load(f)["traceEvents"]
    return {"kind": request.param, "events": events, "log": log, "spans": spans}


def _windows(run):
    return [e for e in run["events"] if e["type"] == "step_window"]


def test_every_step_has_a_window_with_its_own_record(late_run):
    windows = _windows(late_run)
    assert [e["step"] for e in windows] == list(range(1, 13))
    for e in windows:
        assert e["slow_step"] == e["step"] == e["slow"]["step"]
        assert e["step_s_max"] == e["step_s_med"] == e["slow"]["wall_s"]
        assert e["proc_cpu_s"] == e["slow"]["proc_cpu_s"]
        assert e["gc_n"] >= 0 and e["gc_s"] >= 0 and e["machine"]["cpus"] == os.cpu_count()
        assert "hbm" not in e                       # the CPU backend has no statistics
        assert not any(k.startswith("prof_") for k in e)
    assert windows[0]["slow"]["first_dispatch"] and not windows[0]["slow"]["in_median"]


def test_the_late_step_is_the_slow_one(late_run):
    late = _windows(late_run)[LATE_STEP - 1]
    assert late["slow_step"] == LATE_STEP and late["step_s_max"] >= LATE_S
    assert late["slow"]["dispatch_s"] >= LATE_S and late["slow"]["queue_depth"] >= 0
    others = [e["step_s_max"] for e in _windows(late_run)[1:] if e["step"] != LATE_STEP]
    assert max(others) < LATE_S
    # on the fixture's clock, to the microsecond: the machine added nothing
    assert late["step_s_max"] == pytest.approx(EVERY_S + LATE_S, abs=2e-6)
    assert others == [pytest.approx(EVERY_S, abs=2e-6)] * 10


def test_the_late_steps_phases_add_up_to_its_wall_time(late_run):
    slow = _windows(late_run)[LATE_STEP - 1]["slow"]
    assert sum(slow[p] for p in PHASES) == pytest.approx(slow["wall_s"], rel=0.05)


def test_cpu_seconds_tell_waiting_from_working(late_run):
    slow = _windows(late_run)[LATE_STEP - 1]["slow"]
    if late_run["kind"] == "sleep":
        assert slow["thread_cpu_s"] < 0.1 * slow["wall_s"]
    else:
        assert slow["proc_cpu_s"] > 0.9 * LATE_S      # however long the wall let it take
        assert slow["thread_cpu_s"] > 0.9 * LATE_S


def test_one_stall_event_with_the_same_record_and_a_warning(late_run):
    stalls = [e for e in late_run["events"] if e["type"] == "step_stall"]
    assert [e["step"] for e in stalls] == [LATE_STEP]
    slow = _windows(late_run)[LATE_STEP - 1]["slow"]
    assert {k: stalls[0][k] for k in slow} == slow
    assert stalls[0]["x_median"] > StepRecords.STALL_FACTOR
    assert late_run["log"].count("WARNING: step") == 1
    assert f"WARNING: step {LATE_STEP} took" in late_run["log"]


def test_the_ring_holds_one_step_span_around_the_four_phases(late_run):
    steps = {e["args"]["step"]: e for e in late_run["spans"] if e.get("name") == "train.step"}
    assert sorted(steps) == list(range(1, 13))
    late = steps[LATE_STEP]
    assert late["args"]["wall_s"] >= LATE_S and late["dur"] >= LATE_S * 1e6
    inside = [e for e in late_run["spans"]
              if e.get("name") in ("train.data_get", "train.dispatch", "train.loss_sync",
                                   "train.log_window") and e["args"].get("step") == LATE_STEP]
    assert len(inside) == 4
    for e in inside:
        assert late["ts"] - 2 <= e["ts"] and e["ts"] + e["dur"] <= late["ts"] + late["dur"] + 2


def test_the_step_close_phase_holds_the_events_write(late_run):
    """The window's event is written inside a ``train.*`` phase of its own."""
    closes = [e for e in late_run["spans"] if e.get("name") == "train.step_close"]
    assert [e["args"]["step"] for e in closes] == list(range(1, 13))
    steps = {e["args"]["step"]: e for e in late_run["spans"] if e.get("name") == "train.step"}
    for e in closes[:-1]:                   # it runs after its step, at the next one's top
        nxt = steps[e["args"]["step"] + 1]
        assert nxt["ts"] - 2 <= e["ts"] and e["ts"] + e["dur"] <= nxt["ts"] + nxt["dur"] + 2


def _quiet_run(tmp_path, name, iters, clock, **extra):
    """A trainer whose step takes ``EVERY_S`` of ``clock``."""
    from mlx_cuda_distributed_pretraining_tpu.train.trainer import Trainer
    from tests.test_trainer import _tiny_config

    steps = {"logging_interval": 1, "checkpoint_interval": 0, "validation_interval": 0}
    steps.update(extra.pop("steps", {}))
    cfg = _tiny_config(tmp_path, name=name, iters=iters, **{"logging.steps": steps, **extra})
    tr = Trainer(cfg, runs_root=str(tmp_path / "runs"), quiet=True)
    inner = tr.train_step

    def step(state, batch):
        clock.passes(EVERY_S)
        return inner(state, batch)

    tr.train_step = step
    return tr


def test_stopping_a_capture_is_the_programs_own_work_and_no_stall(tmp_path, monkeypatch, clock):
    """A profiler whose stop takes 0.3 s, stopped at the top of step 11, on a
    clock the test moves: the loop times the capture's start and stop on its own
    ``time`` (train/trainer.py), the records on theirs."""
    from mlx_cuda_distributed_pretraining_tpu.train import trainer

    monkeypatch.setattr(trainer, "time", clock)
    tr = _quiet_run(tmp_path, "prof", 14, clock, **{"logging.profile_start": 9,
                                                    "logging.profile_stop": 11})

    def start(step=None):
        tr.profiler.active = True
        return True

    def stop(step=None):
        clock.passes(0.3)
        tr.profiler.active = False
        return None

    tr.profiler.start, tr.profiler.stop = start, stop
    tr._apply_profile_report = lambda report, step: None
    tr.train()
    events = list(iter_events(events_path(tr.run_dir)))
    assert [(e["action"], e["step"]) for e in events if e["type"] == "profiler"] == [
        ("start", 9), ("stop", 11)]
    assert not [e for e in events if e["type"] == "step_stall"]
    slow = [e for e in events if e["type"] == "step_window"][10]["slow"]
    assert slow["step"] == 11 and slow["wall_s"] == pytest.approx(0.3 + EVERY_S)
    assert slow["side_s"] == pytest.approx(0.3)
    assert slow["x_median"] < StepRecords.STALL_FACTOR
    with open(os.path.join(tr.run_dir, "log.txt")) as f:
        assert "WARNING: step" not in f.read()


def test_a_window_is_written_before_its_steps_evaluation_and_checkpoint(tmp_path, clock):
    """Work beside the step is in no step's record, and a kill during the save
    of step N finds ``step_window`` N already in the log. The records read a
    clock the test moves (a step call takes 0.03 s of it, a save 100 s), so a
    busy machine changes no number here."""
    tr = _quiet_run(tmp_path, "order", 12, clock, steps={"checkpoint_interval": 8,
                                                         "validation_interval": 8})
    saved = tr._save_checkpoint_inner
    tr._save_checkpoint_inner = lambda *a: (clock.passes(100.0), saved(*a))[1]
    tr.train()
    events = list(iter_events(events_path(tr.run_dir)))
    order = [(e["type"], e.get("step")) for e in events
             if e["type"] in ("step_window", "eval", "checkpoint_save")]
    at = order.index(("step_window", 8))
    assert order[at:at + 4] == [("step_window", 8), ("eval", None), ("checkpoint_save", 8),
                                ("step_window", 9)]
    windows = [e for e in events if e["type"] == "step_window"]
    assert [e["step"] for e in windows] == list(range(1, 13))
    assert clock.now == pytest.approx(12 * EVERY_S + 2 * 100.0)   # saves at step 8 and at the end
    assert all(e["slow"]["wall_s"] == pytest.approx(EVERY_S) and "side_s" not in e["slow"]
               for e in windows)
    assert not [e for e in events if e["type"] == "step_stall"]


def test_a_late_step_arms_no_capture(late_run):
    assert not [e for e in late_run["events"] if e["type"] == "trace_capture"]


# -- the benchmark's readers -------------------------------------------------------
def _reader(name):
    readers = os.path.join(ROOT, "benchmark", "layer_metrics")
    if readers not in sys.path:
        sys.path.insert(0, readers)   # as run.py does: readers import one another
    path = os.path.join(readers, name + ".py")
    spec = importlib.util.spec_from_file_location("reader_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _event(step, wall, cpu=0.05, gc_s=0.0, busy=0.25, reserved=14 * 2 ** 30):
    return {"type": "step_window", "step": step, "steps": 1,
            "goodput": {"dispatch_s": wall - 0.01, "other_s": 0.01},
            "step_s_max": wall, "step_s_med": wall, "slow_step": step,
            "slow": {"step": step, "wall_s": wall, "proc_cpu_s": cpu},
            "proc_cpu_s": cpu, "nivcsw": 0, "gc_n": int(gc_s > 0), "gc_s": gc_s,
            "machine": {"busy_s": busy, "steal_s": 0.0, "cpus": 10},
            "hbm": {"peak": 5 * 2 ** 30, "reserved": reserved, "largest_free": 2 ** 30,
                    "allocs": 12}}


def _sources(events):
    return {"step_window_events": events, "window": (100.0, 110.0)}


CLEAN = [_event(s, 1.0) for s in range(6, 16)]
# one step of ten 2 s late: the host worked through 1.5 s of it, 0.4 s in the collector,
# and the allocator held 0.25 GiB more
LATE = [_event(s, 1.0) for s in range(6, 15)] + [
    _event(15, 3.0, cpu=1.55, gc_s=0.4, busy=1.75, reserved=int(14.25 * 2 ** 30))]
OLD = [{"type": "step_window", "step": s, "steps": 1, "goodput": {"dispatch_s": 1.0},
        "xla_compiles": 0} for s in range(6, 16)]     # a program from before these fields

EXPECTED = {
    # reader: (a window with one late step, a window with none)
    "step_stall_pct": (100.0 * 2.0 / 10.0, 0.0),
    "slow_step_extra_cpu_ms": (1500.0, 0.0),
    "step_host_cpu_ms": (50.0, 50.0),
    "gc_pause_ms_per_step": (40.0, 0.0),
    "hbm_reserved_gib": (14.25, 14.0),
}


@pytest.mark.parametrize("name", sorted(EXPECTED))
@pytest.mark.parametrize("case", ["late", "clean", "old"])
def test_readers_on_hand_made_windows(name, case):
    read = _reader(name)
    if case == "old":
        assert read(_sources(OLD)) is None and read(_sources([])) is None and read({}) is None
        return
    value = read(_sources(LATE if case == "late" else CLEAN))
    assert math.isfinite(value)
    assert value == pytest.approx(EXPECTED[name][0 if case == "late" else 1], abs=1e-9)


def test_hbm_reserved_falls_back_to_the_peak_where_nothing_is_reserved():
    events = [dict(e, hbm={k: v for k, v in e["hbm"].items() if k != "reserved"}) for e in CLEAN]
    assert _reader("hbm_reserved_gib")(_sources(events)) == 5.0


def _traced(events, span_t0):
    """A traced run's sources: one harness stamp a step, a second apart, and
    the profiler started ``span_t0`` seconds into the window."""
    first = events[0]["step"] - events[0]["steps"] + 1
    return {**_sources(events), "trace_span": [100.0 + span_t0, 0.0],
            "timed_steps": [{"i": i, "t0": 100.0 + (i - first), "t1": 100.9 + (i - first)}
                            for i in range(first, events[-1]["step"] + 1)]}


@pytest.mark.parametrize("name", ["step_stall_pct", "slow_step_extra_cpu_ms"])
def test_the_step_in_which_the_harness_started_its_profiler_is_left_out(name):
    # step 15 of 6..15 began 9.0 s into the window and the profiler started at 9.95 s:
    # its 2 s and its 1.5 s of CPU are the harness's own, and the rest is a clean window
    sources = _traced(LATE, 9.95)
    assert _reader(name)(sources) == pytest.approx(0.0, abs=1e-9)
    # started one step earlier, the late step stays
    sources = _traced(LATE, 8.95)
    assert _reader(name)(sources) == pytest.approx(EXPECTED[name][0], abs=1e-9)
    # an untraced run's sources have the stamps and no span: nothing is left out
    sources = dict(_traced(LATE, 9.95), trace_span=None)
    assert _reader(name)(sources) == pytest.approx(EXPECTED[name][0], abs=1e-9)


def test_stall_share_of_windows_of_several_steps():
    # four steps a window; one window's slowest step 2 s late, another's other steps 0.5 s late each
    def window(step, slowest, elapsed):
        return {"type": "step_window", "step": step, "steps": 4, "step_s_max": slowest,
                "step_s_med": 1.0, "goodput": {"dispatch_s": elapsed}}

    events = [window(4, 1.0, 4.0), window(8, 3.0, 6.0), window(12, 1.5, 6.0), window(16, 1.0, 4.0)]
    late = 2.0 + (0.5 + 1.5)
    assert _reader("step_stall_pct")(_sources(events)) == pytest.approx(100.0 * late / 10.0)
    # the profiler started inside step 6: the window of steps 5-8 is left out whole
    traced = _traced(events, 5.95)
    assert _reader("step_stall_pct")(traced) == pytest.approx(100.0 * (0.5 + 1.5) / 10.0)


def _benchmark(with_waiting):
    """``BENCHMARK.json`` as it stands, or as it will read once the waiting
    serving entries of ``benchmark/pending/`` are appended to it."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    if with_waiting:
        with open(os.path.join(ROOT, "benchmark", "pending", "serving-long.json")) as f:
            waiting = json.load(f)
        for key in ("configs", "workloads", "end_to_end", "per_layer"):
            bench[key] = bench[key] + waiting[key]
        assert waiting["workloads"]  # a file that waits with nothing in it proves nothing
    return bench


@pytest.mark.parametrize("with_waiting", [False, True], ids=["declared", "with_serving_cells"])
def test_every_new_reader_is_declared_for_every_training_cell(with_waiting):
    bench = _benchmark(with_waiting)
    # the cells that report what the readers move, in the benchmark's order
    (cells,) = [m["workloads"] for m in bench["end_to_end"]
                if m["name"] == "train_tokens_per_s_per_chip"]
    assert cells == [w["name"] for w in bench["workloads"] if w["name"] in cells]
    declared = {m["name"]: m for m in bench["per_layer"]}
    for name in EXPECTED:
        m = declared[name]
        assert m["workloads"] == cells and m["moves"] == "train_tokens_per_s_per_chip"
        assert m["better"] == "lower"
