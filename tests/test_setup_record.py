"""A run's set-up accounts for itself (PR 54): ``obs/compiles.py``'s stages,
outermost spans and cache outcomes; the trainer's set-up phases and its
``compile`` event; ``xla_compiled`` on a window that built a program; the
ring's ``init.*`` and ``compile.*`` spans; and the benchmark's eight readers
of the event."""

import glob
import importlib.util
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import pytest

from mlx_cuda_distributed_pretraining_tpu.obs import compiles, hoststats
from mlx_cuda_distributed_pretraining_tpu.train.trainer import Trainer
from test_trainer import _tiny_config

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PARTS = ("before_trainer", "trainer_build", "step_trace", "step_lower", "step_compile_or_load",
         "steps_to_window", "other")
READERS = tuple("setup_part_s." + p for p in PARTS) + ("setup_cache_misses",)
_EVERY_STEP = {"logging.steps": {"logging_interval": 1, "checkpoint_interval": 0,
                                 "validation_interval": 0}}


def _delta(before, after):
    return {k: after[k] - before[k] for k in after}


# -- the listener --------------------------------------------------------------------
def test_a_nested_jits_trace_counts_once_and_two_top_level_traces_add():
    @jax.jit
    def inner(x):
        time.sleep(0.02)
        return jnp.sin(x) * 2

    @jax.jit
    def outer(x):
        time.sleep(0.02)
        return inner(x) + inner(x * 3)

    x, y = jnp.ones(11), jnp.ones(13)
    t0, s0 = time.time(), compiles.stages()
    outer(x).block_until_ready()
    s1 = compiles.stages()
    got = _delta(s0, s1)
    kept = [s for s in compiles.spans(t0) if s.stage == "trace"]
    # inner's traces (and sin's, multiply's) fired inside outer's: reported, not kept
    assert got["trace_n"] >= 3 and [s.fun for s in kept] == ["outer"]
    assert got["trace_s"] == pytest.approx(kept[0].seconds, abs=1e-5) and got["trace_s"] >= 0.04
    assert got["lower_n"] == got["backend_n"] == 1
    fun = compiles.functions(t0)["outer"]   # lowering and the backend say jit(outer)
    assert fun["trace_s"] > 0 and fun["lower_s"] > 0 and fun["backend_s"] > 0
    assert "inner" not in compiles.functions(t0)
    # a second top-level trace of the same function adds
    outer(y).block_until_ready()
    again = _delta(s1, compiles.stages())
    assert again["trace_s"] >= 0.04
    assert compiles.functions(t0)["outer"]["trace_s"] == pytest.approx(
        got["trace_s"] + again["trace_s"], abs=1e-4)
    cut = compiles.inside(kept[0].start, kept[0].start + 0.01)
    assert cut["trace_s"] == pytest.approx(0.01, abs=1e-6) and cut["backend_s"] == 0.0


def test_totals_are_the_backend_compiles_as_before():
    f = jax.jit(lambda x: x * 2 + 1)
    f(jnp.ones(3)).block_until_ready()
    n0, s0 = compiles.totals()
    f(jnp.ones(3)).block_until_ready()
    assert compiles.totals() == (n0, s0)
    before = compiles.stages()
    f(jnp.ones(5)).block_until_ready()
    n1, s1 = compiles.totals()
    got = _delta(before, compiles.stages())
    assert n1 - n0 == got["backend_n"] >= 1 and s1 - s0 == pytest.approx(got["backend_s"], abs=1e-5)


def test_a_cache_miss_then_a_hit_are_told_apart_by_function(tmp_path):
    from jax.experimental.compilation_cache import compilation_cache

    held = {k: getattr(jax.config, k) for k in (
        "jax_enable_compilation_cache", "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs", "jax_persistent_cache_min_entry_size_bytes")}
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    compilation_cache.reset_cache()
    try:
        @jax.jit
        def told_apart(x):
            return jnp.cos(x) - 7

        x = jnp.ones(17)
        t0, s0 = time.time(), compiles.stages()
        told_apart(x).block_until_ready()
        s1 = compiles.stages()
        cold = _delta(s0, s1)
        assert cold["cache_misses"] >= 1 and cold["cache_hits"] == 0
        assert compiles.functions(t0)["told_apart"]["cache"] == "miss"
        jax.clear_caches()
        t1 = time.time()
        told_apart(x).block_until_ready()
        warm = _delta(s1, compiles.stages())
        assert warm["cache_hits"] >= 1 and warm["cache_misses"] == 0 and warm["cache_load_s"] > 0
        assert warm["backend_n"] >= 1          # a load is a backend compile to totals(), as it was
        assert compiles.functions(t1)["told_apart"]["cache"] == "hit"
        assert compiles.functions(t0)["told_apart"]["cache"] == "miss"   # a miss among several wins
    finally:
        for k, v in held.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()


def test_the_process_start_is_before_now_and_after_boot():
    t = hoststats.process_start_t()
    if t is None:
        pytest.skip("no /proc here")
    assert 0 < time.time() - t < 86400 * 30


# -- the trainer ---------------------------------------------------------------------
def _events(run_dir):
    with open(os.path.join(run_dir, "events.jsonl")) as f:
        return [json.loads(line) for line in f]


@pytest.fixture(scope="module")
def traced_run(tmp_path_factory):
    """A tiny run with the ring on: (trainer, constructor entry, events)."""
    tmp = tmp_path_factory.mktemp("setup")
    cfg = _tiny_config(tmp, name="setup", iters=4,
                       **{"logging.trace": {"enabled": True}, **_EVERY_STEP})
    entered = time.time()
    tr = Trainer(cfg, runs_root=str(tmp / "runs"), quiet=True)
    tr.train()
    return tr, entered, _events(tr.run_dir)


def test_the_compile_event_is_the_runs_set_up_record(traced_run):
    tr, entered, events = traced_run
    (ev,) = [e for e in events if e["type"] == "compile"]
    phases = ev["phases"]
    names = [p["name"] for p in phases]
    assert names == ["init.system", "init.tokenizer", "init.model", "init.params", "init.data",
                     "init.optimizer", "init.telemetry", "train.start", "train.data_get",
                     "train.dispatch"]
    by = {p["name"]: p for p in phases}
    top = [p for p in phases if p["name"] != "init.params"]
    for a, b in zip(top, top[1:]):                         # in order, not overlapping
        assert a["t"] + a["seconds"] <= b["t"] + 1e-4
    model, params = by["init.model"], by["init.params"]   # kept apart, inside it
    assert model["t"] <= params["t"] and \
        params["t"] + params["seconds"] <= model["t"] + model["seconds"] + 1e-4
    exit_t = by["train.dispatch"]["t"] + by["train.dispatch"]["seconds"]
    assert entered <= top[0]["t"] and exit_t <= ev["t"] + 1e-3
    assert sum(p["seconds"] for p in top) >= 0.95 * (exit_t - entered)
    # seconds is what it was: the first dispatch's, and goodput's compile_s
    assert ev["seconds"] == pytest.approx(by["train.dispatch"]["seconds"], abs=1e-4) and ev["step"] == 1
    first = next(e for e in events if e["type"] == "step_window")
    assert first["goodput"]["compile_s"] == pytest.approx(ev["seconds"], abs=1e-4)
    assert ev["process_start_t"] == hoststats.process_start_t()
    # the step by the name JAX reports, its stages inside the first dispatch
    assert ev["step_fun"] == "train_step"
    step = ev["step_stages"]
    assert step["trace_s"] > 0 and step["lower_s"] > 0 and step["backend_s"] > 0
    # the suite runs with the cache off; the record is the process's, other tests' misses included
    assert step["cache"] is None and "train_step" not in ev["misses"] and len(ev["misses"]) <= 16
    d = by["train.dispatch"]
    assert d["trace_s"] >= step["trace_s"] - 1e-4 and d["backend_s"] >= step["backend_s"] - 1e-4
    assert d["trace_s"] + d["lower_s"] + d["backend_s"] <= d["seconds"] + 1e-4
    assert "train_step" in ev["functions"] and len(ev["functions"]) <= 8
    assert ev["stages"]["trace_n"] > ev["stages"]["backend_n"] >= 1
    assert ev["stages"]["trace_s"] >= step["trace_s"] - 1e-4
    with open(os.path.join(tr.run_dir, "log.txt")) as f:
        (line,) = [ln for ln in f if "set-up to step 1's dispatch returning" in ln]
    assert "init.params" in line and "train_step: trace" in line and "cache hits" in line


def test_the_rings_export_shows_set_up_on_one_timeline(traced_run):
    tr, _, _ = traced_run
    with open(os.path.join(tr.run_dir, "trace.json")) as f:
        spans = [e for e in json.load(f)["traceEvents"] if e.get("ph") == "X"]
    by = {}
    for e in spans:
        by.setdefault(e["name"], []).append(e)
    for name in ("init.system", "init.tokenizer", "init.model", "init.params", "init.data",
                 "init.optimizer", "init.telemetry", "train.start"):
        assert len(by[name]) == 1, name
    (step,) = [e for e in by["compile.trace"] if e["args"]["fun"] == "train_step"]
    first = min(by["train.dispatch"], key=lambda e: e["ts"])
    for stage in ("compile.trace", "compile.lower", "compile.backend"):   # under the phase they fell in
        (mine,) = [e for e in by[stage] if e["args"]["fun"] == "train_step"]
        assert first["ts"] <= mine["ts"] and mine["ts"] + mine["dur"] <= first["ts"] + first["dur"] + 50
    inside_model = [e for e in by["compile.backend"]
                    if by["init.model"][0]["ts"] <= e["ts"] <= by["init.model"][0]["ts"] + by["init.model"][0]["dur"]]
    assert inside_model and step["dur"] > 0


def test_a_forced_recompile_names_the_program_in_its_window(tmp_path):
    cfg = _tiny_config(tmp_path, name="recompile", iters=6, **_EVERY_STEP,
                       **{"logging.profile_start": 3, "logging.profile_stop": 6})
    tr = Trainer(cfg, runs_root=str(tmp_path / "runs"), quiet=True)
    inner, calls = tr.train_step, []

    def narrower_at_step_4(state, batch):
        calls.append(1)
        if len(calls) == 4:   # another batch shape: the step is traced, lowered and compiled again
            batch = {k: (v[:2] if getattr(v, "ndim", 0) == 2 else v) for k, v in batch.items()}
        return inner(state, batch)

    tr.train_step = narrower_at_step_4
    tr.train()
    windows = {e["step"]: e for e in _events(tr.run_dir) if e["type"] == "step_window"}
    assert windows[4]["xla_compiles"] >= 1 and "train_step" in windows[4]["xla_compiled"]
    assert len(windows[4]["xla_compiled"]) <= 8
    # the compile event names what was built up to the first dispatch; the first window what came after
    assert windows[1]["xla_compiles"] >= 1 and "train_step" not in windows[1].get("xla_compiled", {})
    for step in (2, 3, 5, 6):
        assert windows[step]["xla_compiles"] == 0 and "xla_compiled" not in windows[step]
    # it fell inside the trainer's profiler session: on that trace too, by stage
    (dump,) = glob.glob(os.path.join(tr.run_dir, "profile", "plugins", "profile", "*", "*.xplane.pb"))
    marks = [e.name for plane in jax.profiler.ProfileData.from_file(dump).planes
             if plane.name.startswith("/host:") for line in plane.lines for e in line.events
             if e.name == "xla_compile"]
    # the step's trace, lowering and backend compile; none from before step 3
    assert 3 <= len(marks) <= 12, marks


# -- the benchmark's readers -----------------------------------------------------------
def _read_metric(name, sources):
    readers = os.path.join(REPO, "benchmark", "layer_metrics")
    if readers not in sys.path:
        sys.path.insert(0, readers)
    spec = importlib.util.spec_from_file_location("lm_" + name.replace(".", "_"),
                                                  os.path.join(readers, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(sources)


def _hand_made(work, compile_event, windows=()):
    """A work directory as a run leaves it, and sources whose first timed step
    began 100 s after the hand-made process did."""
    run = work / "runs" / "bench-cell"
    run.mkdir(parents=True)
    with open(run / "events.jsonl", "w") as f:
        for e in (compile_event, *windows):
            f.write(json.dumps(e) + "\n")
        f.write('{"type": "step_window", "step": 9, "xla_comp')   # a torn last line
    to_mono = time.perf_counter() - time.time()
    return {"trace_dir": str(work / "trace"),
            "timed_steps": [{"i": 6, "t0": T0 + 100.0 + to_mono, "t1": T0 + 101.0 + to_mono}]}


T0 = 1_800_000_000.0
_RECORD = {
    "type": "compile", "t": T0 + 80.0, "seconds": 50.0, "step": 1, "process_start_t": T0,
    "phases": [
        {"name": "init.system", "t": T0 + 12.0, "seconds": 1.0},
        {"name": "init.model", "t": T0 + 13.0, "seconds": 6.0, "backend_s": 4.0},
        {"name": "init.params", "t": T0 + 14.0, "seconds": 5.0, "backend_s": 4.0},
        {"name": "init.optimizer", "t": T0 + 19.0, "seconds": 2.5},
        {"name": "init.telemetry", "t": T0 + 21.5, "seconds": 0.5},
        {"name": "train.start", "t": T0 + 27.0, "seconds": 0.5},
        {"name": "train.data_get", "t": T0 + 27.5, "seconds": 0.25},
        {"name": "train.dispatch", "t": T0 + 30.0, "seconds": 50.0,
         "trace_s": 30.5, "lower_s": 8.75, "backend_s": 7.0},
    ],
    "stages": {"trace_s": 31.0, "lower_s": 9.0, "backend_s": 12.0, "cache_misses": 2},
    "step_fun": "train_step",
    "step_stages": {"trace_s": 30.0, "lower_s": 8.5, "backend_s": 6.0, "cache": "miss"},
    "functions": {}, "misses": ["train_step", "fn"],
}


def test_the_readers_cut_set_up_into_pieces_that_sum_to_it(tmp_path):
    windows = [
        {"type": "step_window", "step": 1, "xla_compiles": 9},
        {"type": "step_window", "step": 3, "xla_compiles": 2,
         "xla_compiled": {"fn": "miss", "other": "hit", "eager": None}},
        {"type": "step_window", "step": 6, "xla_compiles": 1, "xla_compiled": {"late": "miss"}},
    ]
    sources = _hand_made(tmp_path, _RECORD, windows)
    got = {name: _read_metric(name, sources) for name in READERS}
    want = {"before_trainer": 12.0, "trainer_build": 10.0, "step_trace": 30.0, "step_lower": 8.5,
            "step_compile_or_load": 7.0, "steps_to_window": 20.0, "other": 12.5}
    for part, seconds in want.items():
        assert got["setup_part_s." + part] == pytest.approx(seconds, abs=1e-3), part
    assert sum(got["setup_part_s." + p] for p in PARTS) == pytest.approx(100.0, abs=1e-3)
    # by construction, whatever the clocks read: the pieces are the whole
    from _setup import account
    parts, misses = account(sources)
    assert sum(parts.values()) == pytest.approx(
        sources["timed_steps"][0]["t0"] + (time.time() - time.perf_counter()) - T0, abs=1e-3)
    assert sum(parts[p] for p in PARTS if p != "other") + parts["other"] == \
        pytest.approx(sum(parts.values()), abs=1e-6)
    assert misses == got["setup_cache_misses"] == 3.0   # the event's two, one of step 3's; step 6 is timed


@pytest.mark.parametrize("case", ["empty", "no_trace_dir", "no_event", "parent_event", "no_init_phase"])
def test_the_readers_find_nothing_where_the_program_wrote_nothing(tmp_path, case):
    parent = {"type": "compile", "t": T0 + 80.0, "seconds": 50.0, "step": 1}   # before PR 54
    if case == "empty":
        sources = {}
    elif case == "no_event":
        sources = _hand_made(tmp_path, {"type": "run_start", "t": T0})
    elif case == "no_init_phase":
        sources = _hand_made(tmp_path, {**_RECORD, "phases": _RECORD["phases"][-1:]})
    else:
        sources = _hand_made(tmp_path, parent if case == "parent_event" else _RECORD)
        if case == "no_trace_dir":
            sources["trace_dir"] = None
    assert {name: _read_metric(name, sources) for name in READERS} == dict.fromkeys(READERS)


def test_the_eight_metrics_are_declared_for_set_up_in_every_training_cell():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (cells,) = [m["workloads"] for m in bench["end_to_end"]
                if m["name"] == "train_tokens_per_s_per_chip"]
    names = [m["name"] for m in bench["per_layer"]]
    mine = bench["per_layer"][names.index(READERS[0]):][:len(READERS)]   # appended, one run of entries
    assert [m["name"] for m in mine] == list(READERS)
    for m in mine:
        assert m["moves"] == "setup_s" and m["workloads"] == cells and m["better"] == "lower"
        assert os.path.isfile(os.path.join(REPO, "benchmark", "layer_metrics", m["name"] + ".py"))
    assert [(m["unit"], m["source"]) for m in mine] == [("s", "program_span")] * 7 + \
        [("count", "program_counter")]
    assert [m["layer"] for m in mine] == ["trainer set-up"] * 2 + ["train step"] * 3 + \
        ["trainer loop", "trainer set-up", "train step"]
    # set-up had no per-layer metric before these
    assert {m["name"] for m in bench["per_layer"] if m["moves"] == "setup_s"} == set(READERS)
