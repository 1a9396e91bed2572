"""Device-prefetch input pipeline tests (data/device_prefetch.py).

Covers the PR's contracts: ordering, depth back-pressure, StopIteration /
error propagation, worker-thread lifecycle, checkpoint position semantics
(consumed, not fetched), prefetch on/off loss parity through the real
trainer, the host-side schedule evaluation, the persistent compilation
cache knob, and the data_wait_frac stats gauge.
"""

import json
import os
import threading
import time

import numpy as np
import pytest

from mlx_cuda_distributed_pretraining_tpu.config import Config, DataConfig
from mlx_cuda_distributed_pretraining_tpu.data import (
    DevicePrefetcher,
    StreamingDataManager,
)
from mlx_cuda_distributed_pretraining_tpu.obs import StatsState
from mlx_cuda_distributed_pretraining_tpu.tokenizer import TokenizerManager


def _write_shard(path, n_docs, prefix="doc"):
    with open(path, "w") as f:
        for i in range(n_docs):
            f.write(json.dumps({"text": f"{prefix} {i} " + "hello world " * 20}) + "\n")


def _streaming_cfg(shards, ctx=64, **extra):
    return DataConfig(
        preprocessing={"max_context_size": ctx},
        tokenizer={"type": "byte"},
        source="jsonl",
        streaming={"shards": shards, "shuffle_buffer": 8, **extra},
    )


class FakeLoader:
    """Deterministic loader: batch contents encode the step. Raises
    StopIteration past ``limit`` (like a finite stream)."""

    def __init__(self, limit=10**9):
        self.limit = limit
        self.fetches = 0

    def generate_batch(self, step):
        self.fetches += 1
        if step >= self.limit:
            raise StopIteration("dry")
        return {
            "inputs": np.full((2, 4), step, np.int32),
            "targets": np.full((2, 4), step + 1, np.int32),
            "mask": np.ones((2, 4), np.float32),
        }

    def state_dict(self):
        return {"val_ptr": 0}

    def load_state_dict(self, state):
        pass


def _drain(pf):
    out = []
    while True:
        try:
            batch, tokens, waits = pf.get()
        except StopIteration:
            return out
        out.append((int(np.asarray(batch["inputs"])[0, 0]), tokens, waits))


def _wait_until(cond, timeout=5.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(0.01)
    return cond()


# -- unit: ordering / back-pressure / lifecycle ------------------------------

def test_ordering_matches_loader_sequence():
    pf = DevicePrefetcher(FakeLoader(), depth=2, start_step=0, total_steps=6)
    try:
        got = _drain(pf)
    finally:
        pf.stop()
    assert [g[0] for g in got] == [0, 1, 2, 3, 4, 5]
    # token counts are host-counted by the worker (2x4 all-ones mask)
    assert [g[1] for g in got] == [8] * 6
    assert all("data_wait_s" in g[2] and "h2d_wait_s" in g[2] for g in got)


def test_depth_backpressure_bounds_fetches():
    loader = FakeLoader()
    pf = DevicePrefetcher(loader, depth=2, start_step=0, total_steps=100)
    try:
        # Worker fills the queue (depth) plus at most one item in hand,
        # then blocks — it must NOT run ahead of the consumer.
        _wait_until(lambda: loader.fetches >= 3)
        time.sleep(0.1)
        assert loader.fetches <= 3
        pf.get()
        _wait_until(lambda: loader.fetches >= 4)
        time.sleep(0.1)
        assert loader.fetches <= 4  # one consumed -> exactly one refill
    finally:
        pf.stop()


def test_stopiteration_propagates_after_prefix():
    pf = DevicePrefetcher(FakeLoader(limit=3), depth=2, start_step=0, total_steps=100)
    try:
        got = _drain(pf)
    finally:
        pf.stop()
    assert [g[0] for g in got] == [0, 1, 2]
    with pytest.raises(StopIteration):
        pf.get()  # stays exhausted on repeated calls


def test_loader_error_reraised_at_get():
    class Exploding(FakeLoader):
        def generate_batch(self, step):
            if step >= 1:
                raise RuntimeError("producer died")
            return super().generate_batch(step)

    pf = DevicePrefetcher(Exploding(), depth=2, start_step=0, total_steps=10)
    try:
        pf.get()  # step 1 batch is fine
        with pytest.raises(RuntimeError, match="producer died"):
            pf.get()
    finally:
        pf.stop()


def test_stop_joins_worker_thread():
    pf = DevicePrefetcher(FakeLoader(), depth=2, start_step=0, total_steps=1000)
    assert _wait_until(
        lambda: any(t.name == "device-prefetch" for t in threading.enumerate()))
    pf.stop()
    assert pf._thread is None
    assert not any(
        t.name == "device-prefetch" and t.is_alive() for t in threading.enumerate())


def test_sync_mode_matches_async_sequence():
    on = DevicePrefetcher(FakeLoader(), depth=2, start_step=0, total_steps=5)
    off = DevicePrefetcher(FakeLoader(), depth=0, start_step=0, total_steps=5)
    try:
        a, b = _drain(on), _drain(off)
    finally:
        on.stop()
        off.stop()
    assert [x[0] for x in a] == [x[0] for x in b] == [0, 1, 2, 3, 4]
    assert off._thread is None  # sync mode runs no worker at all


# -- checkpoint position: consumed, not fetched ------------------------------

def test_state_dict_reflects_consumed_not_fetched(tmp_path):
    p = str(tmp_path / "s0.jsonl")
    _write_shard(p, 60)
    tok = TokenizerManager(DataConfig(
        preprocessing={"max_context_size": 64}, tokenizer={"type": "byte"}))
    cfg = _streaming_cfg([p])

    # Reference: plain manager, 2 batches consumed.
    ref = StreamingDataManager(cfg, tok, batch_size=2, seq_len=32)
    for i in range(2):
        ref.generate_batch(i)
    ref_state = ref.state_dict()
    ref.stop()

    # Prefetcher with a deep queue: the worker runs AHEAD of consumption,
    # but state_dict must report the consumed position only.
    mgr = StreamingDataManager(cfg, tok, batch_size=2, seq_len=32)
    pf = DevicePrefetcher(mgr, depth=4, start_step=0, total_steps=100)
    try:
        for _ in range(2):
            pf.get()
        _wait_until(lambda: pf._queue.qsize() >= 3)  # queue fetched ahead
        state = pf.state_dict()
    finally:
        pf.stop()
        mgr.stop()
    assert state["docs_consumed"] == ref_state["docs_consumed"]
    assert state.get("source") == ref_state.get("source")
    assert state.get("buf") == ref_state.get("buf")


def test_resume_equivalence_prefetch_on_vs_off(tmp_path):
    """Resume from a mid-stream checkpoint taken under the prefetcher ==
    resume from one taken without it: batches 4-6 match the uninterrupted
    run exactly (extends test_streaming_exact_resume_batch_equality)."""
    shards = []
    for s in range(2):
        p = str(tmp_path / f"s{s}.jsonl")
        _write_shard(p, 40, prefix=f"shard{s}")
        shards.append(p)
    tok = TokenizerManager(DataConfig(
        preprocessing={"max_context_size": 64}, tokenizer={"type": "byte"}))
    cfg = _streaming_cfg(shards)

    ref = StreamingDataManager(cfg, tok, batch_size=2, seq_len=32)
    ref_batches = [ref.generate_batch(i) for i in range(6)]
    ref.stop()

    mgr = StreamingDataManager(cfg, tok, batch_size=2, seq_len=32)
    pf = DevicePrefetcher(mgr, depth=3, start_step=0, total_steps=100)
    try:
        for _ in range(3):
            pf.get()
        state = pf.state_dict()
    finally:
        pf.stop()
        mgr.stop()

    resumed_mgr = StreamingDataManager(cfg, tok, batch_size=2, seq_len=32)
    resumed_mgr.load_state_dict(state)
    pf2 = DevicePrefetcher(resumed_mgr, depth=3, start_step=3, total_steps=100)
    try:
        resumed = [np.asarray(pf2.get()[0]["inputs"]) for _ in range(3)]
    finally:
        pf2.stop()
        resumed_mgr.stop()

    for got, want in zip(resumed, ref_batches[3:]):
        np.testing.assert_array_equal(got, want["inputs"])


# -- trainer integration: loss parity, checkpoints, breakdown ----------------

def _tiny_cfg(tmp_path, name, prefetch_depth, ckpt_interval=0):
    train = str(tmp_path / "train.jsonl")
    if not os.path.exists(train):
        _write_shard(train, 80)
    return Config.from_dict({
        "name": name,
        "overwrite": True,
        "data": {
            "source": "jsonl",
            "streaming": {"shards": [train], "shuffle_buffer": 8},
            "preprocessing": {"max_context_size": 32},
            "tokenizer": {"normal_vocab_size": 256},
            "prefetch_depth": prefetch_depth,
        },
        "model": {
            "architecture": "llama",
            "dimensions": {"hidden_size": 32, "intermediate_size": 64, "num_layers": 2},
            "attention": {"num_heads": 4, "num_kv_heads": 2, "head_dim": 8},
        },
        "training": {
            "hyperparameters": {"batch_size": 4, "learning_rate": 1e-2, "iters": 8},
            "optimization": {"optimizer": "adamw"},
            "scheduler": {"type": "cosine", "min_lr_ratio": 0.1},
        },
        "logging": {
            "steps": {"logging_interval": 2, "checkpoint_interval": ckpt_interval,
                      "validation_interval": 0},
        },
        "system": {"seed": 0},
    })


def _loss_series(run_dir):
    losses, fracs = [], []
    with open(os.path.join(run_dir, "log.txt")) as f:
        for line in f:
            if "loss=" in line and "tok/s=" in line:
                losses.append(line.split("loss=")[1].split()[0].rstrip("|"))
                assert "data_wait_frac=" in line, line
                fracs.append(float(
                    line.split("data_wait_frac=")[1].split()[0].rstrip("|")))
    return losses, fracs


def test_trainer_loss_parity_prefetch_on_vs_off(tmp_path):
    """Same seed, prefetch on vs off: identical batch sequence, identical
    losses (final loss bitwise), and both runs report data_wait_frac."""
    from mlx_cuda_distributed_pretraining_tpu.train.trainer import Trainer

    results, series = {}, {}
    for depth in (2, 0):
        cfg = _tiny_cfg(tmp_path, f"parity-d{depth}", depth)
        tr = Trainer(cfg, runs_root=str(tmp_path / f"runs-d{depth}"), quiet=True)
        results[depth] = tr.train()
        series[depth] = _loss_series(tr.run_dir)

    assert results[2]["steps"] == results[0]["steps"] == 8
    assert results[2]["final_loss"] == results[0]["final_loss"]  # bitwise
    losses_on, fracs_on = series[2]
    losses_off, fracs_off = series[0]
    assert losses_on == losses_off and len(losses_on) >= 4
    assert all(0.0 <= fr <= 1.0 for fr in fracs_on + fracs_off)


def test_depth0_books_h2d_wait(tmp_path):
    """``prefetch_depth: 0`` copies inline, so the copy is the step loop's
    own wall time: booked as ``h2d_wait_s`` on every window line and in the
    goodput ledger. With a worker (depth 2) the copy overlaps compute and
    books nothing; a stall there already shows as ``data_wait_s``."""
    from mlx_cuda_distributed_pretraining_tpu.obs.events import events_path, iter_events
    from mlx_cuda_distributed_pretraining_tpu.train.trainer import Trainer

    booked = {}
    for depth in (0, 2):
        cfg = _tiny_cfg(tmp_path, f"h2d-d{depth}", depth)
        tr = Trainer(cfg, runs_root=str(tmp_path / f"runs-h2d-d{depth}"), quiet=True)
        tr.train()
        assert tr.prefetcher.h2d_blocks_consumer is (depth == 0)
        events = list(iter_events(events_path(tr.run_dir)))
        windows = [e["goodput"] for e in events if e["type"] == "step_window"]
        [end] = [e for e in events if e["type"] == "run_end"]
        lines = [float(line.split("h2d_wait_s=")[1].split()[0].rstrip("|"))
                 for line in open(os.path.join(tr.run_dir, "log.txt"))
                 if "h2d_wait_s=" in line]
        assert len(windows) == len(lines) == 4
        booked[depth] = ([gp["h2d_wait_s"] for gp in windows], lines,
                         end["goodput_totals"]["h2d_wait_s"])

    windows, lines, total = booked[0]
    assert all(w > 0 for w in windows) and all(v > 0 for v in lines)
    assert total == pytest.approx(sum(windows), abs=1e-3)
    windows, lines, total = booked[2]
    assert windows == [0.0] * 4 and lines == [0.0] * 4 and total == 0.0


def test_trainer_checkpoint_position_prefetch_on_vs_off(tmp_path):
    """The mid-run checkpoint saves the CONSUMED loader position: with the
    device queue running ahead, step-4 state must equal the prefetch-off
    run's (batches in the queue don't count — PR 3 resume contract)."""
    from mlx_cuda_distributed_pretraining_tpu.train.trainer import Trainer

    states = {}
    for depth in (4, 0):
        cfg = _tiny_cfg(tmp_path, f"ckpt-d{depth}", depth, ckpt_interval=4)
        tr = Trainer(cfg, runs_root=str(tmp_path / f"runs-ckpt-d{depth}"), quiet=True)
        tr.train()
        _, _, state_path = tr.checkpoints.paths_for_step(4)
        with open(state_path) as f:
            states[depth] = json.load(f)

    assert states[4]["docs_consumed"] == states[0]["docs_consumed"]
    assert states[4]["step"] == states[0]["step"] == 4


# -- satellites: host-side schedule, compilation cache, stats gauge ----------

def test_schedule_value_matches_device_path():
    import jax.numpy as jnp

    from mlx_cuda_distributed_pretraining_tpu.optim import schedule_value
    from mlx_cuda_distributed_pretraining_tpu.optim.schedules import (
        build_schedule,
        warmup_cosine,
    )

    class TCfg:
        learning_rate = 2e-2

        def __init__(self, sched):
            self.scheduler = sched

    kinds = [
        {"type": "cosine_with_warmup", "warmup_steps": 10, "min_lr_ratio": 0.01},
        {"type": "cosine", "min_lr_ratio": 0.1},
        {"type": "linear", "min_lr_ratio": 0.0},
        {"type": "constant"},
    ]
    for sched in kinds:
        s = build_schedule(TCfg(sched), 100)
        for step in (0, 1, 9, 10, 50, 100):
            host = schedule_value(s, step)
            dev = float(s(jnp.asarray(step)))
            assert host == pytest.approx(dev, rel=1e-5, abs=1e-9), (sched, step)

    # Schedules without the xp keyword fall back to the device path.
    legacy = lambda step: jnp.asarray(3e-4, jnp.float32)
    assert schedule_value(legacy, 7) == pytest.approx(3e-4)
    # warmup boundary is exact in both paths
    w = warmup_cosine(1e-2, 100, 10)
    assert schedule_value(w, 10) == pytest.approx(1e-2, rel=1e-5)


def test_compilation_cache_enabled_and_logged(tmp_path):
    """The trainer's main() places the cache where the environment says
    (utils/compile_cache.py), populates it, and logs a cold/warm line. A
    child process: the suite itself runs with the cache off."""
    import subprocess
    import sys

    from conftest import device_env

    cache_dir = str(tmp_path / "xla-cache")
    cfg_path = str(tmp_path / "cache-run.yaml")
    _tiny_cfg(tmp_path, "cache-run", 2).to_yaml(cfg_path)
    env = device_env(1)
    env["JAX_ENABLE_COMPILATION_CACHE"] = "true"
    env["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    runs = str(tmp_path / "runs-cache")
    proc = subprocess.run(
        [sys.executable, "-m", "mlx_cuda_distributed_pretraining_tpu.train.trainer",
         "--config", cfg_path, "--runs-root", runs],
        env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert os.listdir(cache_dir), "the run compiled and cached nothing"
    with open(os.path.join(runs, "cache-run", "log.txt")) as f:
        log = f.read()
    assert f"compilation cache: {cache_dir}" in log
    assert "cold (will populate)" in log


def test_stats_state_mean_data_wait_frac_gauge():
    st = StatsState()
    st.handle({"type": "metrics", "worker_id": "w0", "step": 5,
               "data": {"loss": 2.0, "tok/s": 100.0, "data_wait_frac": 0.2}})
    st.handle({"type": "metrics", "worker_id": "w1", "step": 5,
               "data": {"loss": 2.0, "tok/s": 100.0, "data_wait_frac": 0.4}})
    agg = st.aggregated()
    assert agg["mean_data_wait_frac"] == pytest.approx(0.3)

    # training-only runs without the field keep the original shape
    st2 = StatsState()
    st2.handle({"type": "metrics", "worker_id": "w0", "step": 1,
                "data": {"loss": 2.0}})
    assert "mean_data_wait_frac" not in st2.aggregated()
