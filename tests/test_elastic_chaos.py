"""Host-kill chaos harness (ISSUE 13 acceptance).

A 2-supervisor fleet (2 simulated hosts x 2 CPU devices, fsdp=4 across the
world) takes a SIGKILL on one host's trainer mid-run. The fleet must:

- resume through the generation barrier + restart-marker protocol and run
  to completion (both supervisors exit 0),
- reproduce the uninterrupted 2-process baseline's per-step losses
  bit-identically after the restarted window (which also proves zero
  skipped/replayed documents — the loss sequence pins the exact doc order),
- book the lost wall clock as ``restart`` events with goodput >= 95% read
  off the ledger (components still sum to the window wall time).

Python-level mirror of ``scripts/chaos_train.sh``.
"""

import json
import os
import signal
import socket
import subprocess
import sys
import time

import numpy as np
import pytest
import yaml

from conftest import device_env

from mlx_cuda_distributed_pretraining_tpu.parallel.elastic import read_membership

BATCH, SEQ, ITERS = 8, 64, 24


def _write_inputs(workdir, vocab=256):
    shard_dir = os.path.join(workdir, "shards")
    os.makedirs(shard_dir)
    n_tokens = (ITERS + 8) * BATCH * (SEQ + 1)
    rng = np.random.default_rng(0)
    arr = rng.integers(1, vocab - 4, size=n_tokens).astype(np.uint16)
    arr.tofile(os.path.join(shard_dir, "shard_00000.bin"))
    with open(os.path.join(shard_dir, "index.json"), "w") as f:
        json.dump({"dtype": "uint16", "shard_tokens": n_tokens,
                   "total_tokens": n_tokens, "files": ["shard_00000.bin"],
                   "vocab_size": vocab, "eos_id": 0}, f)
    return shard_dir


def _write_cfg(workdir, name, shard_dir):
    cfg = {
        "name": name,
        "overwrite": False,
        "data": {"source": "token_shards", "input_file": shard_dir,
                 "preprocessing": {"max_context_size": SEQ},
                 "tokenizer": {"default": "byte"}},
        "model": {
            "architecture": "llama",
            "dimensions": {"hidden_size": 64, "intermediate_size": 128,
                           "num_layers": 2, "num_heads": 4},
            "attention": {"num_kv_heads": 4, "head_dim": 16,
                          "max_position_embeddings": SEQ,
                          "attention_type": "simple"},
            "misc": {"vocab_size": 256},
        },
        "training": {
            "hyperparameters": {"batch_size": BATCH, "learning_rate": 1e-3,
                                "iters": ITERS, "gradient_clip": 1.0},
            "scheduler": {"type": "cosine_with_warmup", "warmup_steps": 2},
            "optimization": {"optimizer": "adamw"},
        },
        "logging": {"steps": {"logging_interval": 1,
                              "checkpoint_interval": 4,
                              "validation_interval": 0}},
        "system": {"seed": 0, "compute_dtype": "float32",
                   "mesh": {"fsdp": 4}},
        # hang_timeout_s 0: the fleet watchdog still runs (process_count>1)
        # but only for peer restart markers — no stale-heartbeat false
        # positives during the cold compile, and a tight 0.5s marker poll
        # keeps restart_lost_s in single-digit seconds.
        "supervisor": {"hang_timeout_s": 0.0, "hang_kill_grace_s": 1.0,
                       "barrier_timeout_s": 90.0},
    }
    path = os.path.join(workdir, f"{name}.yaml")
    with open(path, "w") as f:
        yaml.safe_dump(cfg, f)
    return path


def _free_port():
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def _launch_fleet(cfg_path, runs_root, workdir, tag):
    port = _free_port()
    # Ask for the persistent compile cache, as a deployment would: on
    # multi-process CPU the helper must refuse it (cached executables do
    # not survive gloo re-initialization), whatever the environment says.
    env = device_env(2)
    env["JAX_ENABLE_COMPILATION_CACHE"] = "true"
    env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(workdir, "xla_cache")
    procs = []
    for i in range(2):
        log = open(os.path.join(workdir, f"{tag}_sup_p{i}.log"), "w")
        procs.append(subprocess.Popen(
            [sys.executable, "-m",
             "mlx_cuda_distributed_pretraining_tpu.train.trainer",
             "--config", cfg_path, "--runs-root", runs_root,
             "--auto-resume", "--max-crashes", "5", "--backoff-base", "0.1",
             "--coordinator", f"localhost:{port}",
             "--num-processes", "2", "--process-id", str(i)],
            env=env, stdout=log, stderr=subprocess.STDOUT))
    return procs


def _wait_fleet(procs, workdir, tag, deadline_s=420):
    t0 = time.time()
    rcs = []
    for p in procs:
        try:
            rcs.append(p.wait(timeout=max(5.0, deadline_s - (time.time() - t0))))
        except subprocess.TimeoutExpired:
            p.kill()
            rcs.append(-9)
    if rcs != [0, 0]:
        logs = ""
        for i in range(2):
            path = os.path.join(workdir, f"{tag}_sup_p{i}.log")
            with open(path) as f:
                logs += f"\n--- {path} ---\n" + f.read()[-4000:]
        raise AssertionError(f"{tag} fleet rcs={rcs}{logs}")
    return rcs


def _events(run_dir):
    out = []
    with open(os.path.join(run_dir, "events.jsonl")) as f:
        for line in f:
            try:
                out.append(json.loads(line))
            except ValueError:
                continue
    return out


def _last_losses(events):
    # Last occurrence wins: the chaos run logs a step twice when the
    # restarted generation replays the window after the checkpoint.
    losses = {}
    for ev in events:
        if ev.get("type") == "step_window":
            losses[int(ev["step"])] = float(ev["loss"])
    return losses


@pytest.mark.slow
def test_host_kill_chaos_resumes_with_loss_parity(tmp_path):
    workdir = str(tmp_path)
    shard_dir = _write_inputs(workdir)

    # Uninterrupted 2-process baseline.
    base_cfg = _write_cfg(workdir, "chaos-base", shard_dir)
    base_root = os.path.join(workdir, "runs_base")
    _wait_fleet(_launch_fleet(base_cfg, base_root, workdir, "base"),
                workdir, "base")
    base_losses = _last_losses(_events(os.path.join(base_root, "chaos-base")))
    assert sorted(base_losses) == list(range(1, ITERS + 1)), base_losses
    with open(os.path.join(base_root, "chaos-base", "log.txt")) as f:
        assert "compilation cache: disabled on multi-process CPU" in f.read()
    assert not os.path.isdir(os.path.join(workdir, "xla_cache"))

    # Chaos fleet: SIGKILL host 1's trainer once it has progressed past the
    # step-4 checkpoint (pid comes from its per-host heartbeat file).
    chaos_cfg = _write_cfg(workdir, "chaos", shard_dir)
    chaos_root = os.path.join(workdir, "runs_chaos")
    run_dir = os.path.join(chaos_root, "chaos")
    procs = _launch_fleet(chaos_cfg, chaos_root, workdir, "chaos")
    killed = False
    hb_path = os.path.join(run_dir, "heartbeat_p1.json")
    t0 = time.time()
    while time.time() - t0 < 420 and any(p.poll() is None for p in procs):
        if not killed and os.path.isfile(hb_path):
            try:
                with open(hb_path) as f:
                    hb = json.load(f)
            except (OSError, ValueError):
                hb = {}
            if int(hb.get("step") or 0) >= 5 and hb.get("pid"):
                os.kill(int(hb["pid"]), signal.SIGKILL)
                killed = True
        time.sleep(0.25)
    assert killed, "host 1's trainer never reached step 5 within the deadline"
    _wait_fleet(procs, workdir, "chaos")

    events = _events(run_dir)

    # The fleet restarted as a new generation and recorded who joined it.
    restarts = [ev for ev in events if ev.get("type") == "restart"]
    assert restarts and all(ev.get("generation", 2) >= 2 for ev in restarts)
    membership = read_membership(run_dir)
    assert membership and int(membership["generation"]) >= 2, membership
    assert int(membership["process_count"]) == 2, membership

    # Loss parity: every step the chaos run (re)computed must match the
    # uninterrupted baseline bit-for-bit — same params, same documents.
    chaos_losses = _last_losses(events)
    assert sorted(chaos_losses) == sorted(base_losses), chaos_losses
    for step, want in sorted(base_losses.items()):
        assert chaos_losses[step] == want, (step, chaos_losses[step], want)

    # Ledger goodput: lost wall clock is booked, components still sum to
    # each window's wall time, and goodput = comp/(comp+lost) >= 95%.
    lost = sum(float(ev.get("lost_s") or 0.0) for ev in restarts)
    assert lost > 0.0, restarts
    comp = 0.0
    for ev in events:
        if ev.get("type") != "step_window":
            continue
        gp = ev.get("goodput") or {}
        assert "other_s" in gp and all(
            isinstance(v, (int, float)) and v >= -1e-9 for v in gp.values()), ev
        comp += sum(gp.values())
    assert comp > 0.0
    goodput = comp / (comp + lost)
    assert goodput >= 0.95, (goodput, comp, lost)
