#!/usr/bin/env python3
"""chip_smoke.py — the standing proof that the main path runs on the chip.

One process (a chip belongs to one process at a time; the server runs in a
thread of it) drives, through the entry points a user calls:

1. device   ``jax.devices()``; anything but platform ``tpu`` fails.
2. kernels  flash fwd+bwd against ``reference_attention`` and gmm fwd+bwd
            against ``jax.lax.ragged_dot``, compiled, at the 1B/OLMoE widths.
3. train    ``train.trainer.main`` on configs/model-config-1b-singlechip.yaml
            (h2048, FFN 5632, 16 layers, 16 heads of 128, context 2048) for a
            handful of steps, one validation, one checkpoint.
4. serve    ``InferenceService.from_run`` on that run, the batch engine with
            the paged KV pool, ``serve()`` on port 0, concurrent ``/generate``
            requests over HTTP, greedy output checked against
            ``generate_text`` on the same params.

    python chip_smoke.py               # one TPU chip; what the driver runs
    python chip_smoke.py --multichip   # four chips: fsdp=4 against one device
    python chip_smoke.py --rehearse    # any machine: tiny widths, same paths

The last line of stdout is one JSON object. On a chip it is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
A rehearsal never says ``"ok": true``: it changes sizes, not paths, and
proves control flow, not the chip. Any failed check raises, so the exit
code is non-zero and no result line is printed.
"""

from __future__ import annotations

import argparse
import gc
import json
import math
import os
import shutil
import sys
import tempfile
import threading
import time
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

CONFIG = os.path.join(REPO, "configs", "model-config-1b-singlechip.yaml")
TRAIN_STEPS = 6
# Normalized max error (max|a-b| / max|b|) of a bf16 kernel against the same
# operation on the same bf16 values in float32 at highest matmul precision.
# bf16 keeps 8 significant bits (2^-8 ~ 4e-3 per rounding); a wrong mask,
# tile or expert shows up as O(1).
KERNEL_TOL = 2e-2
# |loss(fsdp=4) - loss(one device)| per step, same seed and global batch.
# The two differ only in tiling and in the order partial sums are reduced,
# but six steps in which the loss moves by up to a nat each amplify that
# (measured on four v5e chips, PR 21: at most 0.0056); a shard in the wrong
# place moves the loss by O(1).
MULTICHIP_LOSS_BAND = 0.05

# --rehearse: tests/test_trainer.py::_tiny_config-sized widths, applied as
# --set overrides on the SAME config file, so every path is the real one.
REHEARSE_MODEL = [
    "model.dimensions.hidden_size=32", "model.dimensions.intermediate_size=64",
    "model.dimensions.num_layers=2", "model.attention.num_heads=4",
    "model.attention.num_kv_heads=4", "model.attention.head_dim=8",
    "model.attention.max_position_embeddings=64",
    "data.preprocessing.max_context_size=64",
    "data.preprocessing.chunk_overlap=8",
    "training.hyperparameters.batch_size=4",
    # a 29k-parameter model moves at the recipe's own lr, not at the 1B's
    "training.hyperparameters.learning_rate=1e-2",
]


class SmokeFailure(AssertionError):
    pass


def check(cond, message: str) -> None:
    if not cond:
        raise SmokeFailure(message)


def say(message: str) -> None:
    print(message, flush=True)


class Phase:
    """Prints a phase's wall time; lets every exception through."""

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        say(f"== {self.name}")
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        dt = time.perf_counter() - self.t0
        say(f"== {self.name}: {'FAILED' if exc_type else 'ok'} in {dt:.1f} s")
        return False


# -- device ------------------------------------------------------------------
def phase_device(rehearse: bool, want_count: int) -> dict:
    from importlib import metadata

    import jax
    import jaxlib

    from mlx_cuda_distributed_pretraining_tpu import native

    devs = jax.devices()
    d = devs[0]
    device = {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}
    say(f"device: {json.dumps(device)} default_backend={jax.default_backend()} "
        f"processes={jax.process_count()}")
    say(f"versions: python {sys.version.split()[0]} jax {jax.__version__} "
        f"jaxlib {jaxlib.__version__} libtpu {metadata.version('libtpu')}")
    say(f"native/_dataplane.so: {native.status()}")
    if not rehearse:
        check(d.platform == "tpu",
              f"JAX found no TPU: platform is {d.platform!r}")
    check(len(devs) == want_count,
          f"this mode needs {want_count} device(s), JAX reports {len(devs)}")
    return device


def peak_hbm(label: str) -> None:
    import jax

    for d in jax.local_devices():
        stats = d.memory_stats()
        if stats is None:  # XLA:CPU keeps none; a rehearsal prints that
            say(f"memory_stats[{d.id}] after {label}: not reported by "
                f"platform {d.platform}")
            continue
        check("peak_bytes_in_use" in stats,
              f"memory_stats() has no peak_bytes_in_use: {sorted(stats)}")
        say(f"memory_stats[{d.id}] after {label}: peak_bytes_in_use="
            f"{stats['peak_bytes_in_use']} ({stats['peak_bytes_in_use'] / 2**30:.2f} GiB) "
            f"bytes_in_use={stats['bytes_in_use']} bytes_limit={stats.get('bytes_limit')}")


# -- kernels -----------------------------------------------------------------
def _nerr(got, want) -> float:
    import jax.numpy as jnp

    got = got.astype(jnp.float32)
    want = want.astype(jnp.float32)
    return float(jnp.max(jnp.abs(got - want)) / jnp.maximum(jnp.max(jnp.abs(want)), 1e-30))


def _compiled_with_kernel(fn, *args):
    """AOT-compile ``fn`` and say whether a Mosaic kernel is in the program."""
    compiled = fn.lower(*args).compile()
    return compiled, "tpu_custom_call" in compiled.as_text()


def phase_kernels(rehearse: bool, seed: int, on_tpu: bool) -> None:
    import jax
    import jax.numpy as jnp

    from mlx_cuda_distributed_pretraining_tpu.ops import masks
    from mlx_cuda_distributed_pretraining_tpu.ops.attention import reference_attention
    from mlx_cuda_distributed_pretraining_tpu.ops.flash_attention import flash_attention
    from mlx_cuda_distributed_pretraining_tpu.ops.grouped_matmul import gmm

    if rehearse:
        flash_cases = [
            ("causal", dict(B=1, S=128, Hq=4, Hkv=4, D=16, mask="causal", window=512)),
            ("gqa", dict(B=1, S=128, Hq=4, Hkv=2, D=16, mask="causal", window=512)),
            ("sliding_window", dict(B=1, S=128, Hq=4, Hkv=4, D=16,
                                    mask="sliding_window", window=32)),
        ]
        gmm_case = dict(T=512, K=64, N=128, E=4)
    else:
        flash_cases = [
            ("causal 16 heads of 128", dict(B=2, S=2048, Hq=16, Hkv=16, D=128,
                                            mask="causal", window=512)),
            ("gqa 12/4 heads of 64", dict(B=2, S=2048, Hq=12, Hkv=4, D=64,
                                          mask="causal", window=512)),
            ("sliding_window 512", dict(B=2, S=2048, Hq=16, Hkv=16, D=128,
                                        mask="sliding_window", window=512)),
        ]
        gmm_case = dict(T=8192, K=2048, N=1024, E=64)

    key = jax.random.PRNGKey(seed)
    for name, c in flash_cases:
        key, kq, kk, kv, kg = jax.random.split(key, 5)
        q = jax.random.normal(kq, (c["B"], c["S"], c["Hq"], c["D"]), jnp.bfloat16)
        k = jax.random.normal(kk, (c["B"], c["S"], c["Hkv"], c["D"]), jnp.bfloat16)
        v = jax.random.normal(kv, (c["B"], c["S"], c["Hkv"], c["D"]), jnp.bfloat16)
        g = jax.random.normal(kg, q.shape, jnp.bfloat16)
        mask_mod = (masks.causal() if c["mask"] == "causal"
                    else masks.sliding_window(c["window"]))

        # The cotangent g is an argument, not a captured constant: a
        # captured array is baked into the executable, and at 16 MB apiece
        # such programs thrash the chip machine's 192 MiB compile cache.
        def flash_loss(q, k, v, g, c=c):
            o = flash_attention(q, k, v, mask_type=c["mask"],
                                window_size=c["window"])
            return jnp.sum(o.astype(jnp.float32) * g.astype(jnp.float32)), o

        def ref_loss(q, k, v, g, mask_mod=mask_mod):
            o = reference_attention(q, k, v, mask_mod=mask_mod)
            return jnp.sum(o * g.astype(jnp.float32)), o

        flash_fn = jax.jit(jax.value_and_grad(flash_loss, argnums=(0, 1, 2), has_aux=True))
        t0 = time.perf_counter()
        compiled, has_kernel = _compiled_with_kernel(flash_fn, q, k, v, g)
        compile_s = time.perf_counter() - t0
        if on_tpu:
            check(has_kernel, f"flash {name}: no tpu_custom_call in the "
                              f"compiled program (interpreter or reference path)")
        (_, o), (dq, dk, dv) = compiled(q, k, v, g)
        with jax.default_matmul_precision("highest"):
            f32 = [t.astype(jnp.float32) for t in (q, k, v)]
            (_, o_r), (dq_r, dk_r, dv_r) = jax.jit(jax.value_and_grad(
                ref_loss, argnums=(0, 1, 2), has_aux=True))(*f32, g)
        errs = {"o": _nerr(o, o_r), "dq": _nerr(dq, dq_r),
                "dk": _nerr(dk, dk_r), "dv": _nerr(dv, dv_r)}
        say(f"flash {name} {list(q.shape)} bf16: kernel_in_hlo={has_kernel} "
            f"compile={compile_s:.1f}s normalized max err "
            + " ".join(f"{n}={e:.2e}" for n, e in errs.items())
            + f" (tol {KERNEL_TOL:.0e})")
        for n, e in errs.items():
            check(e == e and e <= KERNEL_TOL,
                  f"flash {name}: {n} differs from reference_attention by "
                  f"{e:.3e} > {KERNEL_TOL:.0e}")

    T, K, N, E = (gmm_case[x] for x in "TKNE")
    key, kx, kw, kg, ks = jax.random.split(key, 5)
    x = jax.random.normal(kx, (T, K), jnp.bfloat16)
    w = jax.random.normal(kw, (E, K, N), jnp.bfloat16) * (K ** -0.5)
    g = jax.random.normal(kg, (T, N), jnp.bfloat16)
    # Uneven groups, each a multiple of the 128-row tile (the dispatcher's
    # contract), one of them empty, summing to T.
    tiles = T // 128
    cuts = jnp.sort(jax.random.randint(ks, (E - 1,), 0, tiles + 1))
    sizes = jnp.diff(jnp.concatenate([jnp.zeros(1, cuts.dtype), cuts,
                                      jnp.full(1, tiles, cuts.dtype)]))
    sizes = (sizes.at[0].add(sizes[1]).at[1].set(0) * 128).astype(jnp.int32)

    def gmm_loss(x, w, g, sizes):
        y = gmm(x, w, sizes, backend="pallas")
        return jnp.sum(y.astype(jnp.float32) * g.astype(jnp.float32)), y

    def ragged_loss(x, w, g, sizes):
        y = jax.lax.ragged_dot(x, w, sizes)
        return jnp.sum(y * g.astype(jnp.float32)), y

    gmm_fn = jax.jit(jax.value_and_grad(gmm_loss, argnums=(0, 1), has_aux=True))
    t0 = time.perf_counter()
    compiled, has_kernel = _compiled_with_kernel(gmm_fn, x, w, g, sizes)
    compile_s = time.perf_counter() - t0
    if on_tpu:
        check(has_kernel, "gmm: no tpu_custom_call in the compiled program")
    (_, y), (dx, dw) = compiled(x, w, g, sizes)
    with jax.default_matmul_precision("highest"):
        (_, y_r), (dx_r, dw_r) = jax.jit(jax.value_and_grad(
            ragged_loss, argnums=(0, 1), has_aux=True))(
                x.astype(jnp.float32), w.astype(jnp.float32), g, sizes)
    errs = {"y": _nerr(y, y_r), "dx": _nerr(dx, dx_r), "dw": _nerr(dw, dw_r)}
    say(f"gmm/tgmm T={T} K={K} N={N} E={E} bf16 (groups {int(sizes.min())}.."
        f"{int(sizes.max())} rows): kernel_in_hlo={has_kernel} "
        f"compile={compile_s:.1f}s normalized max err "
        + " ".join(f"{n}={e:.2e}" for n, e in errs.items())
        + f" (tol {KERNEL_TOL:.0e})")
    for n, e in errs.items():
        check(e == e and e <= KERNEL_TOL,
              f"gmm: {n} differs from jax.lax.ragged_dot by {e:.3e} > "
              f"{KERNEL_TOL:.0e}")


# -- train -------------------------------------------------------------------
_WORDS = ("the of and to in a is that for it as was with be by on not he "
          "this are or his from at which but have an had they you were "
          "their one all we can her has there been if more when will would "
          "who so no chip step loss token batch layer kernel memory").split()


def write_corpus(path: str, seed: int, n_bytes: int) -> None:
    """Seeded pseudo-prose: a small vocabulary in random order, so byte
    statistics are learnable within a few steps and equal across batches."""
    import numpy as np

    rng = np.random.default_rng(seed)
    written = 0
    with open(path, "w") as f:
        while written < n_bytes:
            words = rng.choice(_WORDS, size=int(rng.integers(120, 260)))
            text = " ".join(words) + "."
            f.write(json.dumps({"text": text}) + "\n")
            written += len(text)


def run_sizes(rehearse: bool):
    """(context, global batch) of the run: the config's, or --rehearse's."""
    return (64, 4) if rehearse else (2048, 8)


def write_corpora(workdir: str, rehearse: bool, seed: int) -> None:
    ctx, batch = run_sizes(rehearse)
    write_corpus(os.path.join(workdir, "train.jsonl"), seed,
                 int(1.5 * batch * TRAIN_STEPS * (ctx + 1)))
    write_corpus(os.path.join(workdir, "val.jsonl"), seed + 1,
                 3 * batch * (ctx + 1))


def train_args(workdir: str, name: str, rehearse: bool, seed: int,
               extra=()) -> list:
    sets = [
        f"name={name}", f"system.seed={seed}",
        f"data.input_file={os.path.join(workdir, 'train.jsonl')}",
        f"data.validation_file={os.path.join(workdir, 'val.jsonl')}",
        f"training.hyperparameters.iters={TRAIN_STEPS}",
        # The recipe reaches lr 1e-2 over 2000 warm-up steps. Six steps
        # cannot: at 1e-2 after 2 steps the loss on the chip went 6.03 ->
        # 9.50 (first chip run, PR 21), at 5e-4 it went 6.03 -> 3.25.
        "training.scheduler.warmup_steps=2",
        "training.hyperparameters.learning_rate=5e-4",
        "logging.steps.logging_interval=1",
        # 0 = no interval events: the run ends with its one validation and
        # its one (blocking) checkpoint save
        "logging.steps.checkpoint_interval=0",
        "logging.steps.validation_interval=0",
    ]
    sets += REHEARSE_MODEL if rehearse else []
    sets += list(extra)
    argv = ["--config", CONFIG, "--runs-root", os.path.join(workdir, "runs")]
    for s in sets:
        argv += ["--set", s]
    return argv


def read_run(run_dir: str) -> dict:
    """Losses and mfu from the log protocol, events from events.jsonl."""
    losses, mfus, val = [], [], []
    with open(os.path.join(run_dir, "log.txt")) as f:
        log = f.read()
    for line in log.splitlines():
        if line.startswith("Step") and "validation:" in line:
            val.append(float(line.split("val_loss=")[1].split()[0]))
        elif line.startswith("Step") and "loss=" in line:
            losses.append(float(line.split("loss=")[1].split(" |")[0]))
            mfus.append(line.split("mfu=")[1].split(" |")[0].strip())
    events = []
    with open(os.path.join(run_dir, "events.jsonl")) as f:
        for line in f:
            events.append(json.loads(line))
    return {"log": log, "losses": losses, "mfu": mfus, "val": val, "events": events}


def run_trainer(workdir: str, name: str, rehearse: bool, seed: int, device: dict,
                extra=()) -> dict:
    """One run through ``trainer.main`` (what train.py calls), then the
    checks every such run must meet. Returns what read_run parsed."""
    from mlx_cuda_distributed_pretraining_tpu.train import trainer

    t0 = time.perf_counter()
    result = trainer.main(train_args(workdir, name, rehearse, seed, extra))
    wall = time.perf_counter() - t0
    run_dir = os.path.join(workdir, "runs", name)
    run = read_run(run_dir)
    first_line = run["log"].splitlines()[0]
    start = next(e for e in run["events"] if e.get("type") == "run_start")
    compile_ev = next(e for e in run["events"] if e.get("type") == "compile")
    say(f"train[{name}]: {result} in {wall:.1f} s; first dispatch (compile + "
        f"step 1) {compile_ev['seconds']} s")
    say(f"train[{name}] first log line: {first_line}")
    say(f"train[{name}] run_start: platform={start['platform']} "
        f"device_kind={start['device_kind']} n_chips={start['n_chips']} "
        f"n_processes={start['n_processes']} xla_backend={start['xla_backend']} "
        f"n_params={start['n_params']} peak_flops={start['peak_flops']}")
    say(f"train[{name}] losses: {run['losses']} val: {run['val']} mfu: {run['mfu']}")
    check(result["steps"] == TRAIN_STEPS and len(run["losses"]) == TRAIN_STEPS,
          f"expected {TRAIN_STEPS} logged steps, got {run['losses']}")
    check(all(math.isfinite(x) for x in run["losses"]),
          f"non-finite loss: {run['losses']}")
    check(run["losses"][-1] < run["losses"][0],
          f"last loss {run['losses'][-1]} is not below first {run['losses'][0]}")
    check(len(run["val"]) == 1 and math.isfinite(run["val"][0]),
          f"expected one finite validation, got {run['val']}")
    # The run names its device itself, and names the one JAX reports.
    check(f"platform={device['platform']} kind={device['kind']}" in first_line,
          f"first log line does not name the device: {first_line}")
    check((start["platform"], start["device_kind"]) == (device["platform"], device["kind"]),
          f"run_start names another device: {start}")
    check(start["xla_backend"] == device["platform"],
          f"XLA flag set resolved for {start['xla_backend']!r}, run is on "
          f"{device['platform']!r} (parallel/xla_flags.py guess_backend)")
    if device["platform"] == "cpu":
        check(set(run["mfu"]) == {"unknown"}, f"CPU mfu must be unknown: {run['mfu']}")
    else:
        check(all(0.0 < float(m) < 1.0 for m in run["mfu"][1:]),
              f"mfu= is not a utilization on every window line: {run['mfu']}")
    for suffix in ("model.safetensors", "optimizer.safetensors", "state.json"):
        path = os.path.join(run_dir, "checkpoints", f"step_final_{suffix}")
        check(os.path.isfile(path) and os.path.getsize(path) > 0,
              f"checkpoint file missing: {path}")
    ckpt = os.path.join(run_dir, "checkpoints", "step_final_model.safetensors")
    say(f"train[{name}] checkpoint: {ckpt} ({os.path.getsize(ckpt) / 2**20:.0f} MiB)")
    run["dir"] = run_dir
    return run


def probe_trainer(workdir: str, name: str, rehearse: bool, seed: int, extra=()):
    """A second Trainer on the same config: same program, but the object is
    ours, so the compiled step can be read and timed."""
    from mlx_cuda_distributed_pretraining_tpu.train import trainer

    args = trainer.build_parser().parse_args(
        train_args(workdir, name, rehearse, seed, extra))
    return trainer.Trainer(trainer.config_from_args(args),
                           runs_root=args.runs_root, quiet=True)


def phase_train(workdir: str, rehearse: bool, seed: int, device: dict) -> str:
    say(f"train: {os.path.relpath(CONFIG, REPO)} "
        + ("at --rehearse widths (h32, 2 layers, context 64, batch 4)" if rehearse
           else "at h2048 FFN5632 16 layers 16 heads of 128 context 2048 batch 8; "
                "cut: nothing (batch and depth as configured)"))
    write_corpora(workdir, rehearse, seed)
    run = run_trainer(workdir, "chip-smoke", rehearse, seed, device)
    peak_hbm("train")
    gc.collect()
    step_timing(workdir, rehearse, seed, device)
    return run["dir"]


def step_timing(workdir: str, rehearse: bool, seed: int, device: dict) -> None:
    """The compiled step: is the flash kernel in the program, and how long
    does a step take when the timing ends in block_until_ready, and when it
    ends in a host fetch of the loss?"""
    import jax

    from mlx_cuda_distributed_pretraining_tpu.train.trainer import _device_batch

    ctx, batch = run_sizes(rehearse)
    tr = probe_trainer(workdir, "chip-smoke-probe", rehearse, seed)
    data = [_device_batch(tr.data.generate_batch(i)) for i in range(3)]
    t0 = time.perf_counter()
    compiled = tr.train_step.lower(tr.state, data[0]).compile()
    say(f"train step compile, second time in this process: "
        f"{time.perf_counter() - t0:.1f} s (persistent cache on = a disk load)")
    has_kernel = "tpu_custom_call" in compiled.as_text()
    say(f"train step HLO: tpu_custom_call present = {has_kernel}")
    ma = compiled.memory_analysis()
    say(f"train step memory_analysis (the compiler's accounting, bytes): "
        f"arguments={ma.argument_size_in_bytes} outputs={ma.output_size_in_bytes} "
        f"aliased={ma.alias_size_in_bytes} temp={ma.temp_size_in_bytes}")
    if device["platform"] == "tpu":
        check(has_kernel, "the compiled 1B train step has no tpu_custom_call: "
                          "flash attention is interpreted or replaced")
    state = tr.state
    state, m = compiled(state, data[0])
    jax.block_until_ready(m["loss"])
    timings = {"block_until_ready": [], "host fetch of loss": []}
    for label in timings:
        for b in data:
            t0 = time.perf_counter()
            state, m = compiled(state, b)
            if label == "block_until_ready":
                jax.block_until_ready(m["loss"])
            else:
                float(m["loss"])
            timings[label].append(1e3 * (time.perf_counter() - t0))
    t0 = time.perf_counter()
    state, m = compiled(state, data[0])
    enqueue_ms = 1e3 * (time.perf_counter() - t0)
    jax.block_until_ready(m["loss"])
    for label, ms in timings.items():
        say(f"train step ms on {device['kind']} ending in {label}: "
            + " ".join(f"{x:.1f}" for x in ms))
    say(f"train step ms to enqueue only (no sync): {enqueue_ms:.1f}")
    tokens = batch * ctx
    best = min(timings["block_until_ready"])
    say(f"train step best {best:.1f} ms = {tokens / best * 1e3:.0f} tok/s at "
        f"{tokens} tokens/step on {device['platform']} ({device['kind']})")
    tr.logger.close()
    del tr, state, compiled, data, m
    gc.collect()


# -- serve -------------------------------------------------------------------
def phase_serve(run_dir: str, rehearse: bool, device: dict) -> None:
    import jax

    from mlx_cuda_distributed_pretraining_tpu.infer.generate import generate_text
    from mlx_cuda_distributed_pretraining_tpu.infer.server import (
        InferenceService, request_generate, request_stream, serve)
    from mlx_cuda_distributed_pretraining_tpu.serve import EngineConfig

    n_tokens = 8 if rehearse else 16
    prompts = ["the chip and the", "loss of a batch", "memory for the step",
               "when the kernel was"]
    t0 = time.perf_counter()
    service = InferenceService.from_run(run_dir)
    say(f"serve: loaded {service.n_params / 1e6:.1f}M params from {run_dir} "
        f"in {time.perf_counter() - t0:.1f} s")
    leaf = jax.tree_util.tree_leaves(service.params)[0]
    check({d.platform for d in leaf.devices()} == {device["platform"]},
          f"served params live on {leaf.devices()}, not on {device['platform']}")
    # The batch engine over the paged KV pool, prefix cache on: the server's
    # --engine batch defaults (infer/server.py main).
    cfg = EngineConfig()
    check(cfg.kv_backend == "paged", "EngineConfig no longer defaults to paged KV")
    engine = service.attach_engine(cfg)
    httpd = serve(service, port=0)
    url = f"http://127.0.0.1:{httpd.server_address[1]}"
    try:
        check({d.platform for d in jax.tree_util.tree_leaves(
            engine.pool.cache)[0].devices()} == {device["platform"]},
            "the KV pool is not on the device")
        t0 = time.perf_counter()
        engine.warmup()
        say(f"serve: engine warm-up (prefill + decode compiles) "
            f"{time.perf_counter() - t0:.1f} s at {url}")

        results = {}

        def plain(i):
            results[i] = request_generate(url, prompts[i], max_tokens=n_tokens,
                                          temperature=0.0)

        def streamed(i):
            results[i] = list(request_stream(url, prompts[i], max_tokens=n_tokens,
                                             temperature=0.0))

        threads = [threading.Thread(target=streamed if i == 0 else plain, args=(i,))
                   for i in range(len(prompts))]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600)
        wall = time.perf_counter() - t0
        check(not any(t.is_alive() for t in threads) and len(results) == len(prompts),
              f"only {len(results)} of {len(prompts)} requests answered "
              f"(a non-200 raises in its thread; see stderr)")
        events = results[0]
        final = events[-1]
        check(final.get("done") is True, f"stream ended without done: {final}")
        stream_ids = [e["token"] for e in events[:-1]]
        results[0] = final
        for i, r in sorted(results.items()):
            say(f"serve: request {i} {'(streamed) ' if i == 0 else ''}"
                f"{prompts[i]!r} -> {r['tokens']} tokens {r['text']!r} "
                f"finish={r.get('finish_reason')} ttft_ms={r.get('ttft_ms')} "
                f"engine={r.get('engine')}")
            check(r.get("engine") == "batch", f"request {i} bypassed the batch engine")
            check(r["tokens"] == n_tokens or r.get("finish_reason") == "stop",
                  f"request {i}: {r['tokens']} tokens, asked for {n_tokens}")
        check(len(stream_ids) == final["tokens"],
              f"stream carried {len(stream_ids)} token events for {final['tokens']} tokens")
        total = sum(int(r["tokens"]) for r in results.values())
        say(f"serve: {len(prompts)} concurrent requests, {total} tokens in "
            f"{wall:.2f} s wall on {device['platform']} ({device['kind']})")

        # Greedy parity with the single-stream decoder on the same params.
        for i in (0, 1):
            text, stats = generate_text(service.params, service.args, service.tokenizer,
                                        prompts[i], max_new_tokens=n_tokens,
                                        temperature=0.0, return_stats=True)
            same = (text == results[i]["text"]
                    and int(stats["generation_tokens"]) == int(results[i]["tokens"]))
            say(f"serve: generate_text {prompts[i]!r} -> {text!r}: "
                f"{'token-identical' if same else 'DIFFERS'}")
            check(same, f"batch engine {results[i]['text']!r} != generate_text {text!r}")
        check(service.tokenizer.detokenize(stream_ids) == results[0]["text"],
              "streamed token ids do not spell the final text")

        with urllib.request.urlopen(url + "/metrics", timeout=30) as resp:
            check(resp.status == 200, f"/metrics answered {resp.status}")
            metrics = json.loads(resp.read())
        say("serve: /metrics " + json.dumps({k: metrics.get(k) for k in (
            "iterations", "completed", "kv_backend", "kv_num_blocks",
            "prefix_cache", "mesh", "weight_dtype")}))
        check(int(metrics.get("completed", 0)) >= len(prompts),
              f"/metrics counts {metrics.get('completed')} completed requests")
    finally:
        httpd.shutdown()
        httpd.server_close()
        service.close()
    check(engine._thread is None or not engine._thread.is_alive(),
          "the engine thread outlived close()")
    peak_hbm("serve")


# -- four chips --------------------------------------------------------------
def phase_multichip(workdir: str, rehearse: bool, seed: int, device: dict) -> None:
    import jax

    _, batch = run_sizes(rehearse)
    write_corpora(workdir, rehearse, seed)
    fsdp = ['system.mesh={"fsdp": 4}', "system.zero_optimization_level=1"]
    one = ['system.mesh={"dp": 1}']
    say(f"multichip: {os.path.relpath(CONFIG, REPO)} global batch {batch}, "
        f"seed {seed}: fsdp=4 over {len(jax.devices())} devices, then one device")
    sharded = run_trainer(workdir, "chip-smoke-fsdp4", rehearse, seed, device, fsdp)
    peak_hbm("fsdp=4 train")
    gc.collect()

    # Who holds what: a probe Trainer on the same mesh, before the
    # one-device run puts anything else on device 0.
    tr = probe_trainer(workdir, "chip-smoke-fsdp4-probe", rehearse, seed, fsdp)
    check(tr.mesh is not None and dict(tr.mesh.shape) == {"fsdp": 4},
          f"trainer mesh is {tr.mesh}")
    w = tr.state["params"]["layers"][0]["feed_forward"]["w_gate"]["weight"]
    shards = [(s.device.id, tuple(s.data.shape)) for s in w.addressable_shards]
    say(f"multichip: layers[0].feed_forward.w_gate.weight {tuple(w.shape)} "
        f"{w.sharding.spec}: shards {shards}")
    check(len({d for d, _ in shards}) == 4,
          f"the parameter lives on {len({d for d, _ in shards})} device(s), not 4")
    check(all(4 * math.prod(s) == int(w.size) for _, s in shards),
          f"shards are not quarters of {tuple(w.shape)}: {shards}")
    in_use = {}
    for d in jax.local_devices():
        stats = d.memory_stats()
        if stats is not None:
            in_use[d.id] = stats["bytes_in_use"]
    say(f"multichip: bytes_in_use per device with only the sharded state "
        f"resident: {in_use if in_use else 'not reported by ' + device['platform']}")
    if in_use:
        check(len(in_use) == 4 and min(in_use.values()) > 0
              and max(in_use.values()) < 1.5 * min(in_use.values()),
              f"the four devices do not hold equal shares: {in_use}")
    tr.logger.close()
    del tr, w
    gc.collect()

    single = run_trainer(workdir, "chip-smoke-one", rehearse, seed, device, one)
    deltas = [abs(a - b) for a, b in zip(sharded["losses"], single["losses"])]
    say("multichip: |loss(fsdp=4) - loss(one device)| per step: "
        + " ".join(f"{d:.4f}" for d in deltas)
        + f" (band {MULTICHIP_LOSS_BAND})")
    check(max(deltas) <= MULTICHIP_LOSS_BAND,
          f"fsdp=4 and one-device losses differ by {max(deltas):.4f}")

    # Tensor-parallel serving of the run just trained: the server's --mesh
    # tp=4 path (reshard on load, sharded KV pool) against no mesh.
    from mlx_cuda_distributed_pretraining_tpu.infer.server import InferenceService
    from mlx_cuda_distributed_pretraining_tpu.parallel import build_serve_mesh
    from mlx_cuda_distributed_pretraining_tpu.serve import EngineConfig

    prompts = ["the chip and the", "loss of a batch"]
    n_tokens = 8 if rehearse else 16
    texts = {}
    for label, sizes in (("no mesh", None), ("tp=4", {"tp": 4})):
        mesh = build_serve_mesh(sizes)
        service = InferenceService.from_run(single["dir"], mesh=mesh)
        engine = service.attach_engine(EngineConfig(mesh=sizes), mesh=mesh)
        try:
            t0 = time.perf_counter()
            texts[label] = [engine.generate(p, max_tokens=n_tokens,
                                            temperature=0.0)["text"] for p in prompts]
            wq = jax.tree_util.tree_leaves(engine.params["layers"][0]["attention"]["wq"])[0]
            say(f"multichip: batch engine, {label}: {texts[label]} in "
                f"{time.perf_counter() - t0:.1f} s (compiles included); wq on "
                f"{len(wq.devices())} device(s) {wq.sharding.spec if mesh else ''}")
            check(len(wq.devices()) == (4 if mesh else 1),
                  f"{label}: wq lives on {len(wq.devices())} device(s)")
        finally:
            service.close()
        del service, engine, wq
        gc.collect()
    check(texts["tp=4"] == texts["no mesh"],
          f"tp=4 serving is not token-identical to no mesh: {texts}")
    say("multichip: batch engine under --mesh tp=4 is token-identical to no mesh")


def _cache_bytes() -> int:
    from mlx_cuda_distributed_pretraining_tpu.utils import compile_cache

    root = compile_cache.cache_dir()
    if not os.path.isdir(root):
        return 0
    return sum(os.path.getsize(os.path.join(root, f)) for f in os.listdir(root))


# -- main --------------------------------------------------------------------
def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--seed", type=int, default=0,
                   help="seeds the corpus, the kernels' inputs and the model")
    p.add_argument("--rehearse", action="store_true",
                   help="tiny widths on whatever JAX finds; sizes change, "
                        "paths do not; never prints \"ok\": true")
    p.add_argument("--multichip", action="store_true",
                   help="four chips: fsdp=4 training against one device of "
                        "the same host, and no other phase")
    args = p.parse_args(argv)

    t_start = time.perf_counter()
    # Before the first compile, like every entry point of the program.
    from mlx_cuda_distributed_pretraining_tpu.utils import compile_cache

    say(compile_cache.enable_compilation_cache())
    entries_before, bytes_before = compile_cache.cache_entries(), _cache_bytes()
    workdir = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        with Phase("device"):
            device = phase_device(args.rehearse, 4 if args.multichip else 1)
        if args.multichip:
            with Phase("multichip"):
                phase_multichip(workdir, args.rehearse, args.seed, device)
        else:
            with Phase("kernels"):
                phase_kernels(args.rehearse, args.seed, device["platform"] == "tpu")
            with Phase("train"):
                run_dir = phase_train(workdir, args.rehearse, args.seed, device)
            with Phase("serve"):
                phase_serve(run_dir, args.rehearse, device)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    say(f"compilation cache {compile_cache.cache_dir()}: {entries_before} entries "
        f"({bytes_before / 2**20:.0f} MiB) before, {compile_cache.cache_entries()} "
        f"({_cache_bytes() / 2**20:.0f} MiB) after")
    say(f"total {time.perf_counter() - t_start:.1f} s")
    if args.rehearse:
        print(json.dumps({"ok": False, "rehearsal": "passed", "device": device}),
              flush=True)
    else:
        print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
