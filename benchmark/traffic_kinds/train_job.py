"""Traffic kind ``train_job``: one pretraining job through the program's own
``Trainer.train()`` loop (loader, device prefetch, jitted step, logging), fed
token shards written from the seed.

Set-up builds ONE Trainer, swaps in weights made from the seed by the
benchmark, and lets ``train()`` drive it: the first ``checked_steps`` steps are
the ones the plain reference follows afterwards, ``warm_steps`` more settle the
loop, and the window opens on the next step boundary of that same object. The
benchmark's only handle on the loop is a wrapper around ``trainer.train_step``
(a span around the call into the step layer): it stamps each step's start and
its end in ``block_until_ready``, keeps the first batches, reads the optimizer
state after step 1 and the weights after the last checked step, and ends the
job by raising once a step finishes past the window.
"""

from __future__ import annotations

import gc
import json
import math
import os
import time
from typing import Any, Dict, List, Optional

import numpy as np

from benchmark import synthetic
from benchmark.flops import llama_dense as flops
from benchmark.reference import llama_dense as ref
from benchmark.reference import optimizers as ref_opt


class _WindowClosed(Exception):
    """Raised from the step wrapper to end ``Trainer.train()`` at the window's
    end without its final validation and checkpoint."""


def trainer_config(ctx, shard_dir: str) -> Dict[str, Any]:
    c, job = ctx.config, ctx.mix
    opt = dict(job["optimizer"])
    name = opt.pop("name")
    hyper = {"batch_size": int(job["batch_size"]), "iters": 1_000_000,
             "learning_rate": opt.pop("learning_rate"),
             "weight_decay": opt.pop("weight_decay", 0.0),
             "gradient_clip": opt.pop("gradient_clip", None)}
    return {
        "name": "bench-" + ctx.cell["name"].replace(".", "-"),
        "overwrite": True,
        "data": {"source": "token_shards", "input_file": shard_dir,
                 "preprocessing": {"max_context_size": int(job["seq_len"]), "chunk_overlap": 0},
                 "prefetch_depth": int(job["prefetch_depth"])},
        "model": {
            "architecture": "llama",
            "dimensions": {"hidden_size": c["hidden_size"],
                           "intermediate_size": c["intermediate_size"],
                           "num_layers": c["num_hidden_layers"]},
            "attention": {"num_heads": c["num_attention_heads"],
                          "num_kv_heads": c["num_key_value_heads"],
                          "head_dim": c["head_dim"],
                          "max_position_embeddings": c["max_position_embeddings"],
                          "attention_type": job["attention_type"]},
            "normalization": {"rms_norm_eps": c["rms_norm_eps"]},
            "rope": {"theta": c["rope_theta"]},
            "misc": {"attention_bias": False, "mlp_bias": False,
                     "tie_word_embeddings": bool(c["tie_word_embeddings"])},
        },
        "training": {"hyperparameters": hyper, "scheduler": {"type": "constant"},
                     "optimization": {"optimizer": name, **opt}},
        "logging": {"steps": {"logging_interval": int(job["logging_interval"]),
                              "checkpoint_interval": 0, "validation_interval": 0}},
        "system": {"seed": int(ctx.seed % (2 ** 31)), "compute_dtype": job["compute_dtype"],
                   "remat": job["remat"], "gradient_checkpointing_ratio": 1.0,
                   "scan_layers": bool(job["scan_layers"]),
                   "mesh": dict(job["mesh"] or {}),
                   "zero_optimization_level": int(job["zero_optimization_level"]),
                   "async_checkpointing": True},
    }


# -- readings of the program's state ---------------------------------------------
def _leaf_names(tree) -> List[str]:
    import jax

    return ["/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
            for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def program_first_gradient_norms(opt_name: str, opt_state, params, hp):
    """Per-leaf norm of the step-1 gradient as the update rule got it, from
    the program's optimizer state (a chain: [clip, moments, ...])."""
    import jax
    import jax.numpy as jnp

    moments = opt_state[1]
    if opt_name == "adamw":
        b1 = float(hp.get("betas", (0.9, 0.999))[0])
        return jax.tree_util.tree_map(
            lambda m: jnp.sqrt(jnp.sum(jnp.square(m))) / (1 - b1), moments["mu"])
    if opt_name == "adafactor":
        def leaf(p, v_row, v):
            f = ref_opt.factored_axes(p.shape)
            if f is None:
                return jnp.sqrt(jnp.sum(v))
            return jnp.sqrt(jnp.sum(v_row) * p.shape[f[1]])
        return jax.tree_util.tree_map(leaf, params, moments["v_row"], moments["v"])
    raise ValueError(f"no reader for the optimizer state of {opt_name!r}")


def program_first_gradient_profiles(opt_name: str, opt_state, hp):
    """The program's step-1 second-moment statistics, leaf by leaf, in the
    reference's order and layout (``reference/optimizers.py``)."""
    import jax
    import jax.numpy as jnp

    moments = opt_state[1]
    if opt_name == "adamw":
        b1 = float(hp.get("betas", (0.9, 0.999))[0])
        return [ref_opt._profile(jnp.square(m / (1 - b1)))
                for m in jax.tree_util.tree_leaves(moments["mu"])]
    if opt_name == "adafactor":
        rows, cols, full = (jax.tree_util.tree_leaves(moments[k])
                            for k in ("v_row", "v_col", "v"))
        return [v.ravel() if v.size > 1 or r.size <= 1
                else jnp.concatenate([r.ravel(), c.ravel()])
                for r, c, v in zip(rows, cols, full)]
    raise ValueError(f"no reader for the optimizer state of {opt_name!r}")


def change_norms(params, seed: int, cfg):
    """Per-leaf |params - initial weights|, the initial weights regenerated
    from the seed inside the same program."""
    import jax
    import jax.numpy as jnp

    def fn(p, s):
        p0 = ref.make_params(s, cfg)
        return jax.tree_util.tree_map(
            lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32) - b))), p, p0)

    return jax.jit(fn)(params, jnp.uint32(seed % (2 ** 32)))


def _host_list(tree) -> List[float]:
    import jax

    return [float(x) for x in jax.tree_util.tree_leaves(jax.device_get(tree))]


class StepRecorder:
    """The wrapper around ``trainer.train_step``."""

    def __init__(self, ctx, trainer, opt_name: str, hp):
        self.ctx, self.tr, self.opt_name, self.hp = ctx, trainer, opt_name, hp
        self.inner = trainer.train_step
        job = ctx.mix
        self.checked = int(job["checked_steps"])
        self.first_timed = self.checked + int(job["warm_steps"]) + 1
        self.trace_steps = int(job["trace_steps"]) if ctx.trace else 0
        self.n = 0
        self.steps: List[Dict[str, float]] = []   # {"i", "t0", "t1", "loss"}
        self.batches: List[Dict[str, np.ndarray]] = []
        self.grad_norms: Optional[List[float]] = None
        self.grad_profiles: Optional[List[np.ndarray]] = None
        self.changes: Optional[List[float]] = None
        self.window_t0: Optional[float] = None
        self.setup_s: Optional[float] = None
        self.trace_dir: Optional[str] = None
        self.trace_span: Optional[List[float]] = None
        self.state_shapes = None
        self.batch_shapes = None
        self.memory: Dict[str, Any] = {}

    def __call__(self, state, batch):
        import jax

        self.n += 1
        i = self.n
        if i <= self.checked:
            self.batches.append({k: np.asarray(batch[k]) for k in ("inputs", "targets")})
        if i == 1:
            sds = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding)
            self.state_shapes = jax.tree_util.tree_map(sds, state)
            self.batch_shapes = jax.tree_util.tree_map(sds, batch)
        t0 = time.perf_counter()
        state, metrics = self.inner(state, batch)
        jax.block_until_ready((state, metrics["loss"]))
        t1 = time.perf_counter()
        self.steps.append({"i": i, "t0": t0, "t1": t1, "loss": float(metrics["loss"])})
        if i == 1:
            self.grad_norms = _host_list(jax.jit(
                lambda o, p: program_first_gradient_norms(self.opt_name, o, p, self.hp))(
                    state["opt_state"], state["params"]))
            self.grad_profiles = [np.asarray(a) for a in jax.device_get(jax.jit(
                lambda o: program_first_gradient_profiles(self.opt_name, o, self.hp))(
                    state["opt_state"]))]
        if i == self.checked:
            self.changes = _host_list(change_norms(state["params"], self.ctx.seed,
                                                   self.ctx.config))
        if i == self.first_timed - 1:
            self.window_t0 = time.perf_counter()
            self.setup_s = self.ctx.since_process_start()
        elif self.window_t0 is not None:
            left = self.ctx.seconds - (time.perf_counter() - self.window_t0)
            if left <= 0:
                self.memory = self.ctx.device_memory()
                raise _WindowClosed()
            # A traced run records the window's last steps, so that stopping
            # the profiler (seconds of host work) falls after the window.
            if self.trace_steps and self.trace_span is None \
                    and left <= (self.trace_steps + 0.5) * (t1 - t0):
                self.trace_dir = self.ctx.trace_path()
                self.ctx.start_trace(self.trace_dir)
                self.trace_span = [time.perf_counter(), 0.0]
        return state, metrics

    def timed(self) -> List[Dict[str, float]]:
        end = self.window_t0 + self.ctx.seconds
        return [s for s in self.steps if s["i"] >= self.first_timed and s["t1"] <= end]


def _read_events(run_dir: str) -> List[Dict[str, Any]]:
    path = os.path.join(run_dir, "events.jsonl")
    if not os.path.isfile(path):
        return []
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


# -- the reference's side -----------------------------------------------------------
def reference_shardings(ctx, n_devices: int):
    """On several chips the reference's own layout: every matrix split along
    its largest axis that the device count divides, rows of a batch split the
    same way; vectors replicated. None on one chip."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    if n_devices == 1:
        return None, None
    mesh = Mesh(np.array(jax.devices()[:n_devices]), ("x",))

    def spec(shape_std):
        shape = shape_std[0]
        if len(shape) < 2:
            return NamedSharding(mesh, P())
        ax = max((a for a in range(len(shape)) if shape[a] % n_devices == 0),
                 key=lambda a: shape[a], default=None)
        parts = [None] * len(shape)
        if ax is not None:
            parts[ax] = "x"
        return NamedSharding(mesh, P(*parts))

    tree = jax.tree_util.tree_map(spec, ref.param_shapes(ctx.config), is_leaf=ref._is_spec)
    return tree, NamedSharding(mesh, P("x"))


def reference_steps(ctx, batches, precision: str = "float32") -> Dict[str, Any]:
    """Follow the first steps in the plain reference: its own weights from the
    seed, its own optimizer. Returns losses, per-leaf first-gradient norms and
    per-leaf norms of the weights' change after the last step."""
    import jax
    import jax.numpy as jnp

    cfg, job = ctx.config, ctx.mix
    hp = dict(job["optimizer"])
    opt_init, opt_step, grad_norms_of, grad_profiles_of = ref_opt.get(hp["name"])
    p_shard, b_shard = reference_shardings(ctx, int(ctx.cell["chips"]) if not ctx.rehearse
                                           else len(jax.devices()) if job["mesh"] else 1)
    params = ref.init_params(ctx.seed, cfg, p_shard)
    state = jax.jit(opt_init)(params)

    def step(params, state, inputs, targets):
        loss, grads = ref.loss_and_grads(params, inputs, targets, cfg, precision)
        new_params, new_state = opt_step(params, grads, state, hp)
        return new_params, new_state, loss

    step = jax.jit(step, donate_argnums=(0, 1))
    losses, grad_norms, grad_profiles = [], None, None
    for i, b in enumerate(batches):
        inputs, targets = jnp.asarray(b["inputs"]), jnp.asarray(b["targets"])
        if b_shard is not None:
            inputs, targets = jax.device_put(inputs, b_shard), jax.device_put(targets, b_shard)
        params, state, loss = step(params, state, inputs, targets)
        losses.append(float(loss))
        if i == 0:
            grad_norms = _host_list(jax.jit(
                lambda s, p: grad_norms_of(s, p, hp))(state, params))
            grad_profiles = [np.asarray(a) for a in jax.device_get(jax.jit(
                lambda s, p: grad_profiles_of(s, p, hp))(state, params))]
    changes = _host_list(change_norms(params, ctx.seed, cfg))
    names = _leaf_names(params)
    del params, state
    gc.collect()
    return {"losses": losses, "grad_norms": grad_norms, "grad_profiles": grad_profiles,
            "changes": changes, "names": names}


def worst_leaf_gap(got: List[float], want: List[float], names: List[str]):
    """Largest |got - want| over leaves, against the reference's norm of that
    leaf or of the median leaf, whichever is larger."""
    med = float(np.median(want))
    worst, where = 0.0, ""
    for g, w, n in zip(got, want, names):
        gap = abs(g - w) / max(w, med, 1e-30)
        if not math.isfinite(gap):
            return float("inf"), n
        if gap > worst:
            worst, where = gap, n
    return worst, where


def worst_profile_gap(got: List[np.ndarray], want: List[np.ndarray], names: List[str]):
    """Largest |got - want| / |want| over leaves (|want| no smaller than the
    median leaf's), on the step-1 second-moment statistics."""
    norms = [float(np.linalg.norm(w)) for w in want]
    med = float(np.median(norms))
    worst, where = 0.0, ""
    for g, w, n, wn in zip(got, want, names, norms):
        gap = float(np.linalg.norm(g.astype(np.float64) - w)) / max(wn, med, 1e-300)
        if not math.isfinite(gap) or g.shape != w.shape:
            return float("inf"), n
        if gap > worst:
            worst, where = gap, n
    return worst, where


def compare(got: Dict[str, Any], want: Dict[str, Any], limits: Dict[str, float],
            say) -> Dict[str, Any]:
    """Each number beside its limit; correct when every one is inside."""
    numbers = {}
    for k, (a, b) in enumerate(zip(got["losses"], want["losses"]), start=1):
        numbers[f"loss_gap_step{k}"] = abs(a - b) / max(abs(b), 1e-30)
    numbers["first_grad_norm_gap"], g_leaf = worst_leaf_gap(
        got["grad_norms"], want["grad_norms"], want["names"])
    numbers["first_grad_profile_gap"], p_leaf = worst_profile_gap(
        got["grad_profiles"], want["grad_profiles"], want["names"])
    numbers["param_change_gap"], c_leaf = worst_leaf_gap(
        got["changes"], want["changes"], want["names"])
    ok = True
    for name, value in numbers.items():
        key = "loss_gap" if name.startswith("loss_gap") else name
        limit = float(limits[key])
        inside = math.isfinite(value) and value <= limit
        ok = ok and inside
        say(f"check {name}: {value:.6g} (limit {limit:g}) {'ok' if inside else 'OUTSIDE'}")
    say(f"check worst leaves: first gradient's norm {g_leaf}, its profile {p_leaf}, "
        f"weights' change {c_leaf}")
    say("check losses: program " + " ".join(f"{x:.6f}" for x in got["losses"])
        + " | reference " + " ".join(f"{x:.6f}" for x in want["losses"]))
    return {"ok": ok, "numbers": numbers}


# -- the run ------------------------------------------------------------------------
def run(ctx) -> Dict[str, Any]:
    import jax

    from mlx_cuda_distributed_pretraining_tpu.config import Config
    from mlx_cuda_distributed_pretraining_tpu.train.trainer import Trainer

    job, cfg = ctx.mix, ctx.config
    chips = len(jax.devices()) if ctx.rehearse else int(ctx.cell["chips"])
    tokens_per_step = int(job["batch_size"]) * int(job["seq_len"])
    shard_dir = os.path.join(ctx.workdir, "shards")
    info = synthetic.write_token_shards(job, int(cfg["vocab_size"]), ctx.seed, shard_dir,
                                        int(job["shard_steps"]))
    ctx.say(f"job: {info['documents']} documents (median {info['doc_len_median']:.0f}, "
            f"max {info['doc_len_max']} tokens) packed into {info['tokens']} tokens; "
            f"{job['batch_size']} x {job['seq_len']} = {tokens_per_step} tokens a step")

    tr = Trainer(Config.from_dict(trainer_config(ctx, shard_dir)),
                 runs_root=os.path.join(ctx.workdir, "runs"), quiet=True)
    if tr.model_args.vocab_size != int(cfg["vocab_size"]):
        raise RuntimeError(f"trainer sized the model for vocabulary "
                           f"{tr.model_args.vocab_size}, not {cfg['vocab_size']}")
    shardings = tr.state_shardings["params"] if tr.state_shardings is not None else None
    tr.state["params"] = None
    gc.collect()
    tr.state["params"] = ref.init_params(ctx.seed, cfg, shardings)
    n_params = sum(int(x.size) for x in jax.tree_util.tree_leaves(tr.state["params"]))
    if n_params != flops.total_params(cfg):
        raise RuntimeError(f"{n_params} parameters, the configuration has "
                           f"{flops.total_params(cfg)}")
    hp = dict(job["optimizer"])
    rec = StepRecorder(ctx, tr, hp["name"], hp)
    tr.train_step = ctx.wrap_step(rec)
    try:
        tr.train()
        raise RuntimeError("the job ended before the window did")
    except _WindowClosed:
        pass
    finally:
        ctx.stop_trace()
        if rec.trace_span:
            rec.trace_span[1] = time.perf_counter()
    if tr.events is not None:
        tr.events.close()
    tr.logger.close()
    events = _read_events(tr.run_dir)

    timed = rec.timed()
    if len(timed) < 2:
        raise RuntimeError(f"only {len(timed)} whole steps finished inside the window")
    span = timed[-1]["t1"] - timed[0]["t0"]
    rate = len(timed) * tokens_per_step / span / chips
    step_ms = [1e3 * (s["t1"] - s["t0"]) for s in timed]
    ctx.say(f"window: {len(timed)} whole steps in {span:.3f} s; step ms median "
            f"{np.median(step_ms):.2f} min {min(step_ms):.2f} max {max(step_ms):.2f}; "
            f"{rate:.1f} tokens/s/chip over {chips} chip(s)")
    window_losses = [s["loss"] for s in rec.steps if s["i"] >= rec.first_timed]
    ctx.say("losses: first steps " + " ".join(f"{s['loss']:.4f}" for s in rec.steps[:rec.checked])
            + f"; window first {window_losses[0]:.4f} last {window_losses[-1]:.4f}")

    sources = {
        "kind": "train_job", "chips": chips, "tokens_per_step": tokens_per_step,
        "tokens_per_s_per_chip": rate, "timed_steps": timed,
        "window": (timed[0]["t0"], timed[-1]["t1"]),
        "step_window_events": [e for e in events if e.get("type") == "step_window"
                               and timed[0]["i"] <= int(e.get("step", -1)) <= timed[-1]["i"]],
        "flops_per_token": flops.train_flops_per_token(cfg, int(job["seq_len"])),
        "trace_dir": rec.trace_dir, "trace_span": rec.trace_span,
    }
    if ctx.trace:
        compiled = rec.inner.lower(rec.state_shapes, rec.batch_shapes).compile()
        ma = compiled.memory_analysis()
        sources["step_memory"] = {
            "arguments": int(ma.argument_size_in_bytes), "outputs": int(ma.output_size_in_bytes),
            "aliased": int(ma.alias_size_in_bytes), "temp": int(ma.temp_size_in_bytes)}
        ctx.say(f"step memory_analysis (per device, bytes): {sources['step_memory']}")
        del compiled

    # Free the program's state, then let the reference follow the first steps.
    program = {"losses": [s["loss"] for s in rec.steps[:rec.checked]],
               "grad_norms": rec.grad_norms, "grad_profiles": rec.grad_profiles,
               "changes": rec.changes}
    batches = rec.batches
    tr.state = None
    tr.train_step = None
    del tr
    rec.inner = None
    gc.collect()
    t_ref = time.perf_counter()
    want = reference_steps(ctx, batches)
    ctx.say(f"reference: {len(batches)} steps in float32 at highest precision, "
            f"{time.perf_counter() - t_ref:.1f} s (not part of setup_s)")
    verdict = compare(program, want, ctx.cell["limits"], ctx.say)
    finite = all(math.isfinite(x) for x in window_losses)
    fell = window_losses[-1] < program["losses"][0]
    ctx.say(f"check window losses finite: {finite}; last {window_losses[-1]:.4f} below "
            f"step 1's {program['losses'][0]:.4f}: {fell}")
    return {
        "correct": bool(verdict["ok"] and finite and fell),
        "attempted": len(rec.steps), "failed": 0,
        "end_to_end": {"train_tokens_per_s_per_chip": rate, "setup_s": rec.setup_s},
        "memory": rec.memory, "sources": sources, "check_numbers": verdict["numbers"],
    }
