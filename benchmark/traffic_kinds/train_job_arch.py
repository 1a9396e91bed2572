"""Traffic kind ``train_job_arch``: ``train_job`` for any architecture.

``train_job.py`` names the program's ``"llama"`` and the reference and FLOP
count of ``llama_dense``. This kind reuses its loop, recorder and comparison
by import and picks the three by the configuration's ``architecture``:
``MODEL_SECTIONS[architecture]`` builds the ``model`` section of the trainer's
config, ``benchmark/reference/<architecture>.py`` is the plain reference and
``benchmark/flops/<architecture>.py`` the count. For the helpers of
``train_job`` that reach for its module-level ``ref`` (``change_norms``,
``reference_shardings``) the run points that name at the chosen reference: one
process runs one cell.

A reference of this kind offers ``init_params``, ``make_params``,
``param_shapes``, ``_is_spec`` as ``llama_dense`` does, and ``loss_and_grads
-> (terms, grads)`` with ``terms`` the loss first and then any terms it is the
sum of (a second head's); ``grads_by_sequence`` with the same result where the
whole batch does not fit beside float32 gradients. Every term is compared:
``loss_gap_step<k>`` runs over (step 1's terms, step 2's, ...), the program's
from its ``step_window`` events (``loss``, then ``main_loss``, ``mtp_loss``
where it reports them).
"""

from __future__ import annotations

import gc
import importlib
import math
import os
import time
import types
from typing import Any, Dict, List

import numpy as np

from benchmark import synthetic
from benchmark.reference import optimizers as ref_opt
from benchmark.traffic_kinds import train_job as base

LOSS_TERMS = ("main_loss", "mtp_loss")  # after "loss", where an event has them


def _xing_model(c, job):
    return {
        "architecture": "xing_mla_moe",
        "dimensions": {"hidden_size": c["hidden_size"], "intermediate_size": c["intermediate_size"],
                       "num_layers": c["num_hidden_layers"]},
        "attention": {"num_heads": c["num_attention_heads"],
                      "max_position_embeddings": c["max_position_embeddings"],
                      "use_flash_attention": job["attention_type"] == "flash"},
        "mla": {k: c[k] for k in ("q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
                                  "qk_rope_head_dim", "v_head_dim")},
        "normalization": {"rms_norm_eps": c["rms_norm_eps"]},
        "rope": {"theta": c["rope_theta"], "scaling": dict(c["rope_scaling"])},
        "moe": {**{k: c[k] for k in ("n_routed_experts", "num_experts_per_tok",
                                     "moe_intermediate_size", "n_shared_experts",
                                     "first_k_dense_replace", "routed_scaling_factor")},
                "experts_held": [c["experts_held"]["first"], c["experts_held"]["count"]]},
        "hyper_connections": {k: c[k] for k in ("hc_mult", "hc_sinkhorn_iters", "hc_eps",
                                                "mhc_h_res_clamp_min", "mhc_h_res_clamp_max")},
        "mtp": {"num_nextn_predict_layers": c["num_nextn_predict_layers"],
                "loss_weight": c["mtp_loss_weight"]},
        "misc": {"tie_word_embeddings": bool(c["tie_word_embeddings"])},
    }


# architecture -> the trainer's ``model`` section; None keeps train_job's own (llama)
MODEL_SECTIONS = {"llama_dense": None, "xing_mla_moe": _xing_model}
# what train_job.trainer_config reads of a llama configuration, for one that lacks them
_LLAMA_KEYS = ("intermediate_size", "num_key_value_heads", "head_dim", "rope_theta",
               "max_position_embeddings", "rms_norm_eps", "tie_word_embeddings")


def modules_for(config: Dict[str, Any]):
    """(reference, flops) of the configuration's architecture."""
    arch = config["architecture"]
    if arch not in MODEL_SECTIONS:
        raise SystemExit(f"unknown architecture {arch!r}: train_job_arch has {sorted(MODEL_SECTIONS)}")
    return (importlib.import_module("benchmark.reference." + arch),
            importlib.import_module("benchmark.flops." + arch))


def trainer_config(ctx, shard_dir: str) -> Dict[str, Any]:
    """train_job's mapping of a job onto the trainer's config, with the
    architecture's own ``model`` section."""
    section = MODEL_SECTIONS[ctx.config["architecture"]]
    view = types.SimpleNamespace(config={**dict.fromkeys(_LLAMA_KEYS, 0), **ctx.config},
                                 mix=ctx.mix, seed=ctx.seed, cell=ctx.cell)
    out = base.trainer_config(view, shard_dir)
    if section is not None:
        out["model"] = section(ctx.config, ctx.mix)
    return out


def _terms(x) -> List[float]:
    return [float(v) for v in (x if isinstance(x, (tuple, list)) else (x,))]


def reference_steps(ctx, batches, precision: str = "float32") -> Dict[str, Any]:
    """``train_job.reference_steps`` with the architecture's reference, the
    gradient taken a sequence at a time where the reference offers that, and
    every term of the loss kept (flattened over the steps)."""
    import jax
    import jax.numpy as jnp

    ref, _ = modules_for(ctx.config)
    base.ref = ref
    cfg, job = ctx.config, ctx.mix
    hp = dict(job["optimizer"])
    opt_init, opt_step, grad_norms_of, grad_profiles_of = ref_opt.get(hp["name"])
    params = ref.init_params(ctx.seed, cfg)
    state = jax.jit(opt_init)(params)
    by_sequence = getattr(ref, "grads_by_sequence", None)
    grads_of = by_sequence or jax.jit(
        lambda p, i, t: ref.loss_and_grads(p, i, t, cfg, precision))
    apply = jax.jit(lambda p, g, s: opt_step(p, g, s, hp), donate_argnums=(0, 1, 2))
    losses, grad_norms, grad_profiles = [], None, None
    for i, b in enumerate(batches):
        inputs, targets = jnp.asarray(b["inputs"]), jnp.asarray(b["targets"])
        terms, grads = (grads_of(params, inputs, targets, cfg, precision) if by_sequence
                        else grads_of(params, inputs, targets))
        params, state = apply(params, grads, state)
        del grads
        losses += _terms(jax.device_get(terms))
        if i == 0:
            grad_norms = base._host_list(jax.jit(
                lambda s, p: grad_norms_of(s, p, hp))(state, params))
            grad_profiles = [np.asarray(a) for a in jax.device_get(jax.jit(
                lambda s, p: grad_profiles_of(s, p, hp))(state, params))]
    changes = base._host_list(base.change_norms(params, ctx.seed, cfg))
    names = base._leaf_names(params)
    del params, state
    gc.collect()
    return {"losses": losses, "grad_norms": grad_norms, "grad_profiles": grad_profiles,
            "changes": changes, "names": names}


def worst_leaves(got, want, names, profile: bool, top: int = 6) -> str:
    """The ``top`` leaves by ``train_job``'s own gap, every leaf counted."""
    size = [float(np.linalg.norm(w)) for w in want] if profile else list(want)
    floor = float(np.median(size))  # as train_job's gaps: against the leaf or the median leaf
    ranked = []
    for i, n in enumerate(names):
        gap = float(np.linalg.norm(np.asarray(got[i], np.float64) - want[i])) if profile \
            else abs(got[i] - want[i])
        ranked.append((gap / max(size[i], floor, 1e-300), n))
    return ", ".join(f"{n} {g:.4f}" for g, n in sorted(ranked, reverse=True)[:top])


def say_worst_leaves(got: Dict[str, Any], want: Dict[str, Any], say) -> None:
    """Which leaves the two first-gradient numbers come from (``train_job.compare``
    says the numbers alone)."""
    say("check, worst leaves: first gradient's norm " + worst_leaves(
        got["grad_norms"], want["grad_norms"], want["names"], False) + "; its profile "
        + worst_leaves(got["grad_profiles"], want["grad_profiles"], want["names"], True))


def program_losses(steps, events, checked: int) -> List[float]:
    """The checked steps' loss (as the step returned it) and its terms (as the
    program logged them), in the reference's order; raises unless every
    checked step logged alone."""
    by_step = {int(e["step"]): e for e in events
               if e.get("type") == "step_window" and int(e.get("steps", 0)) == 1}
    out: List[float] = []
    for s in steps[:checked]:
        out += [s["loss"]] + [float(by_step[s["i"]][t]) for t in LOSS_TERMS if t in by_step[s["i"]]]
    return out


def run(ctx) -> Dict[str, Any]:
    import jax

    from mlx_cuda_distributed_pretraining_tpu.config import Config
    from mlx_cuda_distributed_pretraining_tpu.train.trainer import Trainer

    ref, flops = modules_for(ctx.config)
    base.ref = ref  # train_job's change_norms and reference_shardings read it
    job, cfg = ctx.mix, ctx.config
    if job["mesh"]:
        raise SystemExit("train_job_arch runs one device: its reference is not sharded")
    tokens_per_step = int(job["batch_size"]) * int(job["seq_len"])
    shard_dir = os.path.join(ctx.workdir, "shards")
    info = synthetic.write_token_shards(job, int(cfg["vocab_size"]), ctx.seed, shard_dir,
                                        int(job["shard_steps"]))
    ctx.say(f"job: {info['documents']} documents (median {info['doc_len_median']:.0f}, "
            f"max {info['doc_len_max']} tokens) packed into {info['tokens']} tokens; "
            f"{job['batch_size']} x {job['seq_len']} = {tokens_per_step} tokens a step")

    t_build = time.perf_counter()
    tr = Trainer(Config.from_dict(trainer_config(ctx, shard_dir)),
                 runs_root=os.path.join(ctx.workdir, "runs"), quiet=True)
    if tr.model_args.vocab_size != int(cfg["vocab_size"]):
        raise RuntimeError(f"trainer sized the model for vocabulary "
                           f"{tr.model_args.vocab_size}, not {cfg['vocab_size']}")
    tr.state["params"] = None
    gc.collect()
    tr.state["params"] = ref.init_params(ctx.seed, cfg)
    n_params = sum(int(x.size) for x in jax.tree_util.tree_leaves(tr.state["params"]))
    if n_params != flops.total_params(cfg):
        raise RuntimeError(f"{n_params} parameters, the configuration has "
                           f"{flops.total_params(cfg)}")
    jax.block_until_ready(tr.state["params"])
    ctx.say(f"set-up: trainer built and seeded weights placed {time.perf_counter() - t_build:.1f} s; "
            f"{ctx.since_process_start():.1f} s since the process started")
    hp = dict(job["optimizer"])
    rec = base.StepRecorder(ctx, tr, hp["name"], hp)
    tr.train_step = ctx.wrap_step(rec)
    try:
        tr.train()
        raise RuntimeError("the job ended before the window did")
    except base._WindowClosed:
        pass
    finally:
        ctx.stop_trace()
        if rec.trace_span:
            rec.trace_span[1] = time.perf_counter()
    if tr.events is not None:
        tr.events.close()
    tr.logger.close()
    events = base._read_events(tr.run_dir)

    first = next((e for e in events if e.get("type") == "step_window"), {})
    ctx.say(f"set-up: first step's window booked compile_s {first.get('goodput', {}).get('compile_s')}, "
            f"{first.get('xla_compiles')} XLA compile(s) or cache load(s) in {first.get('xla_compile_s')} s; "
            f"step 1 took {rec.steps[0]['t1'] - rec.steps[0]['t0']:.1f} s; setup_s {rec.setup_s:.1f}")
    timed = rec.timed()
    if len(timed) < 2:
        raise RuntimeError(f"only {len(timed)} whole steps finished inside the window")
    span = timed[-1]["t1"] - timed[0]["t0"]
    rate = len(timed) * tokens_per_step / span
    step_ms = [1e3 * (s["t1"] - s["t0"]) for s in timed]
    ctx.say(f"window: {len(timed)} whole steps in {span:.3f} s; step ms median "
            f"{np.median(step_ms):.2f} min {min(step_ms):.2f} max {max(step_ms):.2f}; "
            f"{rate:.1f} tokens/s/chip over 1 chip(s)")
    window_losses = [s["loss"] for s in rec.steps if s["i"] >= rec.first_timed]
    ctx.say("losses: first steps " + " ".join(f"{s['loss']:.4f}" for s in rec.steps[:rec.checked])
            + f"; window first {window_losses[0]:.4f} last {window_losses[-1]:.4f}")

    window_events = [e for e in events if e.get("type") == "step_window"
                     and timed[0]["i"] <= int(e.get("step", -1)) <= timed[-1]["i"]]
    # The routed experts' required FLOPs from the rows they were sent in the window.
    held = None
    if hasattr(flops, "routed_layers"):
        rows = [e["moe_rows_held"] / max(int(e.get("steps", 1)), 1)
                for e in window_events if "moe_rows_held" in e]
        if rows:
            held = float(np.mean(rows)) / tokens_per_step / flops.routed_layers(cfg)
            ctx.say(f"routed experts: {np.mean(rows):.0f} selections a step landed on the held "
                    f"experts (least {min(rows):.0f}, most {max(rows):.0f}), {held:.4f} a token a "
                    f"routed layer (a uniform router: "
                    f"{flops.uniform_held_experts_per_token(cfg):.4f}); load max/mean "
                    f"{max(e.get('moe_load_max_over_mean', 0) for e in window_events):.2f} at "
                    f"most, dropped {sum(int(e.get('moe_drop', 0)) for e in window_events)}")
    flops_per_token = (flops.train_flops_per_token(cfg, int(job["seq_len"]), held) if held is not None
                       else flops.train_flops_per_token(cfg, int(job["seq_len"])))
    sources = {
        "kind": "train_job_arch", "chips": 1, "tokens_per_step": tokens_per_step,
        "tokens_per_s_per_chip": rate, "timed_steps": timed,
        "window": (timed[0]["t0"], timed[-1]["t1"]),
        "step_window_events": window_events, "flops_per_token": flops_per_token,
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
    program = {"losses": program_losses(rec.steps, events, rec.checked), "grad_norms": rec.grad_norms,
               "grad_profiles": rec.grad_profiles, "changes": rec.changes}
    first_loss = rec.steps[0]["loss"]
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
    say_worst_leaves(program, want, ctx.say)
    verdict = base.compare(program, want, ctx.cell["limits"], ctx.say)
    finite = all(math.isfinite(x) for x in window_losses)
    fell = window_losses[-1] < first_loss
    ctx.say(f"check window losses finite: {finite}; last {window_losses[-1]:.4f} below "
            f"step 1's {first_loss:.4f}: {fell}")
    return {
        "correct": bool(verdict["ok"] and finite and fell),
        "attempted": len(rec.steps), "failed": 0,
        "end_to_end": {"train_tokens_per_s_per_chip": rate, "setup_s": rec.setup_s},
        "memory": rec.memory, "sources": sources, "check_numbers": verdict["numbers"],
    }
