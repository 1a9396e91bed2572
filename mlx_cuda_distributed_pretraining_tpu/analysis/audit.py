"""graftaudit CLI: static analysis of COMPILED programs.

    python -m mlx_cuda_distributed_pretraining_tpu.analysis.audit \
        --config configs/model-config-sample.yaml

graftlint (lint.py) reads source text; graftaudit AOT-lowers the real
hot-path programs of a config — the train step, the serving decode step,
the streaming decode step, and the LR-finder probe step — under abstract
inputs (``jax.eval_shape`` avals through ``jit(...).trace().lower()``)
and audits the lowered jaxpr/HLO. Nothing executes on a device: the
whole audit runs on CPU in seconds, with donation intent forced visible
via ``GRAFTAUDIT_FORCE_DONATE=1`` (ops/donation.py) and collectives made
real by ``--xla_force_host_platform_device_count``.

Findings flow through the same machinery as graftlint: inline
``# graftlint: disable=RULE`` comments on attributed source lines,
``audit_baseline.json`` with per-entry reasons, ``--prune-stale``
hygiene, and the shared ``--format json`` document.

Collective budgets: ``analysis/budgets/<config>.json`` records the
expected per-program collective census and donation summary. A census
above budget is a finding (comm regression); below budget the run exits
nonzero with a refresh hint (scripts/audit_budget.py) so the committed
numbers never overstate the cost.
"""

from __future__ import annotations

import argparse
import ast
import json
import os
import sys
from typing import Any, Dict, List, Optional, Sequence, Tuple

from .audit_rules import (
    ArgLeaf,
    AuditProgram,
    all_audit_rules,
    audit_program,
    fmt_bytes,
)
from .core import (
    PACKAGE_NAME,
    Finding,
    LintResult,
    classify_findings,
    decorated_header_spans,
    load_baseline,
    result_to_json,
    suppressed_rules_at,
    write_baseline,
    write_baseline_entries,
)

_ANALYSIS_DIR = os.path.dirname(os.path.abspath(__file__))
_PKG_PARENT = os.path.dirname(os.path.dirname(_ANALYSIS_DIR))

PROGRAM_NAMES = ("train_step", "serve_decode", "serve_decode_w8",
                 "serve_decode_w4", "stream_decode", "lr_probe")

# Fixed serving-shape knobs: the audit wants ONE representative lowering
# per program, not a sweep — these match the smallest shapes the serve
# tests exercise.
_SERVE_SLOTS = 8
_SERVE_BLOCK = 16
_SERVE_ATTEND = 256
_DECODE_ATTEND = 256
_DECODE_HISTORY = 64


def default_audit_baseline_path() -> str:
    return os.path.join(_ANALYSIS_DIR, "audit_baseline.json")


def default_budget_path(config_name: str) -> str:
    return os.path.join(_ANALYSIS_DIR, "budgets", config_name + ".json")


def config_stem(config_path: str) -> str:
    return os.path.splitext(os.path.basename(config_path))[0]


def setup_env(device_count: int = 8) -> None:
    """Pin the audit environment BEFORE the first jax backend init: CPU
    platform, N virtual host devices (so GSPMD actually partitions and
    the census sees the collectives), and forced donation metadata."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ.setdefault("GRAFTAUDIT_FORCE_DONATE", "1")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={device_count}"
        ).strip()


# -- program construction ----------------------------------------------------


def _keypath_str(kp) -> str:
    parts = []
    for p in kp:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return ".".join(parts)


def _arg_leaves(lowered, arg_names: Sequence[str]) -> List[ArgLeaf]:
    """Flatten ``lowered.args_info`` (a pytree of ArgInfo carrying shape,
    dtype and the donation bit) into audit leaves. The keypath leads with
    (outer-tuple, positional-index); the rest is the in-argument path."""
    import jax.tree_util as jtu
    import numpy as np

    flat, _ = jtu.tree_flatten_with_path(lowered.args_info)
    leaves: List[ArgLeaf] = []
    for kp, info in flat:
        idx = getattr(kp[1], "idx", 0) if len(kp) > 1 else 0
        shape = tuple(int(d) for d in info.shape)
        n = 1
        for d in shape:
            n *= d
        dtype = str(info.dtype)
        try:
            itemsize = np.dtype(dtype).itemsize
        except TypeError:
            itemsize = 4
        leaves.append(ArgLeaf(
            index=idx,
            name=arg_names[idx] if idx < len(arg_names) else f"arg{idx}",
            path=_keypath_str(kp[2:]),
            shape=shape,
            dtype=dtype,
            nbytes=n * itemsize,
            donated=bool(info.donated),
        ))
    return leaves


def _trace_program(name: str, config_name: str, jitted, args,
                   kwargs: Optional[Dict[str, Any]] = None, *,
                   arg_names: Sequence[str],
                   compute_dtype: str = "float32",
                   param_arg_index: Optional[int] = None,
                   expected_param_specs: Optional[Dict[str, str]] = None
                   ) -> AuditProgram:
    traced = jitted.trace(*args, **(kwargs or {}))
    lowered = traced.lower()
    return AuditProgram(
        name=name,
        config_name=config_name,
        lowered=lowered,
        closed_jaxpr=traced.jaxpr,
        arg_leaves=_arg_leaves(lowered, arg_names),
        out_avals=list(traced.jaxpr.out_avals),
        compute_dtype=compute_dtype,
        param_arg_index=param_arg_index,
        expected_param_specs=expected_param_specs or {},
    )


def build_programs(config_path: str,
                   wanted: Optional[Sequence[str]] = None,
                   notes: Optional[List[str]] = None) -> List[AuditProgram]:
    """Lower every auditable program of one config under abstract inputs.

    Mirrors the Trainer's construction wiring (mesh rule, tokenizer-derived
    vocab, loss closure, optimizer) without allocating a single parameter:
    params come from ``jax.eval_shape`` over the real initializer.
    """
    import jax
    import jax.numpy as jnp
    import jax.tree_util as jtu

    from ..config import Config
    from ..models import llama
    from ..models.llama import LlamaArgs
    from ..models.registry import resolve_architecture
    from ..optim import build_optimizer, build_schedule
    from ..parallel import build_mesh
    from ..parallel.context import set_mesh
    from ..parallel.sharding_rules import param_pspec
    from ..tokenizer import TokenizerManager
    from ..train.lr_finder import _sweep_step
    from ..train.train_step import init_train_state, make_train_step
    from ..utils.tree import flatten_dict

    wanted = tuple(wanted or PROGRAM_NAMES)
    notes = notes if notes is not None else []
    cfg = Config.from_yaml(config_path)
    config_name = config_stem(config_path)

    # Same mesh rule as the Trainer: explicit config mesh wins, else
    # implicit pure-DP over all (virtual) devices when the batch divides.
    mesh = None
    explicit = bool(getattr(cfg.system, "mesh", None)) or cfg.system.model_parallel
    if explicit:
        mesh = build_mesh(cfg.system)
    elif jax.device_count() > 1 \
            and cfg.training.batch_size % jax.device_count() == 0:
        mesh = build_mesh(cfg.system)
    set_mesh(mesh)

    tokenizer = TokenizerManager(cfg.data)
    arch = resolve_architecture(cfg.model.architecture)
    args = LlamaArgs.from_config(cfg.model, tokenizer.vocab_size)
    if arch.force_attention:
        args = args.__class__(**{**args.__dict__,
                                 "attention_type": arch.force_attention})

    compute_dtype = ("bfloat16" if cfg.system.compute_dtype == "bfloat16"
                     else "float32")
    jnp_compute = jnp.bfloat16 if compute_dtype == "bfloat16" else jnp.float32
    # Same remat precedence as the Trainer: model.remat_policy wins over
    # system.remat; legacy gradient_checkpointing means "full"; the
    # explicit "none" opts out of all of them.
    remat = getattr(cfg.model, "remat_policy", None)
    if remat is None:
        remat = cfg.system.remat
    if remat is None and cfg.system.gradient_checkpointing:
        remat = "full"
    if remat == "none":
        remat = None
    ce_chunk = int(getattr(cfg.system, "fused_ce_chunk", -1))
    scan_layers = bool(getattr(cfg.system, "scan_layers", False))
    overlap = bool(getattr(cfg.system, "overlap_gather", False))
    z_loss = float(cfg.training.hyperparameters.get("z_loss") or 0.0)
    # the keywords the trainer passes (train/trainer.py): every loss_fn takes them
    moe_experts = args.num_local_experts if args.is_moe else 0
    _stats_kw = {"with_moe_stats": True} if moe_experts else {}
    if overlap:
        _stats_kw = {**_stats_kw, "overlap": True}

    def loss_fn(params, batch):
        return arch.loss_fn(
            params, batch, args, compute_dtype=jnp_compute, remat=remat,
            remat_ratio=float(cfg.system.gradient_checkpointing_ratio),
            ce_chunk=ce_chunk, scan_layers=scan_layers,
            z_loss_weight=z_loss, **_stats_kw)

    params_abs = jax.eval_shape(lambda k: arch.init_params(k, args),
                                jax.random.PRNGKey(0))
    B = cfg.training.batch_size
    L = cfg.data.max_context_size
    batch_abs = {
        "inputs": jax.ShapeDtypeStruct((B, L), jnp.int32),
        "targets": jax.ShapeDtypeStruct((B, L), jnp.int32),
        "mask": jax.ShapeDtypeStruct((B, L), jnp.float32),
    }

    expected_specs: Dict[str, str] = {}
    if mesh is not None:
        for k, leaf in flatten_dict(params_abs).items():
            spec = param_pspec(k, leaf.shape, mesh)
            if any(ax is not None for ax in spec):
                expected_specs["params." + k] = str(spec)

    programs: List[AuditProgram] = []

    if "train_step" in wanted:
        optimizer = build_optimizer(cfg.training, 1000,
                                    schedule=build_schedule(cfg.training, 1000))
        step_fn, _ = make_train_step(
            loss_fn, optimizer,
            accum_steps=cfg.training.gradient_accumulation_steps,
            mesh=mesh,
            zero_level=cfg.system.zero_optimization_level,
            log_grad_norm=cfg.logging.log_gradient_norm,
            params_like=params_abs,
            moe_stats_experts=moe_experts)
        state_abs = jax.eval_shape(
            lambda p: init_train_state(p, optimizer), params_abs)
        prog = _trace_program(
            "train_step", config_name, step_fn, (state_abs, batch_abs),
            arg_names=("state", "batch"), compute_dtype=compute_dtype,
            param_arg_index=0, expected_param_specs=expected_specs)
        # sync-collectives rule inputs: what the config asked for, and
        # the backend this lowering targets (the audit host's — a CPU
        # host resolves every set to (), keeping CPU audits green).
        from ..parallel import xla_flags as _xf
        prog.requested_flag_set = str(
            getattr(cfg.system, "xla_flag_set", "") or "") or None
        prog.flag_backend = _xf.guess_backend()
        programs.append(prog)

    # serve_decode audits the fp serving step; the _w8/_w4 variants lower
    # the SAME step over quantize_weights-shaped abstract params (int8 /
    # packed-int4 weight_q(4) + weight_s leaves) — what the engine actually
    # runs under serving.weight_dtype — so dequant-materialization and the
    # collective budget see the quantized program, not a proxy.
    serve_variants = [v for v in ("serve_decode", "serve_decode_w8",
                                  "serve_decode_w4") if v in wanted]
    if serve_variants:
        if args.is_moe:
            for v in serve_variants:
                notes.append(f"{v}: skipped (paged serving is audited "
                             "dense-only; MoE serve needs the "
                             "grouped-dispatch mesh context)")
        else:
            from ..models.quantize import quantize_weights
            from ..serve.batch_step import paged_decode_step

            table_w = _SERVE_ATTEND // _SERVE_BLOCK
            n_blocks = _SERVE_SLOTS * table_w + 1
            Hkv, Dh = args.num_kv_heads, args.head_dim
            cache_abs = [
                {"k": jax.ShapeDtypeStruct(
                    (n_blocks, _SERVE_BLOCK, Hkv, Dh), jnp.float32),
                 "v": jax.ShapeDtypeStruct(
                    (n_blocks, _SERVE_BLOCK, Hkv, Dh), jnp.float32)}
                for _ in range(args.num_layers)]
            step = paged_decode_step(args, draft_len=0,
                                     attend_len=_SERVE_ATTEND,
                                     table_width=table_w,
                                     block_size=_SERVE_BLOCK)
            for variant in serve_variants:
                wd = {"serve_decode": "fp", "serve_decode_w8": "int8",
                      "serve_decode_w4": "int4"}[variant]
                p_abs = (params_abs if wd == "fp" else jax.eval_shape(
                    lambda p, _wd=wd: quantize_weights(p, _wd), params_abs))
                programs.append(_trace_program(
                    variant, config_name, step,
                    (p_abs, cache_abs,
                     jax.ShapeDtypeStruct((_SERVE_SLOTS, 1), jnp.int32),
                     jax.ShapeDtypeStruct((_SERVE_SLOTS,), jnp.int32),
                     jax.ShapeDtypeStruct((_SERVE_SLOTS, table_w), jnp.int32),
                     jax.ShapeDtypeStruct((_SERVE_SLOTS,), jnp.float32),
                     jax.ShapeDtypeStruct((_SERVE_SLOTS, 2), jnp.uint32)),
                    arg_names=("params", "cache", "tokens", "pos", "tables",
                               "temps", "keys")))

    if "stream_decode" in wanted:
        if args.is_moe:
            notes.append("stream_decode: skipped (MoE decode needs the "
                         "grouped-dispatch mesh context)")
        else:
            from ..infer.generate import _decode_step
            from ..infer.samplers import greedy

            dstep = _decode_step(args, False, _DECODE_ATTEND)
            cache_abs = jax.eval_shape(
                lambda: llama.init_cache(args, 1, max_len=_DECODE_ATTEND))
            programs.append(_trace_program(
                "stream_decode", config_name, dstep,
                (params_abs, cache_abs,
                 jax.ShapeDtypeStruct((1,), jnp.int32),
                 jax.ShapeDtypeStruct((), jnp.int32),
                 jax.ShapeDtypeStruct((2,), jnp.uint32),
                 jax.ShapeDtypeStruct((1, _DECODE_HISTORY), jnp.int32)),
                kwargs={"sampler": greedy(), "processors": ()},
                arg_names=("params", "cache", "token", "pos", "rng",
                           "history")))

    if "lr_probe" in wanted:
        sweep = _sweep_step(loss_fn)
        trace_abs = jtu.tree_map(
            lambda l: jax.ShapeDtypeStruct(l.shape, jnp.float32), params_abs)
        programs.append(_trace_program(
            "lr_probe", config_name, sweep,
            (params_abs, trace_abs, batch_abs,
             jax.ShapeDtypeStruct((), jnp.float32)),
            arg_names=("params", "trace", "batch", "lr"),
            compute_dtype=compute_dtype))

    return programs


# -- budgets -----------------------------------------------------------------


def load_budget(path: str) -> Optional[Dict[str, Any]]:
    if not os.path.isfile(path):
        return None
    with open(path) as f:
        return json.load(f)


def build_budget_doc(config_name: str, device_count: int,
                     programs: Sequence[AuditProgram]) -> Dict[str, Any]:
    return {
        "version": 1,
        "tool": "graftaudit",
        "config": config_name,
        "device_count": device_count,
        "programs": {
            p.name: {"collectives": p.census(),
                     "donation": p.donation_summary()}
            for p in programs
        },
    }


def write_budget(path: str, doc: Dict[str, Any]) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
        f.write("\n")


def attach_budgets(programs: Sequence[AuditProgram],
                   budget_doc: Optional[Dict[str, Any]]) -> None:
    for p in programs:
        if budget_doc is None:
            p.budget = None
        else:
            entry = (budget_doc.get("programs") or {}).get(p.name)
            p.budget = (entry or {}).get("collectives", {}) \
                if entry is not None else None


def budget_shrinks(programs: Sequence[AuditProgram],
                   budget_doc: Optional[Dict[str, Any]]) -> List[str]:
    """Budget entries the current lowering no longer reaches: the comm
    cost SHRANK (a win) and the committed numbers overstate it. Reported
    as a stale-budget gate, symmetric to stale baseline entries."""
    out: List[str] = []
    if budget_doc is None:
        return out
    for p in programs:
        entry = (budget_doc.get("programs") or {}).get(p.name)
        if entry is None:
            continue
        census = p.census()
        for op, want in sorted((entry.get("collectives") or {}).items()):
            got = census.get(op, {"count": 0, "bytes": 0})
            if got["count"] < want["count"] or got["bytes"] < want["bytes"]:
                out.append(
                    f"{p.name}: {op} shrank to {got['count']} op(s) / "
                    f"{fmt_bytes(got['bytes'])} (budget {want['count']} "
                    f"op(s) / {fmt_bytes(want['bytes'])})")
    return out


# -- runner ------------------------------------------------------------------


def _apply_suppressions(findings: Sequence[Finding]
                        ) -> Tuple[List[Finding], List[Finding]]:
    """Split findings into (active, inline-suppressed) by reading the
    attributed source files — same ``# graftlint: disable=`` syntax and
    decorated-header span semantics as the AST linter."""
    cache: Dict[str, Tuple[List[str], Dict[int, Tuple[int, int]]]] = {}
    active: List[Finding] = []
    suppressed: List[Finding] = []
    for f in findings:
        if f.path.startswith("<"):
            active.append(f)
            continue
        info = cache.get(f.path)
        if info is None:
            ap = f.path if os.path.isabs(f.path) \
                else os.path.join(_PKG_PARENT, f.path)
            try:
                with open(ap, encoding="utf-8") as fh:
                    src = fh.read()
                info = (src.splitlines(),
                        decorated_header_spans(ast.parse(src)))
            except (OSError, SyntaxError):
                info = ([], {})
            cache[f.path] = info
        tags = suppressed_rules_at(info[0], info[1], f.line)
        if tags is not None and ("all" in tags or f.rule in tags):
            suppressed.append(f)
        else:
            active.append(f)
    return active, suppressed


def run_audit(programs: Sequence[AuditProgram],
              baseline: Optional[Sequence[Dict[str, Any]]] = None
              ) -> LintResult:
    findings: List[Finding] = []
    seen = set()
    for prog in programs:
        for f in audit_program(prog):
            # The same source line can surface through several programs
            # (train_step and lr_probe trace the same loss); report once.
            k = (f.rule, f.path, f.line, f.message)
            if k in seen:
                continue
            seen.add(k)
            findings.append(f)
    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    active, suppressed = _apply_suppressions(findings)
    baselined, new, stale = classify_findings(active, baseline)
    return LintResult(findings=active, suppressed=suppressed,
                      baselined=baselined, new=new, stale_baseline=stale)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog=f"python -m {PACKAGE_NAME}.analysis.audit",
        description="compiled-program audits: donation, collectives, "
                    "dtype, constants, sharding — over lowered jaxprs")
    ap.add_argument("--config", default="configs/model-config-sample.yaml",
                    help="training YAML whose programs to lower and audit")
    ap.add_argument("--programs", default=None,
                    help="comma list from: " + ",".join(PROGRAM_NAMES))
    ap.add_argument("--devices", type=int, default=8,
                    help="virtual CPU devices (mesh size for the lowering)")
    ap.add_argument("--format", choices=("text", "json"), default="text")
    ap.add_argument("--baseline", default=None,
                    help=f"default: {default_audit_baseline_path()}")
    ap.add_argument("--no-baseline", action="store_true")
    ap.add_argument("--write-baseline", action="store_true",
                    help="regenerate audit_baseline.json from current "
                         "findings (keeps matching reasons) and exit 0")
    ap.add_argument("--prune-stale", action="store_true",
                    help="drop baseline entries no finding matches")
    ap.add_argument("--budget", default=None,
                    help="collective budget file (default: "
                         "analysis/budgets/<config>.json)")
    ap.add_argument("--no-budget", action="store_true",
                    help="skip the collective budget comparison")
    ap.add_argument("--write-budget", action="store_true",
                    help="write the observed census/donation summary as "
                         "the new budget (scripts/audit_budget.py wraps "
                         "this with a shrink-refusing delta report)")
    ap.add_argument("--list-rules", action="store_true")
    args = ap.parse_args(argv)

    rules = all_audit_rules()
    if args.list_rules:
        for rid in sorted(rules):
            print(f"{rid}: {' '.join(rules[rid].description.split())}")
        return 0

    if not os.path.isfile(args.config):
        print(f"graftaudit: no such config: {args.config}", file=sys.stderr)
        return 2
    wanted = [p.strip() for p in args.programs.split(",")] \
        if args.programs else list(PROGRAM_NAMES)
    bad = [p for p in wanted if p not in PROGRAM_NAMES]
    if bad:
        print(f"graftaudit: unknown program(s): {', '.join(bad)}",
              file=sys.stderr)
        return 2

    setup_env(args.devices)
    notes: List[str] = []
    programs = build_programs(args.config, wanted, notes=notes)
    config_name = config_stem(args.config)

    budget_path = args.budget or default_budget_path(config_name)
    if args.write_budget:
        doc = build_budget_doc(config_name, args.devices, programs)
        write_budget(budget_path, doc)
        print(f"graftaudit: wrote budget for {len(programs)} program(s) "
              f"to {budget_path}", file=sys.stderr)
        budget_doc = doc
    else:
        budget_doc = None if args.no_budget else load_budget(budget_path)
    attach_budgets(programs, budget_doc)
    shrinks = [] if args.no_budget else budget_shrinks(programs, budget_doc)

    baseline_path = args.baseline or default_audit_baseline_path()
    baseline = [] if args.no_baseline else load_baseline(baseline_path)
    result = run_audit(programs, baseline=baseline)

    if args.write_baseline:
        write_baseline(baseline_path, result.findings, old_entries=baseline,
                       tool="graftaudit")
        print(f"graftaudit: wrote {len(result.findings)} finding(s) to "
              f"{baseline_path}", file=sys.stderr)
        return 0

    stale_gate = False
    if result.stale_baseline and not args.no_baseline:
        if args.prune_stale:
            drop = {}
            for e in result.stale_baseline:
                k = (e.get("rule"), e.get("path"), e.get("message"))
                drop[k] = drop.get(k, 0) + 1
            kept = []
            for e in baseline:
                k = (e.get("rule"), e.get("path"), e.get("message"))
                if drop.get(k, 0) > 0:
                    drop[k] -= 1
                else:
                    kept.append(e)
            write_baseline_entries(baseline_path, kept, tool="graftaudit")
            n = len(baseline) - len(kept)
            print(f"graftaudit: pruned {n} stale baseline entr"
                  f"{'y' if n == 1 else 'ies'} from {baseline_path}",
                  file=sys.stderr)
            result.stale_baseline = []
        else:
            stale_gate = True

    budget_gate = bool(shrinks)
    if args.format == "json":
        doc = result_to_json("graftaudit", result)
        doc["stale_budget"] = shrinks
        doc["notes"] = notes
        print(json.dumps(doc))
    else:
        for f in result.new:
            print(f"{f.path}:{f.line}:{f.col}: [{f.rule}] {f.message}")
        for note in notes:
            print(f"note: {note}", file=sys.stderr)
        for e in result.stale_baseline:
            print(f"{'error' if stale_gate else 'note'}: stale baseline "
                  f"entry (fixed?): [{e.get('rule')}] {e.get('path')} — "
                  f"{e.get('message')}", file=sys.stderr)
        if stale_gate:
            print("graftaudit: baseline has stale entries — run "
                  f"`python -m {PACKAGE_NAME}.analysis.audit --config "
                  f"{args.config} --prune-stale` to drop them",
                  file=sys.stderr)
        for s in shrinks:
            print(f"error: stale budget (comm shrank — a win): {s}",
                  file=sys.stderr)
        if budget_gate:
            print("graftaudit: the committed budget overstates the comm "
                  "cost — refresh with scripts/audit_budget.py",
                  file=sys.stderr)
        print(f"graftaudit: {len(programs)} program(s), "
              f"{len(result.new)} new, {len(result.baselined)} baselined, "
              f"{len(result.suppressed)} suppressed", file=sys.stderr)
    return 1 if (result.new or stale_gate or budget_gate) else 0


if __name__ == "__main__":
    sys.exit(main())
