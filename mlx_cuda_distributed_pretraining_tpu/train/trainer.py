"""Trainer — the training runtime spine.

Capability parity with the reference Trainer (reference:
core/training.py:898-2082): config → run dir → tokenizer → model → data →
optimizer → train loop with validation / early stopping / LR finder /
sample generation / checkpoint-resume, plus the ``log.txt`` metric protocol.

TPU-native structure: the hot path is ONE jitted, buffer-donated,
mesh-sharded XLA program (train_step.py); the Python loop only feeds numpy
batches and reads back scalar metrics every ``logging_interval`` steps.
Multi-host SPMD replaces the reference's device-thread + remote-worker
coordinator (hybrid_distributed.py): every host runs this same class;
per-host data sharding comes from ``jax.process_index()``.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import math
import os
import time
from typing import Any, Dict, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp
import jax.profiler
import numpy as np

from ..checkpoint import CheckpointIntegrityError, CheckpointManager
from ..checkpoint.manager import _atomic_json
from ..config import Config, apply_overrides
from ..data import DataManager
from ..data.device_prefetch import DevicePrefetcher
from ..data.streaming import build_data_manager
from ..models import llama as llama_mod
from ..models.registry import resolve_architecture
from ..obs import Logger
from ..obs.events import (
    EventLog,
    events_path,
    heartbeat_path,
    replay_into,
    write_heartbeat,
)
from ..obs import compiles, hoststats
from ..ops.flash_attention import plan_counts as flash_plan_counts
from ..models.moe import plan_counts as moe_plan_counts
from ..ops.fused_ce import plan_counts as fused_ce_plan_counts
from ..ops.grouped_matmul import plan_counts as gmm_plan_counts
from ..obs.flops import (GoodputLedger, matmul_params, model_flops_per_token,
                         peak_flops_per_chip)
from ..obs.flops import mfu as compute_mfu
from ..obs.metrics import MetricsRegistry
from ..obs.steprecord import ANNOTATED, PHASES, StepRecords
from ..obs.trace import Phase, Tracer
from ..optim import build_optimizer, build_schedule, schedule_value
from ..parallel import build_mesh
from ..tokenizer import TokenizerManager
from ..utils.compile_cache import enable_compilation_cache
from .early_stopping import EarlyStoppingMonitor
from .lr_finder import run_lr_finder
from .train_step import init_train_state, make_eval_step, make_train_step

# What was traced into the step, by the modules that choose while tracing: the
# key on the first step_window event -> (the log line's label, the tally).
_PLAN_TALLIES = {
    "flash_plan": ("flash plan (kernel calls traced, by path)", flash_plan_counts),
    "fused_ce_plan": ("fused CE (chunk walks traced)", fused_ce_plan_counts),
    "moe_plan": ("expert layers (traced, by form)", moe_plan_counts),
    "gmm_plan": ("grouped matmuls (kernel calls traced, by kernel and column block)",
                 gmm_plan_counts),
}


def _put_tree(tree: Any, shardings: Any) -> Any:
    """Place ``tree`` onto ``shardings`` without cross-process transfers.

    ``jax.device_put`` of a committed process-local array onto a sharding
    that spans processes issues eager per-buffer collectives; on the CPU
    (gloo) backend their issue order is not synchronized across processes,
    which intermittently aborts the transport (preamble-size mismatches)
    or silently corrupts state after an elastic restart. Every caller here
    holds the full value on every process — init replicates it (same seed)
    and resume loads it from disk — so multi-process placement can always
    go through ``make_array_from_callback``, which only uploads the
    addressable shards and never communicates.
    """
    if jax.process_count() <= 1:
        return jax.device_put(tree, shardings)

    def put(x, s):
        if s is None:
            return x
        if isinstance(x, jax.Array) and not x.is_fully_addressable:
            # Already a global array (the resharding loaders build these
            # straight onto the target placement); only move it if the
            # placement actually differs.
            try:
                same = x.sharding.is_equivalent_to(s, x.ndim)
            except Exception:
                same = x.sharding == s
            return x if same else jax.device_put(x, s)
        arr = np.asarray(x)
        return jax.make_array_from_callback(
            arr.shape, s, lambda idx, _a=arr: _a[idx])

    return jax.tree_util.tree_map(put, tree, shardings)


class Trainer:
    def __init__(
        self,
        config: Any,
        for_training: bool = True,
        runs_root: str = "runs",
        quiet: bool = False,
    ):
        self.config: Config = config if isinstance(config, Config) else Config.from_yaml(config)
        cfg = self.config
        self.for_training = for_training
        self.runs_root = runs_root
        # Span tracer (obs/trace.py), first: the constructor's sections are its
        # phases. It mirrors every goodput booking as a chrome-trace span
        # carrying the SAME duration, so per-window span sums reconcile with the
        # ledger by construction. Off by default; logging.trace.enabled turns
        # it on for the whole run, SIGUSR2 opens an on-demand capture window
        # mid-run. Named for its process below, once the flags are applied:
        # asking for the process index starts the backend.
        tcfg = dict(cfg.logging.trace or {})
        self.tracer = Tracer(
            "trainer",
            capacity=int(tcfg.get("capacity", 65536)),
            sample=float(tcfg.get("sample", 1.0)),
            enabled=bool(tcfg.get("enabled", False)))
        # Set-up's phases (name, start on time.time(), seconds), ring or no
        # ring: the ``compile`` event carries them (_setup_record).
        self._setup_phases: List[Tuple[str, float, float]] = []
        # The newest of obs/compiles.py's spans this trainer has written out
        # (_note_compiles): the ring and xla_compiled take what closed since.
        self._spans_seen_t = time.time()

        with self._setup_phase("init.system"):
            # -- system: XLA flag set, seeds, mesh (reference setup_system
            # :964-1016). Flags FIRST: they are read once at backend init, and
            # PRNGKey below initializes the backend.
            from ..parallel import xla_flags as xla_flags_mod

            self.xla_stamp = xla_flags_mod.apply_flag_set(
                cfg.system.xla_flag_set, extra=cfg.system.xla_extra_flags)
            self.rng = jax.random.PRNGKey(cfg.system.seed)
            np.random.seed(cfg.system.seed)
            self.tracer.service = f"trainer-p{jax.process_index()}"
            from ..parallel.context import set_mesh

            self.mesh = None
            explicit_mesh = bool(getattr(cfg.system, "mesh", None)) or cfg.system.model_parallel
            if explicit_mesh:
                self.mesh = build_mesh(cfg.system)
            elif jax.device_count() > 1 and for_training:
                # Implicit pure-DP mesh over all devices — but only when the
                # global batch divides evenly; otherwise stay single-program on
                # device 0 (the reference likewise falls back to one device when
                # distribution isn't configured: core/training.py:964-1016).
                if cfg.training.batch_size % jax.device_count() == 0:
                    self.mesh = build_mesh(cfg.system)
            set_mesh(self.mesh)

            # -- run dir ---------------------------------------------------------
            resume = cfg.resume is not None and bool(cfg.resume.checkpoint)
            run_dir = os.path.join(runs_root, cfg.name)
            # Destructive setup (overwrite rmtree) happens exactly once: on the
            # chief, in the fleet's FIRST generation. Supervisor restarts
            # (ELASTIC_GENERATION > 1) continue into the existing dir — wiping
            # it again would destroy events.jsonl and race against peers. The
            # barrier orders the chief's rmtree+mkdir before any peer writes
            # (heartbeats, tokenizer cache) land in the same tree.
            from ..parallel.elastic import ELASTIC_GENERATION_ENV, process_barrier

            elastic_gen = int(os.environ.get(ELASTIC_GENERATION_ENV) or 1)
            if (for_training and not resume and elastic_gen <= 1
                    and jax.process_index() == 0):
                run_dir = CheckpointManager.setup_run_directory(runs_root, cfg.name, cfg.overwrite)
            if for_training:
                process_barrier("run_dir_setup")
            self.run_dir = run_dir
            os.makedirs(run_dir, exist_ok=True)
            # Telemetry substrate (obs/metrics.py): one registry per Trainer —
            # subsystems record into it, Prometheus/stats export read from it.
            self.metrics = MetricsRegistry()
            self.checkpoints = CheckpointManager(
                run_dir, keep_last=cfg.logging.keep_last,
                keep_every=cfg.logging.keep_every, metrics=self.metrics)
            is_chief = jax.process_index() == 0
            self.logger = Logger(run_dir, cfg, quiet=quiet or not is_chief, write_files=is_chief)
            # Integrity events (quarantine, GC, ledger rebuild, degraded
            # optimizer resume) surface in log.txt, not just stderr.
            self.checkpoints.notify = self.logger.log
            # What this run actually executes on, in the first log line and the
            # run_start event: system.device in the config is only a label, and
            # a multi-chip or multi-host run that silently became one device
            # must be readable from the log alone.
            dev = jax.devices()[0]
            self.device_stamp = {
                "platform": dev.platform, "device_kind": dev.device_kind,
                "n_chips": jax.device_count(), "n_processes": jax.process_count()}
            self.logger.log(
                f"device: platform={dev.platform} kind={dev.device_kind} "
                f"count={jax.device_count()} processes={jax.process_count()}")
            if self.xla_stamp["xla_backend"] != dev.platform:
                self.logger.log(
                    f"WARNING: XLA flag set was resolved for backend "
                    f"{self.xla_stamp['xla_backend']!r} but the run is on "
                    f"{dev.platform!r} (parallel/xla_flags.py guess_backend)")
            if self.xla_stamp["xla_flags"]:
                applied = self.xla_stamp["xla_flags_applied"]
                self.logger.log(
                    f"xla flag set {self.xla_stamp['xla_flag_set']!r} "
                    f"({self.xla_stamp['xla_backend']}): "
                    + ("applied" if applied
                       else f"NOT applied — {self.xla_stamp.get('reason')}"))
            if for_training and not resume and is_chief:
                cfg.to_yaml(os.path.join(run_dir, "config.yaml"))

        with self._setup_phase("init.tokenizer"):
            # -- tokenizer -------------------------------------------------------
            self.tokenizer = TokenizerManager(cfg.data, run_dir=run_dir if for_training else None)

        with self._setup_phase("init.model"):
            # -- model -----------------------------------------------------------
            arch = resolve_architecture(cfg.model.architecture)
            self.arch = arch
            vocab_size = self.tokenizer.vocab_size
            if getattr(cfg.data, "source", None) == "token_shards":
                # Pre-tokenized binary shards: the shard index's vocab is
                # authoritative (the tokenizer is only used for sampling).
                idx_dir = getattr(cfg.data, "input_file", None) or (
                    getattr(cfg.data, "streaming", {}) or {}).get("shard_dir")
                if idx_dir:
                    idx_path = os.path.join(idx_dir, "index.json")
                    if os.path.isfile(idx_path):
                        with open(idx_path) as f:
                            vocab_size = int(json.load(f).get("vocab_size", vocab_size))
            args = arch.args_cls.from_config(cfg.model, vocab_size)
            if arch.force_attention:
                args = args.__class__(**{**args.__dict__, "attention_type": arch.force_attention})
            self.model_args = args
            self.rng, init_key = jax.random.split(self.rng)
            # The host's seconds (traces, compiles or cache loads, dispatches);
            # the device runs them behind the host and is waited for later.
            with self._setup_phase("init.params"):
                params = arch.init_params(init_key, args)
            self.n_params = llama_mod.num_params(params)
            # Shapes are all the step builders need (and the LR finder's
            # rebuild, later). The arrays themselves go into self.state below
            # and are NOT kept here: under a mesh that would hold a whole
            # unsharded copy on the first device beside its shard (seen on four
            # v5e chips: 4.12 GB in use on device 0, 0.83 GB on the others).
            self.params_like = jax.eval_shape(lambda: params)
            self.logger.log_model_summary(self.n_params, args)

            self.compute_dtype = jnp.bfloat16 if cfg.system.compute_dtype == "bfloat16" else jnp.float32
            # model.remat_policy is the first-class knob (named policies over
            # checkpoint_name-tagged sites); system.remat / the legacy
            # gradient_checkpointing bool remain as fallbacks.
            remat = cfg.model.remat_policy
            if remat is None:
                remat = cfg.system.remat
            if remat is None and cfg.system.gradient_checkpointing:
                remat = "full"
            if remat == "none":
                remat = None
            self.remat = remat
            self.remat_ratio = float(cfg.system.gradient_checkpointing_ratio)

            ce_chunk = int(getattr(cfg.system, "fused_ce_chunk", -1))
            if (ce_chunk == -1 and self.mesh is not None
                    and "sp" in self.mesh.axis_names and self.mesh.shape["sp"] > 1):
                if self.mesh.shape.get("tp", 1) > 1:
                    # With BOTH sp and tp, the projection is vocab-sharded and
                    # the sequence is sharded: neither fused path applies; the
                    # unfused CE under GSPMD is already vocab-parallel.
                    ce_chunk = 0
                    self.logger.log(
                        "fused CE auto-disabled on sp x tp mesh (vocab-sharded "
                        "projection); explicit fused_ce_chunk > 0 is respected")
                else:
                    # loss_fn routes to the shard_map sequence-sharded fused CE
                    # (ops/fused_ce.py::fused_cross_entropy_sp).
                    self.logger.log("fused CE: sequence-sharded path on sp mesh")

            scan_layers = bool(getattr(cfg.system, "scan_layers", False))
            # Manual fsdp gather/compute overlap (parallel/overlap.py). The
            # knob only requests it; models/llama.py still gates on
            # can_overlap(mesh, ...) so unsupported meshes fall back to GSPMD.
            overlap = bool(getattr(cfg.system, "overlap_gather", False))
            self.overlap_gather = overlap
            z_loss_weight = float(cfg.training.hyperparameters.get("z_loss") or 0.0)

            # MoE training steps carry routing stats (expert load, dropped
            # selections) out through loss_fn's aux — models/moe.py tap. The
            # pipeline loss threads the same stats through its tick carries
            # (make_pipeline_loss with_moe_stats), so pp and non-pp runs report
            # identical routing gauges.
            # Every architecture's loss_fn takes the same keywords; one whose
            # args say is_moe returns the stats under with_moe_stats.
            self.moe_stats_experts = args.num_local_experts if args.is_moe else 0
            _stats_kw = {"with_moe_stats": True} if self.moe_stats_experts else {}
            _ov_kw = {"overlap": True} if overlap else {}

            def loss_fn(params, batch):
                return arch.loss_fn(
                    params, batch, args, compute_dtype=self.compute_dtype,
                    remat=self.remat, remat_ratio=self.remat_ratio,
                    ce_chunk=ce_chunk, scan_layers=scan_layers,
                    z_loss_weight=z_loss_weight, **_stats_kw, **_ov_kw,
                )

            # Validation excludes MoE router aux terms: val loss / ppl stay pure
            # LM cross-entropy, comparable across dense and MoE runs.
            def eval_loss_fn(params, batch):
                return arch.loss_fn(
                    params, batch, args, compute_dtype=self.compute_dtype,
                    include_aux=False, ce_chunk=ce_chunk,
                    scan_layers=scan_layers,
                )

            self.loss_fn = loss_fn
            self.eval_loss_fn = eval_loss_fn

        with self._setup_phase("init.data"):
            # -- data ------------------------------------------------------------
            self.data: Optional[DataManager] = None
            if for_training:
                self.data = build_data_manager(
                    cfg,
                    self.tokenizer,
                    batch_size=cfg.training.batch_size,
                    seq_len=cfg.data.max_context_size,
                    seed=cfg.system.seed,
                    process_index=jax.process_index(),
                    process_count=jax.process_count(),
                )
                # A model trained by diffusion over blocks says how its batches are
                # noised (models/sdar.py); the loader's own are the clean rows.
                diffusion = getattr(args, "diffusion", None)
                if diffusion:
                    from ..data.block_diffusion import BlockDiffusionBatches

                    self.data = BlockDiffusionBatches(self.data, cfg.system.seed,
                                                      process_index=jax.process_index(), **diffusion)

        with self._setup_phase("init.optimizer"):
            # -- steps / optimizer (reference setup_training :1093-1133) --------
            self.total_steps = 0
            if for_training:
                if cfg.training.iters:
                    self.total_steps = cfg.training.iters
                elif hasattr(self.data, "batches_per_epoch"):
                    epochs = cfg.training.epochs or 1
                    self.total_steps = epochs * self.data.batches_per_epoch
                else:
                    raise ValueError("streaming data sources require training.iters")
            self.schedule = build_schedule(cfg.training, max(self.total_steps, 1))
            self.optimizer = build_optimizer(cfg.training, max(self.total_steps, 1), schedule=self.schedule)
            self.accum_steps = cfg.training.gradient_accumulation_steps

            # Pipeline parallelism: a pp>1 mesh axis switches the whole step to
            # the GPipe schedule (parallel/pipeline.py) over stacked layer params.
            self.pipeline = bool(
                self.mesh is not None
                and "pp" in self.mesh.axis_names
                and self.mesh.shape["pp"] > 1
            )
            self.pipeline_interleave = 1
            self.pipeline_compute_skip = True
            if self.pipeline:
                from ..parallel.pipeline import (
                    make_pipeline_loss,
                    make_pipeline_train_step,
                    stack_layers,
                )

                pp = self.mesh.shape["pp"]
                self.pipeline_interleave = max(1, int(
                    getattr(cfg.system, "pipeline_interleave", 1) or 1))
                self.pipeline_compute_skip = bool(
                    getattr(cfg.system, "pipeline_compute_skip", True))
                self.microbatches = int(cfg.system.pipeline_microbatches or 2 * pp)
                # Pipeline microbatching IS gradient accumulation: fold the
                # configured accum factor in so the effective batch semantics
                # match the same config on a non-pp mesh.
                if self.accum_steps > 1:
                    self.microbatches = max(self.microbatches, self.accum_steps)
                    self.logger.log(
                        f"pipeline: gradient_accumulation_steps={self.accum_steps} folded "
                        f"into {self.microbatches} microbatches"
                    )
                if cfg.training.batch_size % self.microbatches != 0:
                    raise ValueError(
                        f"batch_size {cfg.training.batch_size} must be divisible by "
                        f"pipeline_microbatches {self.microbatches}"
                    )
                if self.model_args.num_layers % (pp * self.pipeline_interleave) != 0:
                    raise ValueError(
                        f"num_layers {self.model_args.num_layers} must be divisible "
                        f"by pp*pipeline_interleave="
                        f"{pp}*{self.pipeline_interleave}"
                    )
                self.train_step, self.state_shardings = make_pipeline_train_step(
                    args, self.optimizer, self.mesh, self.microbatches,
                    compute_dtype=self.compute_dtype, remat=self.remat,
                    zero_level=cfg.system.zero_optimization_level,
                    params_like=self.params_like,
                    log_grad_norm=cfg.logging.log_gradient_norm,
                    ce_chunk=ce_chunk, z_loss_weight=z_loss_weight,
                    interleave=self.pipeline_interleave,
                    compute_skip=self.pipeline_compute_skip,
                    moe_stats_experts=self.moe_stats_experts,
                )
                self.eval_step = jax.jit(make_pipeline_loss(
                    args, self.mesh, self.microbatches,
                    compute_dtype=self.compute_dtype, include_aux=False,
                    ce_chunk=ce_chunk, interleave=self.pipeline_interleave,
                    compute_skip=self.pipeline_compute_skip,
                ))
                self.state = init_train_state(
                    stack_layers(params, interleave=self.pipeline_interleave),
                    self.optimizer)
                self.state = _put_tree(self.state, self.state_shardings)
            else:
                self.train_step, self.state_shardings = make_train_step(
                    self.loss_fn, self.optimizer,
                    accum_steps=self.accum_steps,
                    mesh=self.mesh,
                    zero_level=cfg.system.zero_optimization_level,
                    log_grad_norm=cfg.logging.log_gradient_norm,
                    params_like=self.params_like,
                    moe_stats_experts=self.moe_stats_experts,
                )
                self.eval_step = make_eval_step(self.eval_loss_fn, self.mesh, self.state_shardings)

                self.state = init_train_state(params, self.optimizer)
                if self.mesh is not None and self.state_shardings is not None:
                    self.state = _put_tree(self.state, self.state_shardings)
            del params

        with self._setup_phase("init.telemetry"):
            # optional live stats publishing (obs/stats_server.py hub)
            self.stats_client = None
            if for_training and cfg.logging.stats_url:
                from ..obs.stats_client import StatsClient

                self.stats_client = StatsClient(
                    cfg.logging.stats_url,
                    worker_id=f"{cfg.name}-p{jax.process_index()}",
                ).start()
                self.stats_client.register({"devices": jax.local_device_count()})

            self.early_stopping = EarlyStoppingMonitor.from_config(cfg.training)
            self.total_tokens = 0
            self.start_step = 0
            self.val_history: Dict[str, list] = {"steps": [], "losses": []}
            # Created by train() right before the step loop; checkpoints read
            # the consumed loader position through it (see _data_state).
            self.prefetcher: Optional[DevicePrefetcher] = None

            # -- telemetry (obs/): FLOPs model, goodput ledger, event log -------
            # MFU accounting: analytic FLOPs/token from the model config + exact
            # param count, peak from the chip's device_kind (None on CPU — log
            # lines then report mfu=unknown; an unlisted accelerator raises).
            self.flops_per_token = (
                arch.flops_per_token(args, cfg.data.max_context_size)
                if arch.flops_per_token is not None else model_flops_per_token(
                    cfg.model, self.n_params, cfg.data.max_context_size,
                    vocab_size=self.model_args.vocab_size))
            self.peak_flops = peak_flops_per_chip()
            self.goodput = GoodputLedger()
            self._trace_capture_steps = int(tcfg.get("capture_steps", 20))
            self._trace_request = 0   # bumped by SIGUSR2
            self._trace_until = 0     # on-demand window end step (exclusive)
            self._trace_owns_prof = False
            self._trace_prev_enabled = self.tracer.enabled
            # Single owner of jax.profiler start/stop (obs/profiler.py): the
            # profile window, SIGUSR2 capture, and end-of-run finally all go
            # through it, and every stop runs the graftprof attribution over
            # the fresh dump (logging.profile_report.enabled gates it).
            from ..obs.profiler import ProfileCapture

            self.profiler = ProfileCapture(
                os.path.join(run_dir, "profile"),
                log=self.logger.log,
                sync=lambda: jax.block_until_ready(self.state["step"]),
                analytic_fn=self._prof_analytic,
                summary_path=os.path.join(run_dir, "prof_summary.json"),
                report=cfg.logging.profile_report_enabled,
                top_k=cfg.logging.profile_report_top_k)
            self._compiled = False  # first dispatch books into compile_s
            # obs/compiles.py's backend compiles, count and seconds, at the last
            # window's close: the difference rides each step_window event as
            # xla_compiles / xla_compile_s, and what was built since the newest
            # span written out as xla_compiled, by name (the first dispatch
            # writes out, so the first window names what the compile event does not).
            self._compiles_seen = compiles.totals()
            # The name JAX reports the jitted step under, read before anyone
            # wraps it (the benchmark does).
            self._step_fun = getattr(self.train_step, "__name__", None)
            self._side_s = 0.0  # seconds a capture's start or stop took inside the open step
            # Which path the step's flash kernels, forward and backward, were traced to
            # (ops/flash_attention.py flash_plan), and whether its fused CE computes
            # the head's gradients in the forward walk (ops/fused_ce.py), and how many
            # expert layers it dispatches and combines by gathers (models/moe.py), and
            # how many gmm and tgmm calls keep an expert's block in VMEM, at which
            # column block (ops/grouped_matmul.py gmm_plan): the tallies since here,
            # logged after the first compile and carried by the first step_window event.
            self._plan_tallies = {**_PLAN_TALLIES, **(arch.plans or {})}
            self._plans_seen = {name: counts() for name, (_, counts) in self._plan_tallies.items()}
            self._plans: Optional[Dict[str, Dict[str, int]]] = None
            self._metrics_server = None
            # events.jsonl is the durable telemetry source: replay it FIRST so
            # counters survive crash-restarts, then open for append. Chief only
            # (one file per run; non-chief processes keep a local registry).
            self.events: Optional[EventLog] = None
            self._hb_path: Optional[str] = None
            if for_training and is_chief:
                replayed = replay_into(self.metrics, events_path(run_dir))
                if replayed:
                    self.logger.log(
                        f"telemetry: registry rebuilt from {replayed} events "
                        f"in {events_path(run_dir)}")
                self.events = EventLog(
                    events_path(run_dir),
                    max_bytes=self.config.logging.events_max_bytes)
            if for_training:
                # Per-host heartbeat: process 0 keeps the legacy heartbeat.json
                # name; peers write heartbeat_p<idx>.json — so a supervisor
                # watchdog can attribute a fleet stall to the host that
                # stopped beating, not just "somewhere".
                self._hb_path = heartbeat_path(run_dir, jax.process_index())
            if for_training and jax.process_count() > 1:
                # Generation-stamped membership record (parallel/elastic.py):
                # every host agrees which epoch of the world it joined. The
                # device barrier first makes sure no peer records into a run
                # dir the chief is still (re)creating. Best-effort: telemetry
                # must never kill training.
                try:
                    from jax.experimental import multihost_utils

                    from ..parallel.elastic import record_membership

                    multihost_utils.sync_global_devices("elastic_membership")
                    rec = record_membership(run_dir, log=self.logger.log)
                    self.logger.log(
                        f"elastic: recorded membership generation "
                        f"{rec['generation']} as process "
                        f"{jax.process_index()}/{jax.process_count()}")
                except Exception as e:  # noqa: BLE001 - advisory record only
                    self.logger.log(
                        f"WARNING: elastic membership record failed "
                        f"({type(e).__name__}: {e}); continuing")
            # Handles for the hot-path counters (idempotent re-declaration —
            # replay_into already registered them).
            self._m_steps = self.metrics.counter(
                "train_steps_total", "optimizer steps completed over the run lifetime")
            self._m_toks = self.metrics.counter(
                "train_tokens_total", "non-pad target tokens trained on")
            self._m_saves = self.metrics.counter(
                "checkpoint_saves_total", "checkpoints written")
            self._m_evals = self.metrics.counter(
                "eval_runs_total", "validation passes")
            self._m_goodput = self.metrics.counter(
                "goodput_seconds_total", "wall-clock seconds by goodput component")
            self._g_step = self.metrics.gauge("train_step", "current optimizer step")
            self._g_loss = self.metrics.gauge("train_loss", "last logged train loss")
            self._g_tok_s = self.metrics.gauge(
                "train_tok_s", "global tokens/second over the last window")
            self._g_mfu = self.metrics.gauge(
                "train_mfu", "model FLOPs utilization over the last window")
            # graftscope anomaly-rule inputs: the gradient norm was only ever
            # a log-line field, and non-finite loss windows only a warning —
            # export both so the grad-norm-blowup and NaN-sentinel rules have
            # a scrapeable series.
            self._g_grad_norm = self.metrics.gauge(
                "train_grad_norm", "global gradient norm over the last window")
            self._m_nonfinite = self.metrics.counter(
                "train_nonfinite_total",
                "logging windows whose loss came back NaN/Inf")
            self._g_prof = {
                "prof_compute_frac": self.metrics.gauge(
                    "prof_compute_frac",
                    "step time in compute ops (last graftprof attribution)"),
                "prof_comm_frac": self.metrics.gauge(
                    "prof_comm_frac",
                    "step time in EXPOSED collectives (not hidden under "
                    "compute) from the last graftprof attribution"),
                "prof_overlap_frac": self.metrics.gauge(
                    "prof_overlap_frac",
                    "fraction of collective time overlapped with compute "
                    "(1.0 = fully hidden) from the last graftprof attribution"),
                "prof_idle_frac": self.metrics.gauge(
                    "prof_idle_frac",
                    "step time with no device op running (last graftprof "
                    "attribution)"),
            }
            if self.moe_stats_experts:
                self._m_moe_dropped = self.metrics.counter(
                    "moe_dropped_tokens_total",
                    "expert selections dropped by capacity limits (0 when dropless)")
                self._g_moe_load = self.metrics.gauge(
                    "moe_expert_load_frac",
                    "per-expert fraction of routed selections over the last window")
                self._g_moe_entropy = self.metrics.gauge(
                    "moe_balance_entropy",
                    "normalized routing entropy over the last window (1.0 = uniform)")
            self._g_bubble = None
            self._bubble_frac = 0.0
            if self.pipeline:
                from ..obs.flops import pipeline_bubble_frac

                self._bubble_frac = pipeline_bubble_frac(
                    self.mesh.shape["pp"], self.microbatches,
                    self.pipeline_interleave)
                self._g_bubble = self.metrics.gauge(
                    "pipeline_bubble_frac",
                    "fraction of pipeline schedule ticks spent in the "
                    "warmup/drain bubble (idle with compute-skip)")
                self._g_bubble.set(self._bubble_frac)

        if resume and for_training:
            with self._setup_phase("init.restore"):
                self._resume()

    def _host_params(self):
        """Current params in the canonical list-of-layers layout (pipeline
        mode stores them stacked [L, ...]; checkpoints and generation use
        the unstacked layout so files stay interchangeable across meshes)."""
        if self.pipeline:
            from ..parallel.pipeline import unstack_layers

            return unstack_layers(self.state["params"], self.model_args.num_layers,
                                  interleave=self.pipeline_interleave)
        return self.state["params"]

    def _host_opt_state(self):
        """Optimizer state with stacked ``layers`` subtrees unstacked — same
        cross-mesh checkpoint compatibility as :meth:`_host_params`."""
        if self.pipeline:
            from ..parallel.pipeline import unstack_opt_state

            return unstack_opt_state(self.state["opt_state"], self.model_args.num_layers,
                                     interleave=self.pipeline_interleave)
        return self.state["opt_state"]

    # -- checkpointing ------------------------------------------------------
    def _data_state(self) -> Dict[str, Any]:
        """Loader position as consumed by the trainer. When the device
        prefetcher is active its snapshot wins: batches sitting in the
        device queue have NOT been trained on, so saving the raw loader's
        position would skip them on resume."""
        if self.prefetcher is not None:
            return self.prefetcher.state_dict()
        return self.data.state_dict() if self.data else {"val_ptr": 0}

    def save_checkpoint(self, step, blocking: bool = True) -> None:
        """Timed + profiler-annotated wrapper: the save's train-loop cost
        (gather + serialize enqueue; the disk write itself overlaps when
        async) books into the goodput ledger as ``ckpt_save_s`` and lands
        in events.jsonl, and the heartbeat is refreshed afterwards so a
        long blocking save never trips the hang watchdog."""
        with self.tracer.phase("checkpoint_save", step=str(step)) as ph:
            self._save_checkpoint_inner(step, blocking)
        dt = ph.seconds
        self.goodput.add("ckpt_save_s", dt)
        self._m_saves.inc()
        if self.events is not None:
            self.events.append("checkpoint_save", step=step,
                               seconds=round(dt, 4), blocking=bool(blocking))
        self._touch_heartbeat()

    def _trace_phase(self, name: str, dur_s: float, **args) -> None:
        """Record, after the fact, a wait the prefetch worker measured
        (``data_wait`` / ``h2d_wait``; same duration the ledger got). The
        loop's own phases are live spans (``tracer.phase``). A no-op
        method call when tracing is off — nothing allocated."""
        if self.tracer.enabled:
            self.tracer.complete(name, dur_s, **args)

    @contextlib.contextmanager
    def _setup_phase(self, name: str) -> Iterator[Phase]:
        """A phase of set-up: a ``tracer.phase`` (an annotation always, a ring
        span with the ring on) whose name, start and seconds are also kept."""
        with self.tracer.phase(name) as ph:
            yield ph
        self._setup_phases.append((name, ph.t, ph.seconds))

    def _note_compiles(self) -> Dict[str, Optional[str]]:
        """What JAX built since the last call (obs/compiles.py's outermost
        spans): into the ring as completed ``compile.<stage>`` spans, so they
        lie under the phase they fell in; those that ended inside this
        trainer's profiler session onto its clock, zero-length as
        ``train.step_record`` is; and, returned, the backend compiles by
        function name (the 8 longest) with the cache's outcome."""
        found = compiles.spans(self._spans_seen_t)
        if not found:
            return {}
        self._spans_seen_t = max(s.end for s in found)
        for s in found:
            if self.tracer.enabled:
                self.tracer.complete("compile." + s.stage, s.seconds, end_wall=s.end,
                                     fun=s.fun, **({"cache": s.cache} if s.cache else {}))
            if self.profiler.active and s.end >= self.profiler.started_t:
                with jax.profiler.TraceAnnotation("xla_compile", fun=s.fun, stage=s.stage,
                                                  seconds=round(s.seconds, 6)):
                    pass
        built = sorted((s for s in found if s.stage == "backend"), key=lambda s: -s.seconds)
        return {s.fun: s.cache for s in built[:8]}

    def _setup_record(self) -> Dict[str, Any]:
        """The run's account of its set-up, from the process's start to the
        first dispatch's end, as the ``compile`` event carries it."""
        found = compiles.spans()
        phases = []
        for name, t, seconds in sorted(self._setup_phases, key=lambda ph: ph[1]):
            ph = {"name": name, "t": round(t, 6), "seconds": round(seconds, 6)}
            # what of the phase was a trace, a lowering, a compile or a cache load
            ph.update({k: round(v, 6) for k, v in
                       compiles.inside(t, t + seconds, found).items() if v >= 5e-4})
            phases.append(ph)
        def rounded(f: Optional[Dict[str, Any]]) -> Optional[Dict[str, Any]]:
            return f and {k: round(v, 6) if isinstance(v, float) else v for k, v in f.items()}

        funs = compiles.functions()
        longest = sorted(funs, key=lambda n: -sum(funs[n][st + "_s"] for st in compiles.STAGES))
        return {
            "process_start_t": hoststats.process_start_t(),
            "phases": phases,
            "stages": compiles.stages(),
            "step_fun": self._step_fun,
            # this trainer's step: a process may have built another's before
            "step_stages": rounded(compiles.functions(phases[0]["t"]).get(self._step_fun)),
            "functions": {name: rounded(funs[name]) for name in longest[:8]},
            "misses": [name for name in longest if funs[name]["cache"] == "miss"][:16],
        }

    def _book_dispatch(self, phase: Phase, step: int) -> None:
        """Book one call into the jitted step (``phase``: its
        ``train.dispatch``): the run's first dispatch is dominated by the
        trace, the lowering and the XLA compile and goes to ``compile_s``, so
        that steady-state ``dispatch_s`` stays meaningful (later compilations
        show as ``xla_compiles`` and ``xla_compiled`` on the window's event).
        Where it closes, set-up is over: the ``compile`` event says what it
        was made of (``_setup_record``)."""
        seconds = phase.seconds
        if self._compiled:
            self.goodput.add("dispatch_s", seconds)
            return
        self._compiled = True
        self.goodput.add("compile_s", seconds)
        self._setup_phases.append(("train.dispatch", phase.t, seconds))
        setup = self._setup_record()
        self._note_compiles()
        if self.events is not None:
            self.events.append("compile", seconds=round(seconds, 4), step=step, **setup)
        st, fn, since = setup["stages"], setup["step_stages"], setup["process_start_t"]
        self.logger.log(
            f"set-up to step {step}'s dispatch returning"
            + (f", {phase.t + seconds - since:.1f} s since the process started" if since else "")
            + ": " + ", ".join(f"{ph['name']} {ph['seconds']:.2f}" for ph in setup["phases"])
            + (f"; {self._step_fun}: trace {fn['trace_s']:.2f}, lower {fn['lower_s']:.2f}, "
               f"backend {fn['backend_s']:.2f} (cache {fn['cache'] or 'off'})" if fn else "")
            + f"; every program: trace {st['trace_s']:.2f}, lower {st['lower_s']:.2f}, backend "
            f"{st['backend_s']:.2f} s in {st['backend_n']}, cache hits {st['cache_hits']}, "
            f"misses {st['cache_misses']}"
            + (" (" + ", ".join(setup["misses"]) + ")" if setup["misses"] else ""))
        self._plans = {name: {key: n - self._plans_seen[name].get(key, 0)
                              for key, n in counts().items()}
                       for name, (_, counts) in self._plan_tallies.items()}
        self.logger.log("; ".join(
            f"{label}: " + ", ".join(f"{key}={n}" for key, n in self._plans[name].items())
            for name, (label, _) in self._plan_tallies.items()))

    def _step_closed(self, rec: Optional[Dict[str, Any]]) -> None:
        """A step's record has closed (the loop's top, the start of work
        beside the step, or the loop's end): put it on the profiler's clock
        and in the ring, say so if it was late, and append the window's event
        that waited for it."""
        self._side_s = 0.0
        if rec is None:
            return
        # Zero-length: an open profiler session gets the record on the device
        # trace's clock, on this thread; without one it is a flag test.
        with jax.profiler.TraceAnnotation("train.step_record", **{k: rec[k] for k in ANNOTATED}):
            pass
        # A phase of the loop like the other four: the event's write is host
        # work a step, which the benchmark's step_host_ms counts by its train.* name.
        with self.tracer.phase("train.step_close", step=rec["step"]):
            if self.tracer.enabled:
                self.tracer.complete("train.step", rec["wall_s"],
                                     end_mono=self._steps.closed_at, **rec)
            over = rec.get("x_median", 0.0)
            if over > StepRecords.STALL_FACTOR:
                self.logger.log(
                    f"WARNING: step {rec['step']} took {rec['wall_s']:.3f} s, {over:.1f} x the "
                    f"run's median step; process CPU {rec['proc_cpu_s']:.3f} s, phases "
                    + ", ".join(f"{k}={rec[k]:.3f}" for k in PHASES))
                if self.events is not None:
                    self.events.append("step_stall", **rec)
            if self._pending_window is not None:
                ev, self._pending_window = self._pending_window, None
                fields = self._steps.window()
                if self.events is not None:
                    self.events.append("step_window", **ev, **fields)

    def _touch_heartbeat(self, step: Optional[int] = None) -> None:
        if self._hb_path is None:
            return
        if step is not None:
            self._hb_step = int(step)
        try:
            write_heartbeat(self._hb_path,
                            getattr(self, "_hb_step", self.start_step),
                            process_index=jax.process_index())
        except OSError:
            pass  # heartbeat is advisory; never kill training over it

    def _prof_analytic(self) -> Dict[str, Any]:
        """Analytic joins for the graftprof report: the exact numbers the
        trainer already holds for MFU, split into the 6N matmul term and
        the attention residual (obs/flops.py convention)."""
        cfg = self.config
        matmul = 6.0 * matmul_params(cfg.model, self.n_params,
                                     self.model_args.vocab_size)
        return {
            "tokens_per_step": float(cfg.training.batch_size)
            * float(cfg.data.max_context_size),
            "matmul_flops_per_token": matmul,
            "attn_flops_per_token": max(
                0.0, float(self.flops_per_token) - matmul),
        }

    def _apply_profile_report(self, report, step: Optional[int]) -> None:
        """Fan one graftprof attribution out to gauges, the event log,
        and the run log. No-op on None (capture yielded nothing)."""
        if not report:
            return
        from ..obs.profile_report import prof_fields

        fields = prof_fields(report)
        for name, val in fields.items():
            self._g_prof[name].set(val)
        agg = report["aggregate"]
        self.logger.log(
            f"graftprof: steps={agg['n_steps']} "
            f"compute={fields['prof_compute_frac']:.3f} "
            f"comm_exposed={fields['prof_comm_frac']:.3f} "
            f"overlap={fields['prof_overlap_frac']:.3f} "
            f"idle={fields['prof_idle_frac']:.3f} "
            f"(summary: {self.profiler.summary_path})")
        if self.events is not None:
            ev = dict(fields)
            if step is not None:
                ev["step"] = int(step)
            self.events.append("profile_report", **ev)

    def _save_checkpoint_inner(self, step, blocking: bool = True) -> None:
        # The host gather is a COLLECTIVE when state is sharded across
        # processes (multi-host FSDP/ZeRO), so every process runs it; only
        # process 0 touches the filesystem afterwards.
        from ..checkpoint.manager import _to_numpy_tree

        host_params = _to_numpy_tree(self._host_params())
        host_opt = _to_numpy_tree(self._host_opt_state())
        if jax.process_count() > 1 and self.data is not None:
            # Data-loader position is PER HOST (each host consumes a
            # disjoint stream); every process writes its own sidecar so
            # resume restores each host's exact position, not process 0's.
            os.makedirs(self.checkpoints.checkpoint_dir, exist_ok=True)
            sidecar = os.path.join(
                self.checkpoints.checkpoint_dir,
                f"step_{step}_data_p{jax.process_index()}.json")
            # Temp+rename (not a plain json.dump): a crash mid-write must
            # not leave a torn sidecar that corrupts this host's resume
            # position. The chief folds the sidecars into the step manifest.
            _atomic_json(sidecar, self._data_state())
        if jax.process_index() != 0:
            return
        training_state = {
            "step": int(self.state["step"]),
            "total_tokens": int(self.total_tokens),
            **self._data_state(),
            "validation": self.val_history,
            "early_stopping": self.early_stopping.state_dict(),
        }
        self.checkpoints.save(
            step, host_params, host_opt, training_state,
            metadata_extra={"total_tokens": int(self.total_tokens)},
            blocking=blocking,
        )
        self._write_metadata_summary()
        self.logger.log(f"Saved checkpoint at step {step}"
                        + ("" if blocking else " (async write)"))

    def _write_metadata_summary(self) -> None:
        self.checkpoints.update_ledger(
            validation=self.val_history, total_tokens=int(self.total_tokens))

    def _resolve_resume_tag(self) -> Optional[str]:
        """Map ``resume.checkpoint`` onto a VERIFIED step tag.

        "latest"/"" asks latest_complete_step() for the newest manifested,
        checksum-clean step (quarantining corrupt ones and falling back
        through older checkpoints). An explicit tag is verified too: a tag
        with no manifest but files on disk loads unverified (legacy
        pre-manifest checkpoint); a tag whose manifest fails size/CRC
        checks raises in strict mode, otherwise is quarantined and resume
        falls back to the newest verified step. Returns None when nothing
        resumable exists (caller starts from scratch, or raises in strict
        mode)."""
        rc = self.config.resume
        strict = bool(rc.strict)
        tag = rc.checkpoint
        if tag in ("latest", ""):
            resolved = self.checkpoints.latest_complete_step()
            if resolved is None and strict:
                raise CheckpointIntegrityError(
                    f"resume.checkpoint={tag!r} with resume.strict: no "
                    f"verified checkpoint exists in {self.checkpoints.checkpoint_dir}")
            return resolved
        ok, reason = self.checkpoints.verify(tag)
        if ok:
            return tag
        if reason == "no manifest":
            # Quarantine is reserved for steps whose manifest EXISTS and
            # fails size/CRC checks. A requested tag with no manifest but
            # files on disk is a legacy pre-manifest checkpoint (even in a
            # mixed-era dir where newer steps do have manifests): honor
            # the user's explicit choice and load it unverified.
            model_path, _, _ = self.checkpoints.paths_for_step(tag)
            if os.path.isfile(model_path):
                self.logger.log(
                    f"resume: checkpoint {tag} has no integrity manifest "
                    f"(pre-manifest checkpoint); loading unverified")
                return tag
            # No manifest AND no files: the tag simply doesn't exist —
            # nothing to quarantine.
            if strict:
                raise CheckpointIntegrityError(
                    f"resume.checkpoint={tag} does not exist in "
                    f"{self.checkpoints.checkpoint_dir} and resume.strict is set")
            self.logger.log(
                f"WARNING: resume.checkpoint={tag} does not exist; falling "
                f"back to the newest verified checkpoint")
            return self.checkpoints.latest_complete_step()
        if strict:
            raise CheckpointIntegrityError(
                f"resume.checkpoint={tag} failed verification ({reason}) "
                f"and resume.strict is set")
        self.logger.log(
            f"WARNING: resume.checkpoint={tag} failed verification "
            f"({reason}); quarantining it and falling back to the newest "
            f"verified checkpoint")
        self.checkpoints.quarantine_step(tag, reason)
        return self.checkpoints.latest_complete_step()

    def _resume_data_state(self, tag, tstate: Dict[str, Any]) -> Dict[str, Any]:
        """Data-loader position for THIS host. Same-world resume reads the
        host's own sidecar (or the chief's training_state snapshot for
        single-process runs); a world-size change routes every old host's
        snapshot through ``data.streaming.remap_data_states`` so the new
        fleet resumes with zero skipped and zero replayed documents."""
        from ..data.streaming import remap_data_states

        pindex, pcount = jax.process_index(), jax.process_count()
        sidecars = self.checkpoints.data_sidecar_states(tag)
        if sidecars:
            old_world = len(sidecars)
            if old_world == pcount and pindex in sidecars:
                return sidecars[pindex]
            states = [sidecars[i] for i in sorted(sidecars)]
            remapped = remap_data_states(states, pindex, pcount)
            self.logger.log(
                f"elastic: remapped data position from a {old_world}-host "
                f"snapshot to {pcount} host(s); this is process {pindex}")
            return remapped
        old_world = int(tstate.get("process_count", 1) or 1)
        if old_world == pcount:
            return tstate
        snap = {k: tstate[k]
                for k in ("docs_consumed", "buf", "source", "hf")
                if k in tstate}
        snap["process_count"] = old_world
        snap["process_index"] = int(tstate.get("process_index", 0) or 0)
        remapped = remap_data_states([snap], pindex, pcount)
        self.logger.log(
            f"elastic: remapped data position from a {old_world}-host "
            f"snapshot to {pcount} host(s); this is process {pindex}")
        return remapped

    def _resume(self) -> None:
        """Resume from ``resume.checkpoint`` (reference: :1545-1564 with
        reset_optimizer / reset_training_state flags :124-127), but only
        ever from a checkpoint that passed manifest verification."""
        rc = self.config.resume
        tag = self._resolve_resume_tag()
        if tag is None:
            self.logger.log(
                "WARNING: no resumable checkpoint found; starting from scratch")
            return
        # The resume source must survive retention GC for the whole run:
        # until the first NEW checkpoint lands it is the only good state.
        self.checkpoints.protect_steps.add(str(tag))
        # Mesh runs reshard straight from disk into the live placement —
        # params through load_params(mesh=) / load_params_stacked and the
        # optimizer moments through load_opt_state_resharded — so the
        # on-disk mesh shape is irrelevant: an fsdp4 checkpoint resumes on
        # fsdp2×pp2 (and vice versa) via per-device-slice callbacks with
        # no host gather and no device ever holding a full replica.
        pp_direct = self.pipeline and self.mesh is not None
        mesh_direct = self.mesh is not None and self.state_shardings is not None
        host_like = not (pp_direct or mesh_direct)
        params, opt_state, tstate = self.checkpoints.load(
            tag,
            like_params=self._host_params() if host_like else None,
            like_opt_state=(self._host_opt_state()
                            if not rc.reset_optimizer and not mesh_direct
                            else None),
            strict=bool(rc.strict),
            with_params=host_like,
        )
        if mesh_direct and not rc.reset_optimizer:
            opt_state = self.checkpoints.load_opt_state_resharded(
                tag, self.state["opt_state"],
                self.state_shardings["opt_state"],
                num_layers=self.model_args.num_layers if self.pipeline else 0,
                interleave=self.pipeline_interleave if self.pipeline else 1,
                strict=bool(rc.strict))
        if opt_state is None and not rc.reset_optimizer:
            self.logger.log(
                f"WARNING: resuming step {tag} WITHOUT optimizer state "
                f"(missing/unreadable) — moment statistics restart from "
                f"zero; set resume.strict: true to fail instead")
        step = 0 if rc.reset_training_state else int(tstate.get("step", 0))
        if pp_direct:
            model_path, _, _ = self.checkpoints.paths_for_step(tag)
            params = self.checkpoints.load_params_stacked(
                model_path, self.mesh, self.model_args.num_layers,
                interleave=self.pipeline_interleave,
                like_stacked=self.state["params"])
        elif mesh_direct:
            model_path, _, _ = self.checkpoints.paths_for_step(tag)
            params = self.checkpoints.load_params(
                model_path, like=self.state["params"], mesh=self.mesh)
        else:
            params = jax.tree_util.tree_map(jnp.asarray, params)
        if opt_state is not None and not mesh_direct:
            opt_state = jax.tree_util.tree_map(jnp.asarray, opt_state)
        if self.pipeline:
            from ..parallel.pipeline import stack_layers, stack_opt_state

            if not pp_direct:
                params = stack_layers(
                    params, interleave=self.pipeline_interleave)
            if opt_state is not None and not mesh_direct:
                opt_state = stack_opt_state(
                    opt_state, self.model_args.num_layers,
                    interleave=self.pipeline_interleave)
        self.state = {
            "params": params,
            "opt_state": self.state["opt_state"] if rc.reset_optimizer or opt_state is None
            else opt_state,
            "step": jnp.asarray(step, jnp.int32),
        }
        if self.mesh is not None and self.state_shardings is not None:
            self.state = _put_tree(self.state, self.state_shardings)
        if not rc.reset_training_state:
            self.start_step = step
            self.total_tokens = int(tstate.get("total_tokens", 0))
            self.val_history = tstate.get("validation", self.val_history)
            if self.data:
                self.data.load_state_dict(self._resume_data_state(tag, tstate))
            self.early_stopping.load_state_dict(tstate.get("early_stopping", {}))
        self.logger.log(f"Resumed from checkpoint {tag} at step {self.start_step}")
        if self.events is not None:
            self.events.append("resume", tag=str(tag), step=self.start_step)

    # -- validation ---------------------------------------------------------
    def validate(self, cap: int = 50) -> Optional[float]:
        """Timed + profiler-annotated wrapper (see save_checkpoint): eval
        wall clock books into goodput as ``eval_s``; each completed pass
        counts in the registry and events.jsonl."""
        with self.tracer.phase("eval") as ph:
            result = self._validate_inner(cap)
        dt = ph.seconds
        self.goodput.add("eval_s", dt)
        if result is not None:
            self._m_evals.inc()
            if self.events is not None:
                self.events.append("eval", loss=result, seconds=round(dt, 4))
        self._touch_heartbeat()
        return result

    def _validate_inner(self, cap: int = 50) -> Optional[float]:
        if self.data is None or not self.data.has_validation_data:
            return None
        # Accumulate on device; a single host sync after the loop instead of
        # stalling the dispatch of the next batch on every float().
        total_nll, total_toks = None, None
        for batch in self.data.iter_validation(cap):
            loss, toks = self.eval_step(self.state["params"], _device_batch(batch))
            if total_nll is None:
                total_nll, total_toks = loss * toks, toks
            else:
                total_nll = total_nll + loss * toks
                total_toks = total_toks + toks
        if total_nll is None:
            return None
        total_toks = float(total_toks)
        if total_toks == 0:  # no usable batches — report "no signal", not 0.0
            return None
        return float(total_nll) / total_toks

    # -- sample generation (reference: :1818-1904) --------------------------
    def generate_samples(self, step: int, prompts=None, max_new_tokens: int = 48) -> None:
        try:
            from ..infer.generate import generate_text
        except ImportError:
            return
        prompts = prompts or ["Once upon a time"]
        count = int(self.config.logging.log_samples_count or 1)
        # Gather once (collective when params are process-sharded — all
        # processes participate), then only the chief generates.
        from ..checkpoint.manager import _to_numpy_tree

        host_params = jax.tree_util.tree_map(
            jnp.asarray, _to_numpy_tree(self._host_params()))
        if jax.process_index() != 0:
            return
        for prompt in prompts[:count]:
            try:
                text = generate_text(
                    host_params, self.model_args, self.tokenizer, prompt,
                    max_new_tokens=max_new_tokens, temperature=0.0,
                )
                self.logger.log_sample(step, prompt, text)
            except Exception as e:  # sampling must never kill training
                self.logger.log(f"sample generation failed: {e}")
                return

    # -- LR finder ----------------------------------------------------------
    def maybe_run_lr_finder(self) -> Optional[float]:
        """Run the sweep and ADOPT the suggested LR (reference:
        core/training.py:1569-1576 rebuilds the optimizer with it). Skipped
        on resume, as the reference does."""
        lf = dict(self.config.training.lr_finder or {})
        if not lf.get("enabled") or self.start_step > 0:
            return None
        if self.pipeline:
            raise ValueError(
                "training.lr_finder.enabled is not supported with pipeline "
                "parallelism (system.mesh.pp > 1) — run the finder on a "
                "dense mesh and set the LR explicitly"
            )
        self.logger.log("Running LR finder sweep")
        suggested, _, _ = run_lr_finder(
            self.state["params"], self.loss_fn,
            lambda i: _device_batch(self.data.generate_batch(i)),
            min_lr=float(lf.get("min_lr", 1e-7)),
            max_lr=float(lf.get("max_lr", 1.0)),
            num_steps=int(lf.get("num_steps", 100)),
            out_dir=self.run_dir,
        )
        self.logger.log(f"LR finder suggestion: {suggested:.3e}; rebuilding optimizer with it")
        self.config.training.hyperparameters["learning_rate"] = float(suggested)
        self.schedule = build_schedule(self.config.training, max(self.total_steps, 1))
        self.optimizer = build_optimizer(
            self.config.training, max(self.total_steps, 1), schedule=self.schedule)
        self.train_step, self.state_shardings = make_train_step(
            self.loss_fn, self.optimizer,
            accum_steps=self.accum_steps,
            mesh=self.mesh,
            zero_level=self.config.system.zero_optimization_level,
            log_grad_norm=self.config.logging.log_gradient_norm,
            params_like=self.params_like,
            moe_stats_experts=self.moe_stats_experts,
        )
        self.state = init_train_state(self.state["params"], self.optimizer)
        if self.mesh is not None and self.state_shardings is not None:
            self.state = _put_tree(self.state, self.state_shardings)
        return suggested

    # -- the loop -----------------------------------------------------------
    def train(self) -> Dict[str, Any]:
        cfg = self.config
        # From entry to the loop: what a run does before its first batch.
        with self._setup_phase("train.start"):
            train_t0 = time.perf_counter()
            # run_start is appended before any other activity (the step-0
            # validation below emits an eval event) so the stream always
            # opens with it on a fresh run.
            if self.events is not None and self.start_step == 0:
                self.events.append(
                    "run_start", name=cfg.name, total_steps=self.total_steps,
                    n_params=self.n_params, flops_per_token=self.flops_per_token,
                    peak_flops=self.peak_flops, **self.device_stamp,
                    # attribution stamp: every downstream number traces to the
                    # XLA flag set it ran under (parallel/xla_flags.py)
                    **self.xla_stamp)
            log_int = max(1, cfg.logging.logging_interval)
            ckpt_int = cfg.logging.checkpoint_interval
            val_int = cfg.logging.validation_interval
            self.maybe_run_lr_finder()

            # Optional jax.profiler trace window [profile_start, profile_stop).
            prof_start = int(cfg.logging.profile_start or 0)
            prof_stop = int(cfg.logging.profile_stop or 0)

            if self.start_step == 0 and val_int:
                v = self.validate()
                if v is not None:
                    self.logger.log_validation(0, v)
                    self.val_history["steps"].append(0)
                    self.val_history["losses"].append(v)

            window_tokens = 0
            window_steps = 0
            # Per-step MoE routing stats stay device-resident until the log
            # line reads them (one sync per window, same as loss).
            window_moe: list = []
            window_bd_rows: list = []  # a step's positions that carry a diffusion loss
            # Anything booked so far (step-0 validation, lr finder) happened
            # before the first window's clock starts — flush it into the run
            # totals so every window's components sum to its own wall time.
            self.goodput.close_window(time.perf_counter() - train_t0)
            window_start = time.perf_counter()
            last_loss = float("nan")
            stopped_early = False
            # One record a step (obs/steprecord.py), from one top of the loop to
            # the next, or to where an evaluation or a checkpoint begins. A
            # window's event is built in train.log_window and waits for its last
            # step's record to close; the host's counters (obs/hoststats.py) ride
            # it as differences between two window closes.
            self._steps = StepRecords()
            self._pending_window: Optional[Dict[str, Any]] = None
            self._side_s = 0.0
            self._host_seen = hoststats.window_totals()

            # Device-side input pipeline: a background worker keeps
            # data.prefetch_depth batches resident on device, pre-sharded to the
            # jitted step's expected layout, so the loop below never blocks on a
            # host->device copy (data/device_prefetch.py).
            self.prefetcher = DevicePrefetcher(
                self.data,
                mesh=self.mesh,
                depth=int(getattr(cfg.data, "prefetch_depth", 2)),
                start_step=self.start_step,
                total_steps=self.total_steps,
                metrics=self.metrics,
            )

            # Telemetry endpoints for the run: Prometheus exposition behind
            # logging.metrics_port (EVERY process serves — process i binds
            # metrics_port + i and stamps process_index into the exposition,
            # so multi-host fleets expose all hosts, not just the chief; the
            # server stays up after train() returns — daemon thread — so late
            # scrapes see the final counters), the run_start event, and the
            # first heartbeat so the supervisor's hang watchdog has a
            # baseline that covers the initial compile.
            if cfg.logging.metrics_port and self._metrics_server is None:
                from ..obs.prometheus import start_metrics_server

                pidx = jax.process_index()
                port = int(cfg.logging.metrics_port) + pidx
                self._metrics_server = start_metrics_server(
                    self.metrics, port, process_index=pidx)
                if self._metrics_server is not None:
                    self.logger.log(
                        f"telemetry: serving Prometheus metrics on "
                        f":{self._metrics_server.port}/metrics "
                        f"(process {pidx})")
                else:
                    self.logger.log(
                        f"telemetry: metrics port {port} "
                        f"unavailable; exporter disabled")
            self._touch_heartbeat(self.start_step)

        # Preemption-aware checkpointing (SURVEY.md §5 failure-detection
        # plan; the reference's only recovery story is checkpoint-resume):
        # SIGTERM/SIGINT set a flag; the loop saves and exits cleanly at the
        # next step boundary. Installed immediately before the try/finally
        # that restores them, so no exception can leak the handlers.
        self._preempted = False
        prev_handlers = {}

        def _on_signal(signum, frame):
            self._preempted = True
            # restore the previous handler so a second signal (e.g. a
            # repeated Ctrl-C during a hung step) terminates immediately
            import signal as _signal

            _signal.signal(signum, prev_handlers.get(signum, _signal.SIG_DFL))

        def _on_trace_signal(signum, frame):
            # On-demand capture trigger: `kill -USR2 <pid>` records spans
            # + a jax.profiler trace for the next capture_steps steps.
            self._trace_request += 1

        try:
            import signal as _signal

            for sig in (_signal.SIGTERM, _signal.SIGINT):
                # signal() returns None for handlers installed by non-Python
                # code; None is not restorable — map it to SIG_DFL.
                prev = _signal.signal(sig, _on_signal)
                prev_handlers[sig] = prev if prev is not None else _signal.SIG_DFL
            if hasattr(_signal, "SIGUSR2"):
                prev = _signal.signal(_signal.SIGUSR2, _on_trace_signal)
                prev_handlers[_signal.SIGUSR2] = (
                    prev if prev is not None else _signal.SIG_DFL)
        except (ValueError, OSError):  # non-main thread: no signal hooks
            prev_handlers = {}

        try:
            for step in range(self.start_step + 1, self.total_steps + 1):
                self._step_closed(self._steps.turn(
                    step, not self._compiled, compiles.totals()[0], self._side_s))
                # Starting or stopping a capture below is seconds of the
                # program's own host work inside this step: no stall.
                capture_state = (self.profiler.active, self._trace_until)
                capture_t0 = time.perf_counter()
                if prof_stop > prof_start:
                    if step >= prof_stop and self.profiler.active:
                        report = self.profiler.stop(step)
                        self._apply_profile_report(report, step)
                        if self.events is not None:
                            self.events.append("profiler", action="stop", step=step)
                    elif prof_start <= step < prof_stop \
                            and not self.profiler.active:
                        if self.profiler.start(step) \
                                and self.events is not None:
                            self.events.append("profiler", action="start", step=step)
                # On-demand capture window (SIGUSR2).
                if self._trace_until and step >= self._trace_until:
                    self._trace_until = 0
                    if self._trace_owns_prof and self.profiler.active:
                        report = self.profiler.stop(step)
                        self._trace_owns_prof = False
                        self._apply_profile_report(report, step)
                    out = os.path.join(self.run_dir, f"trace_step{step}.json")
                    self.tracer.export(out)
                    self.tracer.enabled = self._trace_prev_enabled
                    self.logger.log(f"trace capture: spans written to {out}")
                    if self.events is not None:
                        self.events.append("trace_capture", action="stop",
                                           step=step, path=out)
                if self._trace_request and not self._trace_until:
                    self._trace_request = 0
                    self._trace_until = step + max(1, self._trace_capture_steps)
                    self._trace_prev_enabled = self.tracer.enabled
                    self.tracer.enabled = True
                    if not self.profiler.active:
                        # start() never raises (capture is best-effort);
                        # a refused start just means spans-only capture.
                        self._trace_owns_prof = self.profiler.start(step)
                    self.logger.log(
                        f"trace capture: recording steps "
                        f"[{step}, {self._trace_until})")
                    if self.events is not None:
                        self.events.append("trace_capture", action="start",
                                           step=step, until=self._trace_until)
                if (self.profiler.active, self._trace_until) != capture_state:
                    self._side_s += time.perf_counter() - capture_t0
                try:
                    with self.tracer.phase("train.data_get", step=step) as ph:
                        batch, local_tokens, waits = self.prefetcher.get()
                    self._steps.note(data_get_s=round(ph.seconds, 6),
                                     queue_depth=waits["queue_depth"])
                    if not self._compiled:
                        self._setup_phases.append(("train.data_get", ph.t, ph.seconds))
                except StopIteration:  # finite stream ran dry (streaming sources)
                    self.logger.log(f"Data stream exhausted before step {step}; stopping")
                    break
                # Token counts (non-pad targets) come host-counted from
                # the prefetch worker, so tok/s stays correct even when
                # device metrics are only read every log_int steps.
                step_tokens = local_tokens * jax.process_count()
                window_tokens += step_tokens
                self.total_tokens += step_tokens
                self.goodput.add("data_wait_s", waits["data_wait_s"])
                self._trace_phase("data_wait", waits["data_wait_s"],
                                  step=step)
                if self.prefetcher.h2d_blocks_consumer:
                    self.goodput.add("h2d_wait_s", waits["h2d_wait_s"])
                    self._trace_phase("h2d_wait", waits["h2d_wait_s"],
                                      step=step)
                # StepTraceAnnotation: profiler traces carry the trainer's
                # step numbering, lining up with events.jsonl step_window
                # records.
                with jax.profiler.StepTraceAnnotation("train", step_num=step), \
                        self.tracer.phase("train.dispatch", step=step) as ph:
                    self.state, metrics = self.train_step(self.state, batch)
                self._book_dispatch(ph, step)
                self._steps.note(dispatch_s=round(ph.seconds, 6))

                window_steps += 1
                if self.moe_stats_experts and "moe_load" in metrics:
                    # Device arrays, no sync: summed/read at the log line.
                    window_moe.append((metrics["moe_load"], metrics["moe_dropped"],
                                       metrics["moe_chunks_whole"]))
                if "bd_loss_rows" in metrics:
                    window_bd_rows.append(metrics["bd_loss_rows"])
                if step % log_int == 0 or step == self.total_steps:
                    with self.tracer.phase("train.loss_sync", step=step) as ph:
                        loss = float(metrics["loss"])  # device sync point
                    self._steps.note(loss_sync_s=round(ph.seconds, 6))
                    with self.tracer.phase("train.log_window", step=step) as ph:
                        last_loss = loss
                        elapsed = max(time.perf_counter() - window_start, 1e-9)
                        # Close the goodput window: components (compile, data
                        # wait, h2d, dispatch, ckpt save, eval) plus the
                        # other_s residual sum to elapsed by construction.
                        gp = self.goodput.close_window(elapsed)
                        tok_s = window_tokens / elapsed
                        # tok_s is the job's; its chips are the mesh's
                        # (one device without a mesh), not the host's.
                        mfu_val = compute_mfu(
                            tok_s, self.flops_per_token, self.peak_flops,
                            self.mesh.size if self.mesh is not None else 1)
                        line = {
                            "loss": loss,
                            "ppl": float(math.exp(min(loss, 30.0))),
                            # Host-side numpy evaluation: the jnp path re-traces
                            # the schedule closure and syncs a device scalar on
                            # every log line (see tests/lint_fixtures).
                            "lr": schedule_value(self.schedule, step),
                            "tok/s": tok_s,
                            "toks": int(window_tokens),
                            # Hardware efficiency: analytic FLOPs/token * tok/s
                            # over chip peak (obs/flops.py); "unknown" on CPU,
                            # which has no listed peak.
                            "mfu": mfu_val if mfu_val is not None else "unknown",
                            # Goodput breakdown for this window (sums to wall
                            # time): data_wait is the only true input stall
                            # (queue get); h2d is booked only when the transfer
                            # blocks the step loop (prefetch_depth=0); dispatch
                            # is time inside the jitted-step calls; other_s is
                            # the residual.
                            "data_wait_s": gp["data_wait_s"],
                            "h2d_wait_s": gp["h2d_wait_s"],
                            "dispatch_s": gp["dispatch_s"],
                            "compile_s": gp["compile_s"],
                            "ckpt_save_s": gp["ckpt_save_s"],
                            "eval_s": gp["eval_s"],
                            "other_s": gp["other_s"],
                            "data_wait_frac": min(gp["data_wait_s"] / elapsed, 1.0),
                        }
                        if "grad_norm" in metrics:
                            line["grad_norm"] = float(metrics["grad_norm"])
                            self._g_grad_norm.set(line["grad_norm"])
                        if self.pipeline:
                            # Honest schedule accounting: the bubble is a
                            # property of (pp, M, V), constant across the run,
                            # but belongs on every window line next to mfu= so
                            # readers see the idle fraction the MFU number is
                            # already paying for.
                            line["bubble"] = round(self._bubble_frac, 4)
                            self._g_bubble.set(self._bubble_frac)
                        if window_moe:
                            # Routing observability (models/moe.py stats tap):
                            # expert-load fractions over the window, normalized
                            # balance entropy (1.0 = uniform routing, 0.0 = one
                            # expert takes everything), and the dropped-selection
                            # count (always 0 for the dropless grouped impl;
                            # nonzero under einsum capacity or a capped ep
                            # exchange factor).
                            import numpy as _np

                            # Summed on the host: adding device arrays here would
                            # launch a device program or two a window, which a
                            # profile's Steps line counts as steps of their own.
                            load = sum(_np.asarray(m[0], _np.float64) for m in window_moe)
                            dropped = int(sum(float(m[1]) for m in window_moe))
                            total = max(load.sum(), 1.0)
                            frac = load / total
                            nz = frac[frac > 0]
                            ent = float(-(nz * _np.log(nz)).sum() / math.log(max(len(load), 2)))
                            line["moe_entropy"] = ent
                            line["moe_drop"] = dropped
                            line["moe_load_max"] = float(frac.max())
                            held = getattr(self.model_args, "experts_held", None)
                            if held is not None:
                                # An expert layer that holds a share of the
                                # router's experts: selections that landed on
                                # it, and how unevenly.
                                mine = load[held[0]:held[0] + held[1]]
                                line["moe_rows_held"] = int(mine.sum())
                                # chunks whose rows did not fit the small buffer
                                line["moe_chunks_whole"] = int(sum(float(m[2]) for m in window_moe))
                                line["moe_load_max_over_mean"] = float(
                                    mine.max() / max(mine.mean(), 1e-9))
                            self._g_moe_entropy.set(ent)
                            self._m_moe_dropped.inc(dropped)
                            for e, f in enumerate(frac):
                                self._g_moe_load.set(float(f), expert=str(e))
                            window_moe = []
                        for term in ("main_loss", "mtp_loss"):  # a loss of several heads
                            if term in metrics:
                                line[term] = float(metrics[term])
                        if window_bd_rows:
                            # Diffusion over blocks (models/sdar.py): positions whose loss
                            # counted in the window, and what the attention plan traced
                            # visits of its tile grid, a head and forward call: of the
                            # live tiles, those masked whole and those walked in squares.
                            line["bd_loss_rows"] = int(sum(float(r) for r in window_bd_rows))
                            window_bd_rows = []
                            for term in ("bd_tiles_live", "bd_tiles_grid", "bd_tiles_masked",
                                         "bd_tiles_narrow"):
                                line[term] = int(metrics[term])
                        if int(metrics["nonfinite"]):
                            self.logger.log(f"WARNING: non-finite loss at step {step}")
                            self._m_nonfinite.inc()
                        self.logger.log_metrics(step, line)
                        if self.stats_client is not None:
                            self.stats_client.log_metrics(step, line)
                        # Registry + event log: the durable counters Prometheus
                        # exports and replay_into rebuilds must move in lockstep
                        # with the step_window events.
                        self._m_steps.inc(window_steps)
                        self._m_toks.inc(window_tokens)
                        self._g_step.set(step)
                        self._g_loss.set(loss)
                        self._g_tok_s.set(tok_s)
                        if mfu_val is not None:
                            self._g_mfu.set(mfu_val)
                        for comp, secs in gp.items():
                            if secs > 0:
                                self._m_goodput.inc(secs, component=comp)
                        compiled = compiles.totals()
                        built = self._note_compiles() if compiled != self._compiles_seen else {}
                        if self.events is not None:
                            ev = dict(
                                step=step, steps=window_steps,
                                toks=int(window_tokens), loss=round(loss, 6),
                                tok_s=round(tok_s, 2), mfu=mfu_val,
                                goodput={k: round(v, 6) for k, v in gp.items()})
                            ev["xla_compiles"] = compiled[0] - self._compiles_seen[0]
                            ev["xla_compile_s"] = round(
                                compiled[1] - self._compiles_seen[1], 4)
                            if built:
                                ev["xla_compiled"] = built
                            if self._plans is not None:
                                ev.update(self._plans)
                                self._plans = None
                            if self.pipeline:
                                ev["bubble"] = round(self._bubble_frac, 6)
                            ev.update({k: line[k] for k in (
                                "moe_rows_held", "moe_chunks_whole", "moe_load_max_over_mean",
                                "moe_drop", "main_loss", "mtp_loss", "bd_loss_rows",
                                "bd_tiles_live", "bd_tiles_grid", "bd_tiles_masked",
                                "bd_tiles_narrow") if k in line})
                            seen = hoststats.window_totals()
                            ev.update(hoststats.window_fields(self._host_seen, seen))
                            self._host_seen = seen
                        self._compiles_seen = compiled
                        # Appended when the window's last step closes (below,
                        # or at the next top of the loop), with its steps' records.
                        self._pending_window = ev if self.events is not None else {}
                        if self.tracer.enabled:
                            self.tracer.instant(
                                "step_window", step=step, tok_s=round(tok_s, 2),
                                mfu=(mfu_val if mfu_val is not None
                                     else "unknown"))
                        self._touch_heartbeat(step)
                        window_tokens = 0
                        window_steps = 0
                        window_start = time.perf_counter()
                    self._steps.note(log_window_s=round(ph.seconds, 6))

                saved_this_step = bool(ckpt_int and step % ckpt_int == 0)
                if saved_this_step or self._preempted or (val_int and step % val_int == 0):
                    # Work beside the step is in no step's record: the step
                    # ends here, and its window's event is written before the
                    # evaluation's and the checkpoint's.
                    self._step_closed(self._steps.close(compiles.totals()[0], self._side_s))

                if val_int and step % val_int == 0:
                    v = self.validate()
                    if v is not None:
                        self.logger.log_validation(step, v)
                        self.val_history["steps"].append(step)
                        self.val_history["losses"].append(v)
                        if self.early_stopping.update(v):
                            self.logger.log(f"Early stopping triggered at step {step}")
                            stopped_early = True

                if cfg.logging.log_samples and val_int and step % val_int == 0:
                    self.generate_samples(step)

                if saved_this_step:
                    # Interval saves overlap the disk write with training;
                    # final/preemption saves below stay blocking.
                    self.save_checkpoint(
                        step, blocking=not cfg.system.async_checkpointing)

                if self._preempted:
                    self.logger.log(
                        f"Preemption signal received: saving checkpoint at step {step} and exiting"
                    )
                    if not saved_this_step:
                        from ..checkpoint.manager import StaleBackgroundWriteError

                        try:
                            self.save_checkpoint(step)
                        except StaleBackgroundWriteError as e:
                            # Exactly this error means the preemption state
                            # IS on disk and only an EARLIER async write had
                            # failed — log it and exit cleanly. Any other
                            # failure (e.g. the gather itself) propagates.
                            self.logger.log(f"Preemption checkpoint: {e}")
                    break

                if stopped_early:
                    break

        finally:
            # Stop the device-prefetch worker first (fast; discards queued
            # not-yet-consumed batches — the consumed-position snapshot the
            # final checkpoint needs is retained on the prefetcher object).
            if self.prefetcher is not None:
                self.prefetcher.stop()
            if self._pending_window is not None:  # the last window's event waits for it
                self._step_closed(self._steps.close(compiles.totals()[0], self._side_s))
            # Drain pending async checkpoint writes even when an exception
            # escapes the loop — the interpreter would otherwise kill the
            # daemon writer mid-file (temp+rename makes that safe for the
            # file; draining makes the checkpoint actually exist).
            try:
                self.checkpoints.wait()
            except RuntimeError as e:
                self.logger.log(str(e))
            if self.profiler.active:
                # Run ended inside a capture window: the trace is still
                # worth attributing (gauges + summary survive the run).
                self._apply_profile_report(
                    self.profiler.stop(), int(self.state["step"]))
            # Persist spans (run-long tracing, or an on-demand window cut
            # short by run end) next to the run's logs.
            if self.tracer.enabled and self.tracer.stats()["recorded"]:
                try:
                    idx = jax.process_index()
                    self.tracer.export(os.path.join(
                        self.run_dir,
                        "trace.json" if idx == 0 else f"trace_p{idx}.json"))
                except OSError as e:
                    self.logger.log(f"trace export failed: {e}")
            if prev_handlers:
                import signal as _signal

                for sig, h in prev_handlers.items():
                    _signal.signal(sig, h)

        step = int(self.state["step"])
        if self.val_history["steps"] and self.val_history["steps"][-1] == step:
            final_val = self.val_history["losses"][-1]  # just validated at this step
        else:
            final_val = self.validate()
            if final_val is not None:
                self.logger.log_validation(step, final_val)
                self.val_history["steps"].append(step)
                self.val_history["losses"].append(final_val)
        self.save_checkpoint("final")  # blocking: drains pending async writes first
        if hasattr(self.data, "stop"):
            self.data.stop()  # streaming sources run a prefetch thread
        if self.stats_client is not None:
            self.stats_client.close()
        if self.events is not None:
            self.events.append(
                "run_end", step=step, total_tokens=int(self.total_tokens),
                final_loss=last_loss, goodput_totals={
                    k: round(v, 4) for k, v in self.goodput.totals().items()})
            self.events.close()
            self.events = None
        # The metrics server (if any) intentionally stays up: a daemon
        # thread serving the final counter snapshot for late scrapes.
        self.logger.log("Training complete")
        self.logger.close()
        return {"final_loss": last_loss, "final_val_loss": final_val, "steps": step}


def _device_batch(batch: Dict[str, np.ndarray]) -> Dict[str, jnp.ndarray]:
    """Synchronous H2D for the cold paths (validation, LR finder). The
    train step loop never calls this — it consumes pre-sharded batches
    from DevicePrefetcher (data/device_prefetch.py)."""
    return {k: jnp.asarray(v) for k, v in batch.items()}


def load_trained(run_name_or_dir: str, runs_root: str = "runs", mesh=None,
                 weight_dtype: str = "fp"):
    """Load a finished run for inference: (params, args, tokenizer, config).
    Mirrors ``Trainer(for_training=False)`` + final-checkpoint load
    (reference: core/generation.py:33-43).

    With ``mesh`` (a serving mesh from ``parallel.build_serve_mesh``) the
    params reshard on load: checkpoints are mesh-agnostic on disk, and each
    leaf is placed straight into the serving mesh's ``NamedSharding`` per
    the training sharding rules — whatever mesh shape trained it, with no
    full-replica materialization (see CheckpointManager.shard_arrays).

    ``weight_dtype`` "int8"/"int4" quantizes the linear weights at the load
    boundary (models/quantize.py; the fp file on disk stays canonical). On
    the mesh path each device quantizes only its own slice, so a quantized
    serving replica never holds an fp copy of a quantized weight."""
    run_dir = run_name_or_dir if os.path.isdir(run_name_or_dir) else os.path.join(runs_root, run_name_or_dir)
    cfg = Config.from_yaml(os.path.join(run_dir, "config.yaml"))
    tok = TokenizerManager.from_run_dir(run_dir)
    ref = resolve_architecture(cfg.model.architecture)
    args = ref.args_cls.from_config(cfg.model, tok.vocab_size)
    ckpts = CheckpointManager(run_dir)
    # Verified resolution: never serve a torn checkpoint (falling back to
    # unverified pre-manifest steps only). Read-only scan: this path may
    # run concurrently with an active trainer on the same run dir, so it
    # must never quarantine (move) files out from under the trainer's
    # resume/GC logic.
    tag = ckpts.latest_complete_step(quarantine=False)
    if tag is None:
        raise FileNotFoundError(f"no verified checkpoints in {run_dir}")
    model_path, _, _ = ckpts.paths_for_step(tag)
    from ..models.quantize import check_weight_dtype, quantize_weights

    wd = check_weight_dtype(weight_dtype)
    params0 = jax.eval_shape(lambda: ref.init_params(jax.random.PRNGKey(0), args))
    if wd != "fp":
        # Restructure against the QUANTIZED shape tree — the loaded arrays
        # carry weight_q/weight_q4/weight_s leaves, not fp weights.
        params0 = jax.eval_shape(lambda p: quantize_weights(p, wd), params0)
    from ..checkpoint.manager import _quantize_flat_np
    from ..checkpoint.safetensors_io import load_safetensors
    from ..utils.tree import unflatten_dict

    arrays, _ = load_safetensors(model_path)
    if mesh is not None:
        nested = unflatten_dict(
            CheckpointManager.shard_arrays(arrays, mesh, weight_dtype=wd))
    else:
        if wd != "fp":
            arrays = _quantize_flat_np(arrays, wd)
        nested = unflatten_dict({k: jnp.asarray(v) for k, v in arrays.items()})
    params = _restructure(params0, nested)
    return params, args, tok, cfg


def _restructure(like, nested):
    if isinstance(like, dict):
        return {k: _restructure(v, nested[k]) for k, v in like.items()}
    if isinstance(like, (list, tuple)):
        vals = [_restructure(v, nested[str(i)]) for i, v in enumerate(like)]
        return vals if isinstance(like, list) else type(like)(vals)
    return nested


def collect_overrides(args) -> Dict[str, Any]:
    """Dotted-path overrides from parsed CLI args (shared with the
    auto-resume supervisor, which must resolve the run name the same way)."""
    overrides: Dict[str, Any] = {}
    for kv in args.set:
        key, _, value = kv.partition("=")
        try:
            value = json.loads(value)
        except json.JSONDecodeError:
            pass
        overrides[key] = value
    if args.iters is not None:
        overrides["training.hyperparameters.iters"] = args.iters
    if args.batch_size is not None:
        overrides["training.hyperparameters.batch_size"] = args.batch_size
    if args.learning_rate is not None:
        overrides["training.hyperparameters.learning_rate"] = args.learning_rate
    if args.run_name:
        overrides["name"] = args.run_name
    return overrides


def config_from_args(args) -> Config:
    """The run's Config: the YAML file with the CLI's dotted overrides
    applied in memory (reference: core/training.py:1907-2013 materializes
    a temp YAML instead)."""
    import yaml

    with open(args.config) as f:
        raw = yaml.safe_load(f)
    return Config.from_dict(apply_overrides(raw, collect_overrides(args)))


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(description="TPU-native LLM pretraining")
    parser.add_argument("--config", required=True)
    parser.add_argument("--runs-root", default="runs")
    parser.add_argument("--set", action="append", default=[], metavar="KEY=VALUE",
                        help="dotted config override, e.g. training.hyperparameters.batch_size=8")
    parser.add_argument("--iters", type=int, default=None)
    parser.add_argument("--batch-size", type=int, default=None)
    parser.add_argument("--learning-rate", type=float, default=None)
    parser.add_argument("--run-name", default=None)
    # Auto-resume supervision (train/supervisor.py): run the trainer in a
    # restarted subprocess instead of this process.
    parser.add_argument("--auto-resume", action="store_true",
                        help="supervise training in a subprocess; on crash/"
                             "preemption, restart it from the newest VERIFIED "
                             "checkpoint with exponential backoff")
    parser.add_argument("--max-crashes", type=int, default=3,
                        help="give up after this many consecutive crashes "
                             "without checkpoint progress (with --auto-resume)")
    parser.add_argument("--backoff-base", type=float, default=2.0,
                        help="first restart delay in seconds (doubles per "
                             "no-progress crash; with --auto-resume)")
    parser.add_argument("--backoff-max", type=float, default=60.0,
                        help="restart delay ceiling in seconds (with --auto-resume)")
    parser.add_argument("--hang-timeout-s", type=float, default=None,
                        help="with --auto-resume: SIGTERM-and-restart the "
                             "trainer when its heartbeat makes no progress "
                             "for this many seconds (overrides "
                             "supervisor.hang_timeout_s; 0 disables)")
    # graftscope sidecar (obs/scope.py): with --auto-resume, the
    # supervisor runs a collector that scrapes the trainer's /metrics
    # port, evaluates the alert rules, and captures evidence on fire.
    parser.add_argument("--scope", action="store_true",
                        help="with --auto-resume: start a graftscope "
                             "collector sidecar scraping the trainer's "
                             "metrics port (requires logging.metrics_port)")
    parser.add_argument("--alerts-config", default=None,
                        help="alerts.yaml for the --scope sidecar "
                             "(default: configs/alerts.yaml when present)")
    # Multi-host rendezvous (parallel/elastic.py). With --auto-resume these
    # configure the multi-host supervisor instead: each host runs one
    # supervisor, children rendezvous per generation.
    parser.add_argument("--coordinator", default=None,
                        help="host:port of process 0 for the "
                             "jax.distributed rendezvous (also "
                             "JAX_COORDINATOR_ADDRESS / config "
                             "system.distributed.coordinator_address)")
    parser.add_argument("--num-processes", type=int, default=None)
    parser.add_argument("--process-id", type=int, default=None)
    parser.add_argument("--rendezvous-timeout-s", type=float, default=None,
                        help="overall rendezvous deadline; retries with "
                             "backoff inside it (default 120, or config "
                             "system.distributed.rendezvous_timeout_s)")
    parser.add_argument("--barrier-timeout-s", type=float, default=None,
                        help="with --auto-resume on a multi-host world: how "
                             "long each host's supervisor waits for peers "
                             "at a generation barrier (overrides "
                             "supervisor.barrier_timeout_s)")
    return parser


def main(argv=None) -> Dict[str, Any]:
    """CLI: ``python -m mlx_cuda_distributed_pretraining_tpu.train --config C``
    with dotted overrides (:func:`config_from_args`)."""
    args = build_parser().parse_args(argv)

    if args.auto_resume:
        from .supervisor import supervise_from_args

        return supervise_from_args(args)

    cfg = config_from_args(args)
    # Multi-host rendezvous BEFORE the Trainer touches any device state.
    # Explicitly configured coordination fails loudly (RendezvousError) —
    # never N solo runs clobbering one run dir.
    coordinator = (args.coordinator
                   or os.environ.get("JAX_COORDINATOR_ADDRESS")
                   or cfg.system.distributed_coordinator)
    if coordinator:
        from ..parallel.launch import initialize_distributed

        timeout = (args.rendezvous_timeout_s
                   if args.rendezvous_timeout_s is not None
                   else cfg.system.distributed_rendezvous_timeout_s)
        initialize_distributed(
            coordinator,
            (args.num_processes if args.num_processes is not None
             else cfg.system.distributed_num_processes),
            args.process_id,
            rendezvous_timeout_s=timeout,
        )
    # Before the first compile (Trainer.__init__ jits the param init), and
    # after the rendezvous, which decides whether this is multi-process CPU.
    cache_line = enable_compilation_cache()
    trainer = Trainer(cfg, runs_root=args.runs_root)
    trainer.logger.log(cache_line)
    return trainer.train()


if __name__ == "__main__":
    main()
