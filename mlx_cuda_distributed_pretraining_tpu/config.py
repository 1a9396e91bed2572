"""YAML config schema.

Mirrors the reference's config surface (reference: core/training.py:52-167)
so that its 58 config YAMLs port nearly verbatim: top-level sections
``data / model / training / logging / system / resume`` plus ``name`` and
``overwrite``. TPU-specific additions live under ``system.mesh`` (device mesh
axis sizes) and ``model.attention.attention_type``.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import yaml


def _get(d: Optional[Dict[str, Any]], key: str, default: Any = None) -> Any:
    if d is None:
        return default
    v = d.get(key, default)
    return default if v is None else v


@dataclass
class DataConfig:
    """Section ``data`` (reference: core/training.py:53-60)."""

    input_file: Optional[str] = None
    preprocessing: Dict[str, Any] = field(default_factory=dict)
    tokenizer: Dict[str, Any] = field(default_factory=dict)
    tokenizer_path: Optional[str] = None
    validation_file: Optional[str] = None
    weight_path: Optional[str] = None
    # TPU additions: streaming sources ("jsonl" | "hf_stream" | "synthetic")
    source: str = "jsonl"
    streaming: Dict[str, Any] = field(default_factory=dict)
    # Device-resident batches kept ahead of the step loop by
    # data/device_prefetch.py (H2D transfer overlaps compute). 0 = fetch
    # and transfer synchronously inside the loop. Distinct from the
    # streaming HOST prefetch queue (streaming.prefetch).
    prefetch_depth: int = 2

    @property
    def max_context_size(self) -> int:
        return int(_get(self.preprocessing, "max_context_size", 1024))

    @property
    def chunk_overlap(self) -> int:
        return int(_get(self.preprocessing, "chunk_overlap", 0))


@dataclass
class ModelConfig:
    """Section ``model`` (reference: core/training.py:62-68)."""

    architecture: str = "llama"
    dimensions: Dict[str, Any] = field(default_factory=dict)
    attention: Dict[str, Any] = field(default_factory=dict)
    normalization: Dict[str, Any] = field(default_factory=dict)
    rope: Dict[str, Any] = field(default_factory=dict)
    misc: Dict[str, Any] = field(default_factory=dict)
    moe: Dict[str, Any] = field(default_factory=dict)
    # Sections only architecture "xing_mla_moe" reads (models/xing.py):
    # latent attention's ranks and head sizes, the residual streams'
    # hyperparameters, the multi-token-prediction module.
    mla: Dict[str, Any] = field(default_factory=dict)
    hyper_connections: Dict[str, Any] = field(default_factory=dict)
    mtp: Dict[str, Any] = field(default_factory=dict)
    # Only architecture "sambay" reads it (models/sambay.py): the Mamba
    # layers' d_state, d_conv, expand, dt_rank.
    ssm: Dict[str, Any] = field(default_factory=dict)
    # Only architecture "sdar_moe" reads it (models/sdar.py): the noise of its
    # block-diffusion objective (block_length, eps, mask_id), which the
    # trainer's loader then draws (data/block_diffusion.py).
    diffusion: Dict[str, Any] = field(default_factory=dict)
    # Architectures "kimi_linear" and "solar_open2" read it: the published
    # linear_attn_config (the delta-rule heads' num_heads, head_dim,
    # short_conv_kernel_size; kimi_linear's kda_layers and full_attn_layers,
    # 1-based; solar_open2's kda_allow_neg_eigval and kda_use_full_proj).
    linear_attn: Dict[str, Any] = field(default_factory=dict)
    # Named rematerialization policy: "none" | "dots" | "full" |
    # "save_attn" (models/stack.py REMAT_POLICIES — save_attn keeps the
    # checkpoint_name-tagged attention activations and replays only the
    # cheap FFN elementwise work). Takes precedence over the legacy
    # system.remat / system.gradient_checkpointing knobs when set.
    remat_policy: Optional[str] = None
    # Opt-in low-precision training matmuls: None/"fp32" | "bf16" |
    # "int8" (ops/flash_attention.py MATMUL_PRECISIONS). int8 tracks
    # per-row/per-channel amax scales on the forward matmuls and keeps
    # the backward pass in fp; loss-parity is gated vs bf16 in tests.
    matmul_precision: Optional[str] = None

    def __post_init__(self):
        if self.remat_policy is not None:
            norm = str(self.remat_policy).lower()
            valid = ("none", "dots", "full", "save_attn")
            if norm not in valid:
                raise ValueError(
                    f"unknown model.remat_policy: {self.remat_policy!r} "
                    f"(expected one of {valid})")
            object.__setattr__(self, "remat_policy", norm)
        if self.matmul_precision is not None:
            norm = str(self.matmul_precision).lower()
            if norm in ("", "none", "fp", "fp32"):
                norm = None
            elif norm not in ("bf16", "int8"):
                raise ValueError(
                    f"unknown model.matmul_precision: {self.matmul_precision!r} "
                    f"(expected one of (None, 'fp32', 'bf16', 'int8'))")
            object.__setattr__(self, "matmul_precision", norm)

    @property
    def hidden_size(self) -> int:
        return int(_get(self.dimensions, "hidden_size", 128))

    @property
    def intermediate_size(self) -> int:
        return int(_get(self.dimensions, "intermediate_size", 4 * self.hidden_size))

    @property
    def num_layers(self) -> int:
        return int(_get(self.dimensions, "num_layers", 4))

    @property
    def num_heads(self) -> int:
        return int(_get(self.attention, "num_heads", 8))

    @property
    def num_kv_heads(self) -> int:
        return int(_get(self.attention, "num_kv_heads", self.num_heads))

    @property
    def head_dim(self) -> int:
        return int(_get(self.attention, "head_dim", self.hidden_size // self.num_heads))

    @property
    def attention_type(self) -> str:
        """"simple" | "flash" | "flex" | "ring" — dispatch mirrors reference
        models/llama.py:181-209 (flex > flash > simple); "ring" (sequence
        parallel over the sp mesh axis) is a TPU addition."""
        if _get(self.attention, "use_ring_attention", False):
            return "ring"
        if _get(self.attention, "use_flex_attention", False):
            return "flex"
        if _get(self.attention, "use_flash_attention", False):
            return "flash"
        return str(_get(self.attention, "attention_type", "simple"))


@dataclass
class TrainingConfig:
    """Section ``training`` (reference: core/training.py:70-89)."""

    hyperparameters: Dict[str, Any] = field(default_factory=dict)
    scheduler: Dict[str, Any] = field(default_factory=dict)
    optimization: Dict[str, Any] = field(default_factory=dict)
    epochs: Optional[int] = None
    early_stopping: Dict[str, Any] = field(
        default_factory=lambda: {
            "enabled": False,
            "patience": 3,
            "min_delta": 0.001,
            "metric": "val_loss",
            "mode": "min",
        }
    )
    lr_finder: Dict[str, Any] = field(
        default_factory=lambda: {
            "enabled": False,
            "min_lr": 1e-7,
            "max_lr": 1.0,
            "num_steps": 100,
        }
    )

    @property
    def batch_size(self) -> int:
        return int(_get(self.hyperparameters, "batch_size", 16))

    @property
    def learning_rate(self) -> float:
        return float(_get(self.hyperparameters, "learning_rate", 3e-4))

    @property
    def weight_decay(self) -> float:
        return float(_get(self.hyperparameters, "weight_decay", 0.0))

    @property
    def iters(self) -> Optional[int]:
        v = _get(self.hyperparameters, "iters", None)
        return None if v is None else int(v)

    @property
    def gradient_clip(self) -> Optional[float]:
        v = _get(self.hyperparameters, "gradient_clip", None)
        return None if v is None else float(v)

    @property
    def gradient_accumulation_steps(self) -> int:
        return int(_get(self.hyperparameters, "gradient_accumulation_steps", 1))

    @property
    def optimizer_name(self) -> str:
        return str(_get(self.optimization, "optimizer", "adamw")).lower()


@dataclass
class LoggingConfig:
    """Section ``logging`` (reference: core/training.py:91-106)."""

    log_dir: str = "logs"
    checkpoint_dir: str = "checkpoints"
    steps: Dict[str, Any] = field(default_factory=dict)
    metrics: Dict[str, Any] = field(default_factory=dict)
    tensorboard: bool = False
    wandb: bool = False
    wandb_project: Optional[str] = None
    wandb_entity: Optional[str] = None
    log_memory_usage: bool = False
    log_gradient_norm: bool = False
    log_parameter_norm: bool = False
    log_samples: bool = False
    log_samples_count: int = 3
    # Capture a jax.profiler trace for steps [profile_start, profile_stop)
    # into <run_dir>/profile/ (the reference has no profiler; SURVEY.md §5
    # tracing plan).
    profile_start: int = 0
    profile_stop: int = 0
    # Checkpoint retention: after each successful (manifested) save, delete
    # interval checkpoints beyond the newest keep_last, except steps
    # divisible by keep_every, the resume-source step, and "final".
    # keep_last: 0 disables GC (keep everything).
    retention: Dict[str, Any] = field(default_factory=dict)
    # Prometheus text exposition of the in-process metrics registry
    # (obs/prometheus.py) on this port; 0 disables. Every process serves:
    # process i binds metrics_port + i and stamps process_index into the
    # exposition, so multi-host scrapes stay disambiguated.
    metrics_port: int = 0
    # Span tracer (obs/trace.py): {enabled: bool, sample: float,
    # capacity: int, capture_steps: int}. capture_steps sizes the
    # SIGUSR2 on-demand window (spans + jax.profiler for the next N
    # steps without restarting the run).
    trace: Dict[str, Any] = field(default_factory=dict)
    # graftprof auto-attribution (obs/profile_report.py) whenever a
    # jax.profiler capture stops: {enabled: bool, top_k: int}. enabled
    # defaults on — a captured trace that nobody attributes is the
    # status quo this knob exists to end; top_k sizes the op table.
    profile_report: Dict[str, Any] = field(default_factory=dict)
    # events.jsonl policy: {max_bytes: int}. max_bytes > 0 rotates the
    # live log to events.1.jsonl when it would exceed the cap
    # (obs/events.py EventLog); 0 keeps the legacy unbounded file.
    events: Dict[str, Any] = field(default_factory=dict)

    @property
    def events_max_bytes(self) -> int:
        return int(_get(self.events, "max_bytes", 0))

    @property
    def logging_interval(self) -> int:
        return int(_get(self.steps, "logging_interval", 1))

    @property
    def checkpoint_interval(self) -> int:
        return int(_get(self.steps, "checkpoint_interval", 1000))

    @property
    def validation_interval(self) -> int:
        return int(_get(self.steps, "validation_interval", 0))

    @property
    def stats_url(self) -> Optional[str]:
        """WebSocket URL of a stats hub (obs/stats_server.py); metrics are
        published there each logging interval when set."""
        url = _get(self.metrics, "stats_url", None)
        return str(url) if url else None

    @property
    def keep_last(self) -> int:
        return int(_get(self.retention, "keep_last", 0))

    @property
    def keep_every(self) -> int:
        return int(_get(self.retention, "keep_every", 0))

    @property
    def profile_report_enabled(self) -> bool:
        return bool(_get(self.profile_report, "enabled", True))

    @property
    def profile_report_top_k(self) -> int:
        return int(_get(self.profile_report, "top_k", 12))


@dataclass
class SystemConfig:
    """Section ``system`` (reference: core/training.py:108-122).

    ``device`` is a label the reference's configs carry; nothing reads it.
    The run executes on whatever JAX finds, and the trainer stamps that
    (platform, device_kind, count) into the first log line and the
    ``run_start`` event. ``devices/cuda_devices`` are likewise
    accepted for config compatibility but the
    execution model is SPMD over ``mesh`` — there is no thread-queue
    device manager to configure.

    ``distributed`` accepts the legacy boolean (compatibility, ignored) or
    a mapping configuring the multi-host rendezvous
    (parallel/elastic.py)::

        distributed:
          coordinator_address: host:port   # of process 0; null = auto-detect
          num_processes: 2
          rendezvous_timeout_s: 120
    """

    seed: int = 42
    device: str = "tpu"
    distributed: Any = False
    devices: Optional[List[str]] = None
    cuda_devices: Optional[List[int]] = None
    memory_limit: Optional[int] = None
    mixed_precision: bool = False
    precision: str = "bfloat16"
    gradient_checkpointing: bool = False
    gradient_checkpointing_ratio: float = 0.5
    model_parallel: bool = False
    model_parallel_size: int = 1
    zero_optimization_level: int = 0
    # TPU-native: named mesh axis sizes, e.g. {dp: 4, tp: 2, sp: 1}.
    # -1 on the dp axis means "all remaining devices".
    mesh: Dict[str, int] = field(default_factory=dict)
    # Ring/blockwise sequence parallelism (context parallel) over the sp axis.
    sequence_parallel: bool = False
    # Rematerialization policy: "none" | "full" | "dots" (overrides
    # gradient_checkpointing when set).
    remat: Optional[str] = None
    # Pipeline parallelism (pp mesh axis): microbatches per step. 0 means
    # 2 * pp-size (keeps the GPipe bubble fraction under 1/3).
    pipeline_microbatches: int = 0
    # Interleaved virtual stages (Megatron-style): each device owns V
    # round-robin chunks of num_layers/(pp*V) layers and activations make
    # V circuits of the ring, shrinking the warmup/drain bubble from P-1
    # to (P-1)/V slab-times. V > 1 requires pipeline_microbatches >= pp.
    # 1 = classic GPipe (bit-identical to the pre-interleave schedule).
    pipeline_interleave: int = 1
    # Skip slab compute (and the stage-0 embed gather) on non-working
    # warmup/drain ticks via lax.cond: per-step slab applications drop
    # from P*(V*M+P-1) to exactly P*V*M, forward and backward. False
    # reproduces the original every-tick schedule bit-identically — only
    # useful for apples-to-apples comparisons.
    pipeline_compute_skip: bool = True
    # Fused chunked cross-entropy (ops/fused_ce.py): rows per chunk.
    # 0 = always materialize full logits; -1 = auto (enable when the
    # [B, S, V] logits tensor would be HBM-significant); >0 = fixed chunk.
    fused_ce_chunk: int = -1
    # Compute dtype. None derives it from mixed_precision; an explicit value
    # is validated and normalized (float16 maps to bfloat16: TPUs have
    # native bf16 MXU support and no fp16 fast path).
    compute_dtype: Optional[str] = None
    # Interval checkpoints hand the disk write to a background thread so
    # the train loop keeps stepping (final/preemption saves stay blocking).
    async_checkpointing: bool = True
    # Run the uniform layer stack as lax.scan bodies over in-jit-stacked
    # params (models/llama.py::forward): XLA compiles ONE layer (two with
    # a partial remat_ratio) instead of num_layers copies — a large
    # compile-time saver at 400M-1B. Training path only; under
    # pipeline parallelism pp stacks layers itself.
    scan_layers: bool = False
    # XLA scheduling flags (parallel/xla_flags.py)::
    #
    #   xla:
    #     flag_set: latency_hiding   # or "none"
    #     extra_flags: ["--xla_..."]  # appended verbatim
    #
    # The named set resolves per backend (CPU resolves empty — XLA:CPU
    # has no latency-hiding scheduler), is applied before the backend
    # initializes, and is stamped into events.jsonl.
    xla: Dict[str, Any] = field(default_factory=dict)
    # Manual comm/compute overlap (parallel/overlap.py): under a pure
    # dp×fsdp mesh with scan_layers, all-gather the NEXT layer's
    # fsdp-sharded params (one bucketed gather per layer) while the
    # current layer's matmuls run, double-buffered through the layer
    # scan; the gather's transpose drains the gradient reduce-scatter
    # per layer behind the backward pass instead of as one monolithic
    # sync at the end. Falls back to the GSPMD path when the mesh or
    # model shape doesn't qualify (tp/sp/ep/pp > 1, MoE, int8 leaves).
    overlap_gather: bool = False

    def __post_init__(self):
        if self.compute_dtype is None:
            self.compute_dtype = "bfloat16" if self.mixed_precision else "float32"
        else:
            norm = str(self.compute_dtype).lower()
            if norm in ("bfloat16", "bf16", "float16", "fp16", "half"):
                self.compute_dtype = "bfloat16"
            elif norm in ("float32", "fp32", "float"):
                self.compute_dtype = "float32"
            else:
                raise ValueError(
                    f"unknown system.compute_dtype: {self.compute_dtype!r} "
                    "(expected bfloat16/float16/float32)")

    @property
    def xla_flag_set(self) -> str:
        v = self.xla.get("flag_set") if isinstance(self.xla, dict) else None
        return str(v).lower() if v else "none"

    @property
    def xla_extra_flags(self) -> List[str]:
        v = self.xla.get("extra_flags") if isinstance(self.xla, dict) else None
        return [str(f) for f in v] if v else []

    def _distributed_map(self) -> Dict[str, Any]:
        return self.distributed if isinstance(self.distributed, dict) else {}

    @property
    def distributed_coordinator(self) -> Optional[str]:
        v = self._distributed_map().get("coordinator_address")
        return str(v) if v else None

    @property
    def distributed_num_processes(self) -> Optional[int]:
        v = self._distributed_map().get("num_processes")
        return int(v) if v is not None else None

    @property
    def distributed_rendezvous_timeout_s(self) -> float:
        v = self._distributed_map().get("rendezvous_timeout_s")
        return float(v) if v is not None else 120.0


@dataclass
class SupervisorConfig:
    """Section ``supervisor`` (TPU addition, no reference counterpart).

    Knobs for the auto-resume supervisor (train/supervisor.py). The hang
    watchdog fires when the trainer's heartbeat file (written every step
    window) goes stale for ``hang_timeout_s`` seconds: the child is
    SIGTERMed (then SIGKILLed after ``hang_kill_grace_s``) and restarted
    from the newest verified checkpoint, with the lost wall clock booked
    into the goodput ledger via a ``restart`` event. 0 disables the
    watchdog.

    ``barrier_timeout_s`` bounds the multi-host generation barrier
    (parallel/elastic.py): how long one host's supervisor waits for its
    peers before every fleet (re)launch — on timeout it fails loudly
    rather than hanging forever on a dead peer."""

    hang_timeout_s: float = 0.0
    hang_kill_grace_s: float = 20.0
    barrier_timeout_s: float = 300.0


@dataclass
class ResumeConfig:
    """Section ``resume`` (reference: core/training.py:124-127).

    ``strict`` (TPU addition): fail hard on ANY checkpoint integrity
    problem (failed manifest verification, missing/unreadable optimizer
    state) instead of warning and falling back to an older checkpoint or
    a fresh optimizer."""

    checkpoint: str = ""
    reset_optimizer: bool = False
    reset_training_state: bool = False
    strict: bool = False


_SECTION_TYPES = {
    "data": DataConfig,
    "model": ModelConfig,
    "training": TrainingConfig,
    "logging": LoggingConfig,
    "system": SystemConfig,
    "supervisor": SupervisorConfig,
}


def _validate_pipeline_config(cfg: "Config") -> None:
    """Cross-section pipeline checks at config-load time.

    An invalid microbatch or layer count would otherwise surface as an
    opaque ``reshape`` tracer error deep inside ``make_pipeline_loss``;
    failing here names the config keys instead.
    """
    sysc = cfg.system
    pp = int((sysc.mesh or {}).get("pp", 1) or 1)
    V = getattr(sysc, "pipeline_interleave", 1)
    V = 1 if V is None else int(V)
    M = int(getattr(sysc, "pipeline_microbatches", 0) or 0)
    if V < 1:
        raise ValueError(
            f"system.pipeline_interleave must be >= 1, got {V}")
    if M < 0:
        raise ValueError(
            f"system.pipeline_microbatches must be >= 0 (0 = 2*pp), got {M}")
    if pp <= 1:
        return
    m_eff = M or 2 * pp
    bs = int(cfg.training.batch_size)
    if bs % m_eff != 0:
        raise ValueError(
            f"training.batch_size={bs} must be divisible by "
            f"system.pipeline_microbatches={m_eff}"
            f"{'' if M else f' (defaulted to 2*pp={m_eff})'}: each pipeline "
            f"microbatch carries batch_size/pipeline_microbatches rows")
    layers = int(cfg.model.num_layers)
    if layers % (pp * V) != 0:
        raise ValueError(
            f"model.num_layers={layers} must be divisible by "
            f"mesh.pp*pipeline_interleave={pp}*{V}={pp * V}: each of the "
            f"pp*interleave virtual stage chunks owns an equal slab of layers")
    if V > 1 and m_eff < pp:
        raise ValueError(
            f"system.pipeline_interleave={V} requires pipeline_microbatches "
            f">= mesh.pp ({m_eff} < {pp}): circuit v's wrap-around "
            f"activation must leave the ring before stage 0 re-feeds that "
            f"microbatch for circuit v+1")


def _build_section(cls, raw: Optional[Dict[str, Any]]):
    raw = dict(raw or {})
    names = {f.name for f in dataclasses.fields(cls)}
    known = {k: v for k, v in raw.items() if k in names}
    # Unknown keys are preserved rather than rejected so forward-compatible
    # configs load (the reference raises TypeError on unknown keys; we're
    # deliberately more tolerant and stash extras).
    extras = {k: v for k, v in raw.items() if k not in names}
    obj = cls(**known)
    if extras:
        object.__setattr__(obj, "_extras", extras)
    return obj


@dataclass
class Config:
    """Top-level config (reference: core/training.py:129-167)."""

    name: str
    data: DataConfig = field(default_factory=DataConfig)
    model: ModelConfig = field(default_factory=ModelConfig)
    training: TrainingConfig = field(default_factory=TrainingConfig)
    logging: LoggingConfig = field(default_factory=LoggingConfig)
    system: SystemConfig = field(default_factory=SystemConfig)
    supervisor: SupervisorConfig = field(default_factory=SupervisorConfig)
    resume: Optional[ResumeConfig] = None
    overwrite: bool = False

    @classmethod
    def from_dict(cls, config_dict: Dict[str, Any]) -> "Config":
        if "name" not in config_dict:
            raise ValueError("Config must specify a 'name' field at the top level")
        sections = {
            key: _build_section(typ, config_dict.get(key))
            for key, typ in _SECTION_TYPES.items()
        }
        resume = None
        if config_dict.get("resume"):
            resume = _build_section(ResumeConfig, config_dict["resume"])
        cfg = cls(
            name=config_dict["name"],
            overwrite=bool(config_dict.get("overwrite", False)),
            resume=resume,
            **sections,
        )
        _validate_pipeline_config(cfg)
        return cfg

    @classmethod
    def from_yaml(cls, yaml_path: str) -> "Config":
        with open(yaml_path, "r") as f:
            config_dict = yaml.safe_load(f)
        return cls.from_dict(config_dict)

    def to_dict(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {"name": self.name, "overwrite": self.overwrite}
        for key in _SECTION_TYPES:
            section = getattr(self, key)
            d = dataclasses.asdict(section)
            d.update(getattr(section, "_extras", {}))
            out[key] = d
        if self.resume is not None:
            out["resume"] = dataclasses.asdict(self.resume)
        return out

    def to_yaml(self, path: str) -> None:
        with open(path, "w") as f:
            yaml.safe_dump(self.to_dict(), f, sort_keys=False)


def apply_overrides(config_dict: Dict[str, Any], overrides: Dict[str, Any]) -> Dict[str, Any]:
    """Apply dotted-path overrides, e.g. ``{"training.hyperparameters.batch_size": 8}``.

    Mirrors the reference's CLI-override mechanism (reference:
    core/training.py:1941-2006, hybrid_distributed.py:802-814) without the
    temp-YAML indirection.
    """
    out = dict(config_dict)
    for path, value in overrides.items():
        parts = path.split(".")
        node = out
        for p in parts[:-1]:
            nxt = node.get(p)
            if not isinstance(nxt, dict):
                nxt = {}
            node[p] = dict(nxt)
            node = node[p]
        node[parts[-1]] = value
    return out
