"""The continuous-batching engine thread.

``BatchEngine`` owns the model params, the slotted KV pool and the
scheduler, and runs one iteration loop on a background thread:

    evict expired -> admit queued -> one prefill chunk -> one batched
    decode step (all occupied slots advance one token) -> metrics

Requests join and leave the batch at iteration granularity (Orca-style
continuous batching): a finishing request frees its slot this iteration
and a queued one takes it the next, so occupancy tracks offered load
instead of draining batch-by-batch.

The HTTP front end (infer/server.py, ``--engine batch``) submits
requests and blocks on per-request waiters; ``QueueFullError`` maps to
429. Per-iteration metrics (occupancy, queue depth, admitted / rejected
/ evicted counts, TTFT, decode tok/s) publish through the existing obs
stats protocol (obs/stats_client.py) so the live dashboard picks them up
unmodified.
"""

from __future__ import annotations

import dataclasses
import queue
import threading
import time
from typing import Any, Dict, List, Optional

import numpy as np
from jax.profiler import StepTraceAnnotation, TraceAnnotation

from . import batch_step, faults
from ..analysis import sync_runtime
from ..obs import compiles
from .kv_pool import PagedKVPool, SlotKVPool
from .scheduler import (
    DECODE,
    DONE,
    PREFILL,
    QueueFullError,
    Request,
    Scheduler,
)

__all__ = ["BatchEngine", "EngineConfig", "QueueFullError"]


@dataclasses.dataclass
class EngineConfig:
    """Pool/queue knobs (configs/serve-sample.yaml documents each)."""

    num_slots: int = 8          # decode batch width = max concurrent requests
    max_len: int = 2048         # per-request KV length bound
    max_queue: int = 32         # admission queue depth; beyond -> 429
    prefill_chunk: int = 256    # prompt tokens written per iteration
    kv_quant: bool = False      # int8 pool buffers (same path as --kv-quant)
    weight_dtype: str = "fp"    # weight-only quant: "fp" | "int8" | "int4"
    #                             (models/quantize.py; embeddings/norms stay fp)
    kv_backend: str = "paged"   # "paged" (block tables) | "slotted" (PR 1)
    block_size: int = 32        # paged: tokens per KV block (power of two)
    num_blocks: int = 0         # paged: KV arena size; 0 = slotted-equivalent
    spec_draft_len: int = 0     # paged: drafts verified per decode step; 0 off
    spec_max_ngram: int = 3     # paged: prompt-lookup suffix n-gram bound
    # Degradation ladder rung 1: below this free-block fraction the next
    # decode step runs without speculation (draft tokens burn arena blocks
    # for speculative positions; under pressure certainty beats speed).
    spec_off_kv_free_frac: float = 0.05
    prefix_cache: bool = True   # paged: content-hash block reuse (off = oracle)
    prefix_min_hit_blocks: int = 1  # shortest cached chain worth adopting
    default_deadline_s: Optional[float] = None  # per-request unless overridden
    trace: bool = False         # span tracer (obs/trace.py); /trace dumps it
    trace_sample: float = 1.0   # fraction of requests traced (by trace id)
    trace_capacity: int = 16384  # span ring-buffer bound (oldest dropped)
    stats_url: Optional[str] = None  # ws://host:port of obs stats server
    stats_interval_s: float = 1.0
    worker_id: str = "serve-engine"
    role: str = "any"           # fleet pool: "prefill" | "decode" | "any"
    metrics_port: int = 0       # Prometheus exposition (obs/prometheus.py); 0 off
    mesh: Optional[Dict[str, int]] = None  # serving mesh axes, e.g. {"tp": 2};
    #                             None/all-ones = single-device (pre-mesh path)

    @classmethod
    def from_yaml(cls, path: str) -> "EngineConfig":
        import yaml

        with open(path) as f:
            doc = yaml.safe_load(f) or {}
        serve = dict(doc.get("serve", doc))
        # Nested prefix_cache block (configs/serve-sample.yaml):
        #   prefix_cache: {enabled: true, min_hit_blocks: 1}
        pc = serve.get("prefix_cache")
        if isinstance(pc, dict):
            serve["prefix_cache"] = bool(pc.get("enabled", True))
            if "min_hit_blocks" in pc:
                serve["prefix_min_hit_blocks"] = int(pc["min_hit_blocks"])
        # Nested trace block: trace: {enabled: true, sample: 0.1, capacity: N}
        tr = serve.get("trace")
        if isinstance(tr, dict):
            serve["trace"] = bool(tr.get("enabled", True))
            if "sample" in tr:
                serve["trace_sample"] = float(tr["sample"])
            if "capacity" in tr:
                serve["trace_capacity"] = int(tr["capacity"])
        # serving: {mesh: {tp: 2}} — the yaml home of the serving mesh
        # (configs/serve-sample.yaml); serve.mesh also accepted. String
        # specs ("tp=2,dp=1") parse like the --mesh CLI flag.
        serving = doc.get("serving")
        if isinstance(serving, dict) and "mesh" in serving:
            serve.setdefault("mesh", serving["mesh"])
        # serving: {weight_dtype: int8} — weight-only quantization knob
        # lives beside the mesh it shards under.
        if isinstance(serving, dict) and "weight_dtype" in serving:
            serve.setdefault("weight_dtype", serving["weight_dtype"])
        if isinstance(serve.get("mesh"), str):
            from ..parallel import parse_mesh_spec

            serve["mesh"] = parse_mesh_spec(serve["mesh"])
        known = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in serve.items() if k in known})


class BatchEngine:
    def __init__(self, params, args, tokenizer,
                 cfg: Optional[EngineConfig] = None, mesh=None):
        self.params = params  # graftsync: owner=engine-thread
        self.args = args
        self.tokenizer = tokenizer
        self.cfg = cfg or EngineConfig()
        if self.cfg.max_len > args.max_position_embeddings:
            raise ValueError(
                f"max_len {self.cfg.max_len} exceeds the model's "
                f"max_position_embeddings {args.max_position_embeddings}")
        # Serving mesh: an explicit Mesh object (e.g. the one the params
        # were reshard-on-loaded into) wins; otherwise build from the
        # config's axis sizes. None = the pre-mesh single-device path with
        # byte-identical jit cache keys.
        if mesh is None and self.cfg.mesh:
            from ..parallel import build_serve_mesh

            mesh = build_serve_mesh(self.cfg.mesh)
        self.mesh = mesh
        if self.mesh is not None:
            self.params = self._place_params(params, self.mesh)
        # Weight-only quantization (models/quantize.py). Params that arrive
        # already quantized (checkpoint/manager.py quantize-on-load — the
        # preferred path: no fp replica ever lands on device) win over the
        # config knob; fp params with weight_dtype set are quantized here.
        from ..models.quantize import (check_weight_dtype, quantize_weights,
                                       weight_dtype_of, weight_plane_bytes)

        wd = check_weight_dtype(self.cfg.weight_dtype)
        have = weight_dtype_of(self.params)
        if have != "fp":
            wd = have
        elif wd != "fp":
            self.params = quantize_weights(self.params, wd)
        self.weight_dtype = wd
        self._weight_bytes = weight_plane_bytes(self.params)
        if self.cfg.kv_backend == "paged":
            self.pool = PagedKVPool(
                args, self.cfg.num_slots, self.cfg.max_len,
                block_size=self.cfg.block_size,
                num_blocks=self.cfg.num_blocks,
                quantize=self.cfg.kv_quant,
                prefix_cache=self.cfg.prefix_cache,
                min_hit_blocks=self.cfg.prefix_min_hit_blocks,
                mesh=self.mesh)
        elif self.cfg.kv_backend == "slotted":
            if self.cfg.spec_draft_len:
                raise ValueError(
                    "spec_draft_len requires kv_backend='paged' (in-batch "
                    "speculation commits through block tables)")
            self.pool = SlotKVPool(args, self.cfg.num_slots, self.cfg.max_len,
                                   quantize=self.cfg.kv_quant, mesh=self.mesh)
        else:
            raise ValueError(f"unknown kv_backend {self.cfg.kv_backend!r} "
                             "(expected 'paged' or 'slotted')")
        self.draft_len = (max(0, int(self.cfg.spec_draft_len))
                          if self.cfg.kv_backend == "paged" else 0)
        self.scheduler = Scheduler(max_queue=self.cfg.max_queue)
        self.scheduler.concurrency = self.cfg.num_slots
        self.chunk = max(1, min(self.cfg.prefill_chunk, self.cfg.max_len))
        self._stop = threading.Event()
        self._wake = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._stats = None
        self.iterations = 0  # graftsync: owner=engine-thread
        # Loop turns that ran a prefill chunk or a decode step: what device
        # time per iteration divides by (``iterations`` also ticks on the
        # idle turns of a waiting engine).
        self.busy_iterations = 0  # graftsync: owner=engine-thread
        # Cross-thread work: the engine thread is the SOLE mutator of pool
        # bookkeeping and self.params, so KV export/adopt and weight swaps
        # enqueue closures here and _iteration drains them between steps.
        self._tasks: "queue.Queue" = queue.Queue()
        # bumps on every applied weight swap
        self.params_version = 0  # graftsync: owner=engine-thread
        # sliding decode-throughput window + last-published snapshot
        self._win_t0 = time.monotonic()  # graftsync: owner=engine-thread
        self._win_tokens = 0  # graftsync: owner=engine-thread
        self._last_publish = 0.0  # graftsync: owner=engine-thread
        self._metrics: Dict[str, Any] = {}  # graftsync: owner=engine-thread
        # Per-request span tracer (obs/trace.py). Disabled is the default
        # and free: span() hands back a shared null span, and every call
        # site additionally guards on `.enabled` so the hot path allocates
        # nothing.
        from ..obs.trace import Tracer

        self.tracer = Tracer(self.cfg.worker_id,
                             capacity=self.cfg.trace_capacity,
                             sample=self.cfg.trace_sample,
                             enabled=self.cfg.trace)
        # Shared metrics substrate (obs/metrics.py): same registry shape as
        # the trainer, so one Prometheus scrape config covers both roles.
        from ..obs.metrics import LATENCY_MS_BUCKETS, MetricsRegistry

        self.metrics_registry = MetricsRegistry()
        reg = self.metrics_registry
        self._mg_occupancy = reg.gauge(
            "serve_batch_occupancy", "occupied decode slots")
        self._mg_queue = reg.gauge("serve_queue_depth", "admission queue depth")
        self._mg_tok_s = reg.gauge("serve_tok_s", "decode tokens/second (window)")
        self._mc_requests = reg.counter(
            "serve_requests_total", "requests by outcome")
        self._mc_iterations = reg.counter(
            "serve_iterations_total", "engine loop iterations")
        self._mc_busy_iterations = reg.counter(
            "serve_busy_iterations_total",
            "engine loop iterations that ran a prefill chunk or a decode step")
        self._mc_compiles = reg.counter(
            "serve_xla_compiles_total",
            "XLA compilations and compile-cache loads in this process")
        self._mc_compile_s = reg.counter(
            "serve_xla_compile_seconds_total",
            "seconds spent in XLA compilations and compile-cache loads")
        # TTFT as a real distribution (the old last-value gauge reported
        # whichever request finished last); components let dashboards
        # split queue wait from prefill from decode without a trace file.
        self._mh_ttft = reg.histogram(
            "serve_ttft_ms", "time to first token (ms)",
            buckets=LATENCY_MS_BUCKETS)
        self._mh_ttft_component = reg.histogram(
            "serve_ttft_component_ms",
            "per-request latency by component (ms)",
            buckets=LATENCY_MS_BUCKETS)
        # Paged-pool + speculative-decode observability (gauges read 0 on
        # the slotted backend; the /metrics surface is backend-stable).
        self._mg_blocks_used = reg.gauge(
            "serve_kv_blocks_used", "paged KV blocks currently mapped")
        self._mg_blocks_free = reg.gauge(
            "serve_kv_blocks_free", "paged KV blocks free")
        self._mg_free_watermark = reg.gauge(
            "serve_kv_free_block_watermark",
            "minimum free blocks over the publish window")
        self._mg_fragmentation = reg.gauge(
            "serve_kv_fragmentation",
            "fraction of mapped KV positions holding no live token")
        self._mc_spec = reg.counter(
            "serve_spec_tokens_total",
            "speculative draft tokens by outcome (proposed/accepted)")
        self._mg_spec_rate = reg.gauge(
            "serve_spec_acceptance_rate",
            "accepted/proposed draft tokens over the publish window")
        # Prefix-cache observability (zero on slotted / prefix_cache=off).
        self._mc_prefix_hits = reg.counter(
            "serve_prefix_cache_hits_total",
            "admissions that adopted a cached block-chain")
        self._mc_prefix_misses = reg.counter(
            "serve_prefix_cache_misses_total",
            "admissions with no usable cached prefix")
        self._mc_prefix_evictions = reg.counter(
            "serve_prefix_cache_evictions_total",
            "cached KV blocks reclaimed by allocation pressure")
        self._mg_prefix_hit_rate = reg.gauge(
            "serve_prefix_cache_hit_rate",
            "prompt tokens served from cache / prompt tokens offered")
        # Disaggregated-fleet observability: KV handoff volume and
        # zero-downtime weight swaps (zero outside a fleet).
        self._mc_kv_transfer = reg.counter(
            "serve_kv_transfer_blocks_total",
            "KV blocks moved by the prefill->decode handoff, by kind "
            "(exported/adopted/reused)")
        self._mc_swaps = reg.counter(
            "serve_weight_swaps_total", "weight swaps applied in place")
        self._mc_kv_fail = reg.counter(
            "serve_kv_transfer_failures_total",
            "refused/failed KV transfers by reason "
            "(corrupt/mismatch/push/adopt)")
        self._spec_proposed = 0  # graftsync: owner=engine-thread
        self._spec_accepted = 0  # graftsync: owner=engine-thread
        # decode steps that ran unspeculated under arena pressure
        self._spec_off_steps = 0  # graftsync: owner=engine-thread
        self._m_last = {  # graftsync: owner=engine-thread
            "admitted": 0, "rejected": 0, "evicted": 0,
            "completed": 0, "preempted": 0, "iterations": 0,
            "busy_iterations": 0, "xla_compiles": 0, "xla_compile_s": 0.0,
            "spec_proposed": 0, "spec_accepted": 0,
            "prefix_hits": 0, "prefix_misses": 0,
            "prefix_evictions": 0}
        self._metrics_server = None
        # Serving-mesh shape: set once (the mesh is fixed for the engine's
        # lifetime), labeled per axis so `serve_mesh_axis_size{axis="tp"}`
        # reads naturally next to the device total.
        self._mg_mesh_devices = reg.gauge(
            "serve_mesh_devices", "devices in the serving mesh (1 = unsharded)")
        self._mg_mesh_axis = reg.gauge(
            "serve_mesh_axis_size", "serving mesh axis size by name")
        self._mg_mesh_devices.set(self.mesh.size if self.mesh else 1)
        for ax, n in (dict(self.mesh.shape) if self.mesh else {}).items():
            self._mg_mesh_axis.set(n, axis=ax)
        # Resident weight-plane bytes as stored (int + scale leaves for a
        # quantized tree): the decode-bandwidth denominator obs/flops.py's
        # ceiling model reads, labeled by dtype so one scrape shows a
        # mixed fp/int8/int4 fleet.
        self._mg_weight_bytes = reg.gauge(
            "serve_weight_bytes",
            "bytes of resident model weights (as stored)")
        self._mg_weight_bytes.set(self._weight_bytes,
                                  weight_dtype=self.weight_dtype)

    @staticmethod
    def _place_params(params, mesh):
        """Pin every param leaf to the mesh's NamedSharding per the training
        sharding rules (Megatron column/row splits). Leaves that already
        carry the right sharding (reshard-on-load) are untouched —
        device_put with an equal sharding is a no-op, not a copy."""
        import jax
        from jax.sharding import NamedSharding

        from ..parallel import tree_pspecs

        return jax.tree_util.tree_map(
            lambda leaf, spec: jax.device_put(leaf, NamedSharding(mesh, spec)),
            params, tree_pspecs(params, mesh))

    # -- lifecycle -----------------------------------------------------------
    def start(self) -> "BatchEngine":
        if self._thread is None:
            if self.cfg.stats_url:
                from ..obs.stats_client import StatsClient

                self._stats = StatsClient(self.cfg.stats_url,
                                          self.cfg.worker_id).start()
                self._stats.register({"role": "serve",
                                      "num_slots": self.cfg.num_slots,
                                      "max_len": self.cfg.max_len})
            if self.cfg.metrics_port and self._metrics_server is None:
                from ..obs.prometheus import start_metrics_server

                self._metrics_server = start_metrics_server(
                    self.metrics_registry, self.cfg.metrics_port)
            self._stop.clear()
            self._thread = threading.Thread(target=self._loop, daemon=True,
                                            name="batch-engine")
            self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        self._wake.set()
        if self._thread is not None:
            self._thread.join(timeout=10.0)
            self._thread = None
        self.scheduler.drain(self.pool)
        self._drain_tasks()  # run stragglers inline; nobody left to race
        if self._stats is not None:
            self._stats.close()
            self._stats = None
        if self._metrics_server is not None:
            self._metrics_server.shutdown()
            self._metrics_server = None

    # -- engine-thread task queue --------------------------------------------
    def _drain_tasks(self) -> None:
        while True:
            try:
                fn, box, done = self._tasks.get_nowait()
            except queue.Empty:
                return
            try:
                box["result"] = fn()
            except Exception as e:  # noqa: BLE001 - delivered to the caller
                box["error"] = e
            done.set()

    def call_in_loop(self, fn, timeout: float = 120.0):
        """Run ``fn`` on the engine thread between iterations and return
        its result (exceptions re-raise here). Pool bookkeeping and
        ``self.params`` have a single writer — the loop — so any
        cross-thread mutation (KV export/adopt, weight swap) must ride
        this. Runs inline when the loop is not running."""
        t = self._thread
        if t is None or not t.is_alive():
            return fn()
        done = threading.Event()
        box: Dict[str, Any] = {}
        self._tasks.put((fn, box, done))
        self._wake.set()
        if not done.wait(timeout):
            raise TimeoutError("engine-loop task timed out")
        if "error" in box:
            raise box["error"]
        return box.get("result")

    # -- disaggregated fleet: weight swap + KV handoff -----------------------
    def swap_params(self, new_params) -> int:
        """Zero-downtime weight swap: shard ``new_params`` into this
        engine's mesh on the CALLING thread (the expensive part — in-flight
        decode keeps stepping on the old weights meanwhile), then cut the
        pointer over between two iterations. Requests straddling the
        cutover decode their remaining tokens on the new weights; nothing
        is evicted, nothing fails. Returns the new params_version."""
        if faults.take("engine.swap_fail", self.cfg.worker_id) is not None:
            # Before any placement or cutover: a failed swap must leave
            # the serving weights untouched (the rolling-swap driver's
            # canary/rollback path handles the error).
            raise RuntimeError("injected swap failure")
        # A quantized engine hot-swaps quantized: the load path quantizes
        # on the way in (load_params infers the dtype from ``like``), but
        # callers handing raw fp trees get the same treatment here so the
        # resident weight plane never changes dtype across a swap.
        if self.weight_dtype != "fp":
            from ..models.quantize import quantize_weights, weight_dtype_of

            if weight_dtype_of(new_params) == "fp":
                new_params = quantize_weights(new_params, self.weight_dtype)
        placed = (self._place_params(new_params, self.mesh)
                  if self.mesh is not None else new_params)
        from ..models.quantize import weight_plane_bytes

        nbytes = weight_plane_bytes(placed)

        def _cutover():
            self.params = placed
            self.params_version += 1
            self._weight_bytes = nbytes
            self._mg_weight_bytes.set(nbytes, weight_dtype=self.weight_dtype)
            self._mc_swaps.inc()
            return self.params_version

        return self.call_in_loop(_cutover)

    def export_kv(self, token_ids: List[int],
                  trace_id: Optional[str] = None):
        """Serialize the cached KV chain covering ``token_ids`` into a
        ``KVTransferPayload`` (the prefill half of the handoff). Pin on
        the engine thread, fetch bytes off it, release on it again."""
        from .kv_transfer import build_payload

        pool = self.pool
        if pool.kind != "paged" or getattr(pool, "prefix", None) is None:
            raise ValueError("KV export needs kv_backend='paged' with "
                             "prefix_cache=True")
        export = self.call_in_loop(lambda: pool.export_blocks(token_ids))
        try:
            payload = build_payload(export, token_ids, pool.block_size,
                                    pool.quantize)
        finally:
            self.call_in_loop(lambda: pool.release_export(export))
        if payload.num_blocks:
            self._mc_kv_transfer.inc(payload.num_blocks, kind="exported")
        if self.tracer.enabled:
            self.tracer.instant("kv_export", trace_id=trace_id,
                                blocks=payload.num_blocks,
                                bytes=payload.nbytes())
        return payload

    def adopt_kv(self, payload, trace_id: Optional[str] = None
                 ) -> Dict[str, int]:
        """Install a transferred payload into this engine's arena (the
        decode half). Verifies the chain keys and the arena layout before
        any bytes land; returns the pool's adopt stats."""
        pool = self.pool
        if pool.kind != "paged" or getattr(pool, "prefix", None) is None:
            raise ValueError("KV adopt needs kv_backend='paged' with "
                             "prefix_cache=True")
        if payload.block_size != pool.block_size:
            raise ValueError(f"payload block_size {payload.block_size} != "
                             f"pool block_size {pool.block_size}")
        if bool(payload.quantized) != bool(pool.quantize):
            raise ValueError("payload/pool KV quantization mismatch "
                             f"({payload.quantized} vs {pool.quantize})")
        payload.verify_keys()
        stats = self.call_in_loop(
            lambda: pool.adopt_blocks(payload.keys, payload.blocks))
        for kind in ("adopted", "reused"):
            if stats.get(kind):
                self._mc_kv_transfer.inc(stats[kind], kind=kind)
        if self.tracer.enabled:
            self.tracer.instant("kv_adopt", trace_id=trace_id, **stats)
        return stats

    def quarantine_kv(self, keys, reason: str = "corrupt") -> int:
        """Degradation ladder rung 2: a refused/corrupt transfer's chain
        keys are unpublished from the local prefix cache (kv_pool
        .quarantine) so a poisoned chain can never be adopted by later
        prompts — the request that needed those blocks falls back to
        local prefill. Bumps ``serve_kv_transfer_failures_total{reason}``
        and returns the number of keys actually dropped."""
        self._mc_kv_fail.inc(reason=reason)
        pool = self.pool
        if pool.kind != "paged" or getattr(pool, "prefix", None) is None:
            return 0
        return self.call_in_loop(lambda: pool.quarantine(list(keys)))

    def note_kv_failure(self, reason: str) -> None:
        """Count a KV-transfer failure with nothing local to quarantine
        (e.g. the prefill side's push died)."""
        self._mc_kv_fail.inc(reason=reason)

    def warmup(self, prompt_ids: Optional[List[int]] = None) -> None:
        """Pay the prefill/decode jit compiles before traffic arrives."""
        running = self._thread is not None
        if not running:
            self.start()
        req = self._submit_ids(prompt_ids or [self.tokenizer.bos_id, 1],
                               max_tokens=2, temperature=0.0, seed=0)
        req.wait(timeout=300.0)
        if not running:
            self.stop()

    # -- submission ----------------------------------------------------------
    def submit(self, prompt: str, max_tokens: int = 64,
               temperature: float = 0.0, seed: int = 0,
               deadline_s: Optional[float] = None,
               stream: bool = False,
               trace_id: Optional[str] = None,
               prefill_only: bool = False) -> Request:
        """Tokenize and enqueue; raises QueueFullError (-> 429) past the
        queue bound, ValueError when the request can never fit a slot.
        With ``stream=True`` the request carries a ``stream_q`` the engine
        pushes each sampled token id into (None = end of stream) — the
        HTTP layer drains it into an SSE response. ``trace_id`` joins this
        request's spans to an upstream trace (router X-Trace-Id); one is
        minted when absent so responses always carry an id.
        ``prefill_only=True`` (disaggregated handoff) finishes the request
        the moment its prompt KV is materialized and published — no token
        is sampled; a decode replica adopts the blocks and samples."""
        ids = [self.tokenizer.bos_id] + self.tokenizer.tokenize(prompt)
        return self._submit_ids(ids, max_tokens, temperature, seed,
                                deadline_s, stream=stream, trace_id=trace_id,
                                prefill_only=prefill_only)

    def _submit_ids(self, ids: List[int], max_tokens: int,
                    temperature: float, seed: int,
                    deadline_s: Optional[float] = None,
                    stream: bool = False,
                    trace_id: Optional[str] = None,
                    prefill_only: bool = False) -> Request:
        import jax

        P = len(ids)
        padded = batch_step.round_up(max(P, 1), self.chunk)
        # Spec headroom: a verify window writes up to draft_len positions
        # past the last committed token, so the budget clamp reserves them
        # (mirrors generate_speculative's `+ k` on cache_len).
        k = self.draft_len
        if padded > self.pool.max_len or P > self.pool.capacity - k:
            raise ValueError(
                f"prompt of {P} tokens cannot fit a {self.pool.max_len}-"
                f"token sequence (chunked prefill pads to {padded}"
                + (f", spec reserves {k}" if k else "") + ")")
        max_tokens = max(1, min(int(max_tokens), self.pool.capacity - P - k))
        req = Request(ids, max_tokens, temperature=temperature, seed=seed,
                      deadline_s=(deadline_s if deadline_s is not None
                                  else self.cfg.default_deadline_s),
                      stop_ids=[self.tokenizer.eos_id],
                      prefill_only=prefill_only)
        if stream:
            req.stream_q = queue.Queue()
        from ..obs.trace import new_trace_id

        req.trace_id = trace_id or new_trace_id()
        req.rng_key = np.asarray(jax.random.PRNGKey(seed))
        self.scheduler.submit(req)
        self._wake.set()
        return req

    # Grace past the engine deadline before the caller forces eviction:
    # the engine's own expiry normally fires first (this is the backstop).
    WAIT_GRACE_S = 5.0

    def generate(self, prompt: str, max_tokens: int = 64,
                 temperature: float = 0.0, seed: int = 0,
                 deadline_s: Optional[float] = None,
                 timeout: Optional[float] = None,
                 trace_id: Optional[str] = None) -> dict:
        """Blocking convenience used by the HTTP front end.

        The caller-side wait derives from the request's own deadline
        (explicit ``deadline_s`` or the engine default) plus a short
        grace — a 5s-deadline request must never park its HTTP thread
        for the old fixed 600s. An explicit ``timeout`` still wins."""
        req = self.submit(prompt, max_tokens, temperature, seed, deadline_s,
                          trace_id=trace_id)
        if timeout is None:
            eff = deadline_s if deadline_s is not None \
                else self.cfg.default_deadline_s
            timeout = eff + self.WAIT_GRACE_S if eff is not None else 600.0
        if not req.wait(timeout):
            req.deadline = 0.0  # force eviction next iteration
            self._wake.set()
            req.wait(timeout=30.0)
        if req.error is not None:
            raise TimeoutError(req.error)
        return dict(req.result or {})

    # -- metrics -------------------------------------------------------------
    def metrics(self) -> Dict[str, Any]:
        # One consistent locked snapshot of the scheduler counters —
        # /metrics runs on HTTP handler threads while the engine thread
        # mutates them under scheduler.lock.
        sched = self.scheduler.counters()
        xla_compiles, xla_compile_s = compiles.totals()
        snap = {
            "iterations": self.iterations,
            "busy_iterations": self.busy_iterations,
            "xla_compiles": xla_compiles,
            "xla_compile_s": round(xla_compile_s, 4),
            "batch_occupancy": self.pool.num_used,
            "num_slots": self.pool.num_slots,
            **sched,
            "kv_backend": self.pool.kind,
            # Fleet fields: the router's poller reads these to learn pool
            # membership and swap progress.
            "role": self.cfg.role,
            "params_version": self.params_version,
            # Dashboard "mesh" column: "tp=2" / "tp=2,dp=2" / "1dev".
            "mesh": (",".join(f"{a}={n}" for a, n in self.mesh.shape.items())
                     if self.mesh is not None else "1dev"),
            # Dashboard "weights" column + the decode-bandwidth ceiling
            # inputs (obs/flops.py weight_bytes_per_token).
            "weight_dtype": self.weight_dtype,
            "weight_bytes": int(self._weight_bytes),
        }
        if self.pool.kind == "paged":
            snap.update({
                "kv_blocks_used": self.pool.blocks_in_use,
                "kv_blocks_free": self.pool.free_blocks,
                "kv_num_blocks": self.pool.num_blocks,
                # Peek (no reset — _publish owns the reset cycle): the
                # fleet autoscaler keys scale-up on this headroom gauge.
                "kv_free_watermark": self.pool._watermark,
                "kv_fragmentation": round(self.pool.fragmentation(), 4),
            })
        if self.draft_len:
            snap.update({
                "spec_proposed": self._spec_proposed,
                "spec_accepted": self._spec_accepted,
                "spec_acceptance_rate": round(
                    self._spec_accepted / max(self._spec_proposed, 1), 4),
                "spec_off_steps": self._spec_off_steps,
            })
        # Injected-fault fires (graftchaos): absent entirely when nothing
        # ever fired, so injection-off metrics are byte-identical.
        fc = faults.counts()
        if fc:
            snap["faults_injected"] = fc
        prefix = getattr(self.pool, "prefix", None)
        snap["prefix_cache"] = prefix is not None
        if prefix is not None:
            snap.update(prefix.stats())
        snap.update(self._metrics)
        return snap

    def _ttft_quantiles(self) -> Dict[str, float]:
        """p50/p95/p99 TTFT estimated from the bounded histogram, plus
        the histogram's sum/count so JSON consumers (graftscope, external
        scrapers without the Prometheus port) can compute averages — the
        quantile keys alone cannot recover a mean."""
        from ..obs.metrics import quantile_from_buckets

        snap = self.metrics_registry.snapshot().get("serve_ttft_ms")
        if not snap or not snap["series"]:
            return {}
        s = snap["series"][0]
        out: Dict[str, float] = {}
        for key, q in (("ttft_ms_p50", 0.5), ("ttft_ms_p95", 0.95),
                       ("ttft_ms_p99", 0.99)):
            v = quantile_from_buckets(s["buckets"], s["count"], q)
            if v is not None:
                out[key] = round(v, 1)
        if out:
            out["ttft_ms_sum"] = round(float(s["sum"]), 3)
            out["ttft_ms_count"] = int(s["count"])
        return out

    def _publish(self) -> None:
        now = time.monotonic()
        if now - self._last_publish < self.cfg.stats_interval_s:
            return
        dt = max(now - self._win_t0, 1e-9)
        tok_s = self._win_tokens / dt
        self._win_t0, self._win_tokens = now, 0
        self._last_publish = now
        self._metrics = {"tok/s": round(tok_s, 2)}
        q = self._ttft_quantiles()
        if q:
            self._metrics.update(q)
            # Back-compat key older dashboards read (was a last-value
            # gauge; a median is strictly more honest).
            self._metrics["ttft_ms"] = q["ttft_ms_p50"]
        # Registry mirror: gauges live, scheduler totals as counter deltas
        # (the scheduler keeps monotonic ints; Prometheus counters must
        # only ever be incremented).
        sched = self.scheduler.counters()  # locked snapshot (engine thread
        # races /metrics HTTP threads on these otherwise)
        self._mg_occupancy.set(self.pool.num_used)
        self._mg_queue.set(sched["queue_depth"])
        self._mg_tok_s.set(tok_s)
        if self.pool.kind == "paged":
            self._mg_blocks_used.set(self.pool.blocks_in_use)
            self._mg_blocks_free.set(self.pool.free_blocks)
            self._mg_free_watermark.set(self.pool.read_watermark())
            self._mg_fragmentation.set(self.pool.fragmentation())
        prefix = getattr(self.pool, "prefix", None)
        cur = {"admitted": sched["admitted"],
               "rejected": sched["rejected"],
               "evicted": sched["evicted"],
               "completed": sched["completed"],
               "preempted": sched["preempted"],
               "iterations": self.iterations,
               "busy_iterations": self.busy_iterations,
               "spec_proposed": self._spec_proposed,
               "spec_accepted": self._spec_accepted,
               "prefix_hits": prefix.hits if prefix else 0,
               "prefix_misses": prefix.misses if prefix else 0,
               "prefix_evictions": prefix.evictions if prefix else 0}
        for k in ("admitted", "rejected", "evicted", "completed",
                  "preempted"):
            d = cur[k] - self._m_last[k]
            if d > 0:
                self._mc_requests.inc(d, outcome=k)
        for k, kind in (("spec_proposed", "proposed"),
                        ("spec_accepted", "accepted")):
            d = cur[k] - self._m_last[k]
            if d > 0:
                self._mc_spec.inc(d, kind=kind)
        dp = cur["spec_proposed"] - self._m_last["spec_proposed"]
        if dp > 0:
            self._mg_spec_rate.set(
                (cur["spec_accepted"] - self._m_last["spec_accepted"]) / dp)
        for k, c in (("prefix_hits", self._mc_prefix_hits),
                     ("prefix_misses", self._mc_prefix_misses),
                     ("prefix_evictions", self._mc_prefix_evictions)):
            d = cur[k] - self._m_last[k]
            if d > 0:
                c.inc(d)
        if prefix is not None:
            self._mg_prefix_hit_rate.set(prefix.hit_rate())
        cur["xla_compiles"], cur["xla_compile_s"] = compiles.totals()
        for k, c in (("iterations", self._mc_iterations),
                     ("busy_iterations", self._mc_busy_iterations),
                     ("xla_compiles", self._mc_compiles),
                     ("xla_compile_s", self._mc_compile_s)):
            d = cur[k] - self._m_last[k]
            if d > 0:
                c.inc(d)
        self._m_last = cur
        if self._stats is not None:
            # "tok/s" is the key the stats server's aggregate sums, so a
            # serving fleet's total decode throughput lands on the
            # dashboard exactly like training workers' token rates.
            self._stats.log_metrics(self.iterations, dict(
                self.metrics(), **{"tok/s": round(tok_s, 2)}))

    # -- the iteration loop --------------------------------------------------
    def _loop(self) -> None:  # graftsync: owner=engine-thread
        sync_runtime.bind("engine-thread")
        while not self._stop.is_set():
            try:
                busy = self._iteration()
            except Exception as e:  # noqa: BLE001 - engine must not die silently
                # Fail every in-flight request loudly and keep serving.
                self.scheduler.drain(self.pool,
                                     error=f"engine error: {type(e).__name__}: {e}")
                busy = False
            if not busy:
                # Profiler-only: fifty idle turns a second would push every
                # request's spans out of the ring.
                with TraceAnnotation("engine.idle_wait"):
                    self._wake.wait(timeout=0.02)
                self._wake.clear()

    def _iteration(self) -> bool:
        self.iterations += 1
        self._drain_tasks()  # KV export/adopt + weight cutover run here
        sched, pool = self.scheduler, self.pool
        for r in sched.expire(pool):
            self._resolve_evicted(r)
        admitted = []
        if sched.queue_depth():  # a phase only when someone is waiting
            with self.tracer.phase("engine.admit"):
                admitted = sched.admit(pool)
        if admitted and self.tracer.enabled:
            for r in admitted:
                # queue_wait closes at slot binding; kv_alloc and any
                # prefix-cache adoption happened inside admit().
                self.tracer.complete(
                    "queue_wait", r.admitted_at - r.submitted_at,
                    trace_id=r.trace_id, end_mono=r.admitted_at, req=r.id)
                self.tracer.instant(
                    "kv_alloc", trace_id=r.trace_id, slot=r.slot,
                    prompt_tokens=len(r.prompt_ids))
                if r.cached_tokens:
                    self.tracer.instant(
                        "prefix_adopt", trace_id=r.trace_id,
                        cached_tokens=r.cached_tokens)
        pre = sched.prefilling()
        dec = None if pre else sched.decoding()
        if not pre and not dec:
            self._publish()
            return False
        self.busy_iterations += 1
        with StepTraceAnnotation("engine_iter", step_num=self.busy_iterations):
            if pre:
                self._prefill_chunk(pre[0])
                dec = sched.decoding()  # a finished prefill decodes this turn
            if dec:
                self._decode(dec)
        self._publish()
        return True

    def _resolve_evicted(self, req: Request) -> None:
        # expire() already resolved the waiter; nothing device-side to undo
        # (stale slot contents are unattendable once the slot is reused).
        pass

    def _attend(self, n: int) -> int:
        """Attend bucket for ``n`` positions, aligned to block bounds on
        the paged backend (gather reads whole blocks)."""
        pool = self.pool
        b = batch_step.attend_bucket(n, pool.max_len)
        if pool.kind == "paged":
            b = min(batch_step.round_up(b, pool.block_size), pool.max_len)
        return b

    def _register_prefix(self, req: Request) -> None:
        """Publish every newly FILLED block of this request into the
        prefix cache (content-hash keys chained from the sequence head).
        Called after each lengths[] advance; no-op without a paged pool
        with prefix caching on."""
        prefix = getattr(self.pool, "prefix", None)
        if prefix is not None and req.slot is not None:
            self.pool.register_upto(req.slot, req.prefill_source())

    def _prefill_chunk(self, req: Request) -> None:
        source = req.prefill_source()
        start = req.prefilled
        n = min(self.chunk, len(source) - start)
        final = start + n >= len(source)
        with self.tracer.phase("engine.prefill_chunk", trace_id=req.trace_id,
                               req=req.id, start=start, tokens=n, final=final):
            self._prefill_chunk_inner(req, source, start, n, final)

    def _prefill_chunk_inner(self, req: Request, source: List[int],
                             start: int, n: int, final: bool) -> None:
        tr = self.tracer
        pool, C = self.pool, self.chunk
        P = len(source)
        with tr.phase("engine.build_tables"):
            toks = np.zeros(C, np.int32)
            toks[:n] = source[start:start + n]
            attend = self._attend(start + C)
            if pool.kind == "paged":
                step = batch_step.paged_prefill_step(
                    self.args, C, attend, pool.max_blocks, pool.block_size,
                    with_logits=final, mesh=self.mesh)
                where = pool.tables[req.slot]
            else:
                step = batch_step.prefill_step(self.args, C, attend,
                                               with_logits=final, mesh=self.mesh)
                where = np.int32(req.slot)
        with tr.phase("engine.dispatch"):
            cache, last_logits = step(self.params, pool.cache, toks, where,
                                      np.int32(start), np.int32(max(n - 1, 0)))
        pool.cache = cache
        req.prefilled = start + n
        pool.lengths[req.slot] = min(start + n, P)
        self._register_prefix(req)
        if not final:
            return
        pool.lengths[req.slot] = P
        if req.prefill_only:
            # Handoff request: the prompt KV is written and every full
            # block published under its chain key — that WAS the job.
            # No sampling; the adopting decode replica recomputes the
            # final prompt token's logits and samples there.
            if req.first_token_at is None:
                req.first_token_at = time.monotonic()
            self._finish(req, "prefill")
            return
        with tr.phase("engine.sample_fetch"):
            tok, lp, key = batch_step.sample_token(last_logits, req.temperature,
                                                   req.rng_key)
            req.rng_key = np.asarray(key)
        if req.first_token_at is None:  # unset on preemption re-prefill
            req.first_token_at = time.monotonic()
        self._emit(req, tok, lp)

    # Decode spans aggregate this many batched steps per request — one
    # span per token would swamp the ring at decode rates.
    DECODE_SPAN_TICKS = 8

    def _open_decode_spans(self, dec: List[Request]) -> None:
        now = time.perf_counter()
        for r in dec:
            if r._decode_t0 is None:
                r._decode_t0 = now

    def _tick_decode_spans(self, dec: List[Request]) -> None:
        for r in dec:
            r._decode_ticks += 1
            if r._decode_ticks >= self.DECODE_SPAN_TICKS and r.state != DONE:
                self._flush_decode_span(r)

    def _flush_decode_span(self, req: Request) -> None:
        if req._decode_t0 is not None and self.tracer.enabled:
            self.tracer.complete(
                "decode", time.perf_counter() - req._decode_t0,
                trace_id=req.trace_id, req=req.id, ticks=req._decode_ticks)
        req._decode_t0 = None
        req._decode_ticks = 0

    def _decode(self, dec: List[Request]) -> None:
        with self.tracer.phase("engine.decode", rows=len(dec)):
            if self.pool.kind == "paged":
                self._decode_paged(dec)
            else:
                self._decode_slotted(dec)

    def _decode_slotted(self, dec: List[Request]) -> None:
        pool, tr = self.pool, self.tracer
        if tr.enabled:
            self._open_decode_spans(dec)
        with tr.phase("engine.build_tables"):
            B = pool.num_slots
            tokens = np.zeros(B, np.int32)
            # Free / prefilling rows ride the fixed-shape step pointed at the
            # reserved junk position; their outputs are discarded.
            pos = np.full(B, pool.max_len - 1, np.int32)
            temps = np.zeros(B, np.float32)
            keys = np.zeros((B, 2), np.uint32)
            for r in dec:
                tokens[r.slot] = r.last_token
                pos[r.slot] = pool.lengths[r.slot]
                temps[r.slot] = r.temperature
                keys[r.slot] = r.rng_key
            bucket = batch_step.attend_bucket(
                int(pos[[r.slot for r in dec]].max()) + 1, pool.max_len)
            step = batch_step.decode_step(self.args, bucket, mesh=self.mesh)
        with tr.phase("engine.dispatch"):
            cache, tok, lp, new_keys = step(self.params, pool.cache, tokens,
                                            pos, temps, keys)
        pool.cache = cache
        with tr.phase("engine.sample_fetch"):
            tok_h, lp_h, keys_h = (np.asarray(tok), np.asarray(lp),
                                   np.asarray(new_keys))
        for r in dec:
            pool.lengths[r.slot] += 1
            r.rng_key = keys_h[r.slot]
            self._emit(r, int(tok_h[r.slot]), float(lp_h[r.slot]))
        if self.tracer.enabled:
            self._tick_decode_spans(dec)

    def _grow_or_preempt(self, dec: List[Request], S: int) -> List[Request]:
        """Map the blocks each decoding row's next verify window needs.
        On arena exhaustion, preempt the YOUNGEST decoding request
        (recompute-on-resume) and retry — oldest requests always make
        progress, so the engine cannot livelock on a full arena."""
        pool, sched = self.pool, self.scheduler
        active = sorted(dec, key=lambda r: r.id)  # oldest first
        i = 0
        while i < len(active):
            r = active[i]
            # arena.exhaust: exercise the preemption/degradation path
            # without actually filling device memory.
            forced = faults.take("arena.exhaust") is not None
            if not forced and pool.ensure_capacity(
                    r.slot, pool.lengths[r.slot] + S):
                i += 1
                continue
            victim = active.pop()
            sched.preempt(pool, victim)
            # victim == r: it was the youngest itself; it re-queues.
        return active

    def _effective_draft_len(self) -> int:
        """Speculation for the NEXT decode step: configured draft length,
        or 0 when paged free blocks dip under ``spec_off_kv_free_frac``
        (degradation ladder rung 1 — a verify window maps draft_len extra
        positions per row, exactly the blocks a pressured arena lacks;
        an unspeculated step is slower but never preempts for drafts)."""
        k = self.draft_len
        if not k:
            return 0
        pool = self.pool
        if pool.free_blocks < self.cfg.spec_off_kv_free_frac \
                * max(pool.num_blocks, 1):
            self._spec_off_steps += 1
            return 0
        return k

    def _decode_paged(self, dec: List[Request]) -> None:
        import jax

        from ..infer.generate import _prompt_lookup_draft

        pool, cfg, tr = self.pool, self.cfg, self.tracer
        with tr.phase("engine.build_tables"):
            k = self._effective_draft_len()
            S = k + 1
            dec = self._grow_or_preempt(dec, S)
            if not dec:
                return
            if tr.enabled:
                self._open_decode_spans(dec)
            B = pool.num_slots
            # Rows outside ``dec`` ride as token 0 at position 0 of the junk block.
            tokens = np.zeros((B, S), np.int32)
            pos = np.zeros(B, np.int32)
            temps = np.zeros(B, np.float32)
            keys = np.zeros((B, 2), np.uint32)
            drafts: Dict[int, List[int]] = {}
            for r in dec:
                d = (_prompt_lookup_draft(r.prompt_ids + r.tokens, k,
                                          cfg.spec_max_ngram) if k else [])
                drafts[r.slot] = d
                tokens[r.slot] = [r.last_token] + d
                pos[r.slot] = pool.lengths[r.slot]
                temps[r.slot] = r.temperature
                keys[r.slot] = r.rng_key
            slots = [r.slot for r in dec]
            bucket = self._attend(int(pos[slots].max()) + S)
            step = batch_step.paged_decode_step(self.args, k, bucket,
                                                pool.max_blocks, pool.block_size,
                                                mesh=self.mesh)
            tables = pool.tables_for(slots)
        with tr.phase("engine.dispatch"):
            out = step(self.params, pool.cache, tokens, pos, tables,
                       temps, keys)
        pool.cache = out[0]
        with tr.phase("engine.sample_fetch"):
            # ONE blocking transfer for every small output.
            (preds, lp_preds, accept, alts, lp_draft, lp_alt,
             bonus, lp_bonus, new_keys) = jax.device_get(out[1:])
        for r in dec:
            s = r.slot
            p0 = pool.lengths[s]
            d = drafts[s]
            r.rng_key = np.asarray(new_keys[s])
            if r.temperature > 0.0:
                m = 0
                while m < k and accept[s][m]:
                    m += 1
                if m < k:
                    emitted = d[:m] + [int(alts[s][m])]
                    lps = [float(x) for x in lp_draft[s][:m]] \
                        + [float(lp_alt[s][m])]
                else:
                    emitted = d + [int(bonus[s])]
                    lps = [float(x) for x in lp_draft[s][:k]] \
                        + [float(lp_bonus[s])]
            else:
                m = 0
                while m < k and d[m] == int(preds[s][m]):
                    m += 1
                # m accepted drafts + the model's own next token at m
                emitted = d[:m] + [int(preds[s][m])]
                lps = [float(x) for x in lp_preds[s][:m + 1]]
            self._spec_proposed += k
            self._spec_accepted += m
            for t, lpv in zip(emitted, lps):
                self._emit(r, t, lpv)
                if r.state == DONE:
                    break
            if r.state != DONE:
                # Committed prefix only: the verify wrote S positions, but
                # lengths advance past just the accepted ones — rejected
                # tail KV is never referenced and the next window
                # overwrites it (no rollback copies).
                pool.lengths[s] = p0 + len(emitted)
                self._register_prefix(r)
        if self.tracer.enabled:
            self._tick_decode_spans(dec)

    def _emit(self, req: Request, tok: int, lp: float) -> None:
        """Account one sampled token: stop/length bookkeeping mirrors
        generate_lite (stop tokens are never appended)."""
        if tok in req.stop_ids:
            self._finish(req, "stop")
            return
        req.tokens.append(tok)
        req.logprobs.append(lp)
        req.last_token = tok
        if req.stream_q is not None:
            req.stream_q.put(tok)
            if self.tracer.enabled:
                self.tracer.instant("stream_emit", trace_id=req.trace_id,
                                    req=req.id, n=len(req.tokens))
        self._win_tokens += 1
        if len(req.tokens) >= req.max_tokens:
            self._finish(req, "length")
        elif req.state == PREFILL:
            req.state = DECODE

    def _finish(self, req: Request, reason: str) -> None:
        self.scheduler.finish(self.pool, req, reason)
        done = time.monotonic()
        dt = max(done - req.submitted_at, 1e-9)
        ttft_ms = ((req.first_token_at - req.submitted_at) * 1e3
                   if req.first_token_at else None)
        # Component breakdown: queue (submit->slot), prefill (slot->first
        # token), decode (first token->done). Histograms record regardless
        # of tracing so /metrics carries the distribution on its own.
        comp: Dict[str, float] = {}
        if req.admitted_at is not None:
            comp["queue_ms"] = (req.admitted_at - req.submitted_at) * 1e3
            if req.first_token_at is not None:
                comp["prefill_ms"] = (req.first_token_at
                                      - req.admitted_at) * 1e3
                comp["decode_ms"] = (done - req.first_token_at) * 1e3
        if ttft_ms is not None:
            self._mh_ttft.observe(ttft_ms)
        for k, v in comp.items():
            self._mh_ttft_component.observe(v, component=k[:-3])
        if self.tracer.enabled:
            self._flush_decode_span(req)
            self.tracer.complete("request", done - req.submitted_at,
                                 trace_id=req.trace_id, end_mono=done,
                                 req=req.id, reason=reason,
                                 tokens=len(req.tokens))
        req.resolve(result={
            "text": self.tokenizer.detokenize(req.tokens),
            "tokens": len(req.tokens),
            "engine": "batch",
            "finish_reason": reason,
            "generation_tokens": float(len(req.tokens)),
            "generation_tps": len(req.tokens) / dt,
            "mean_logprob": (float(np.mean(req.logprobs))
                             if req.logprobs else 0.0),
            "prompt_tokens": float(len(req.prompt_ids)),
            "prefix_cached_tokens": float(req.cached_tokens),
            "stopped_on_token": float(reason == "stop"),
            "trace_id": req.trace_id,
            **({"ttft_ms": round(ttft_ms, 1)} if ttft_ms is not None else {}),
            **{k: round(v, 2) for k, v in comp.items()},
        })
