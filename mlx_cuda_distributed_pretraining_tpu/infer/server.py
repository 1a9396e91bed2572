"""Minimal HTTP inference server over a trained run.

The reference ships model serving as Modal apps (reference:
modal/deploy.py + modal/client.py — an endpoint wrapping generation and
a client that posts prompts). This is the platform-free equivalent: a
dependency-free stdlib HTTP server over the same jitted decode path the
CLI uses, plus a tiny urllib client helper.

    python -m mlx_cuda_distributed_pretraining_tpu.infer.server \
        --run myrun --runs-root runs --port 8400

    POST /generate {"prompt": "...", "max_tokens": 64, "temperature": 0.8}
      -> {"text": ..., "tokens": N, "generation_tps": ..., "logprob": ...}
    GET /healthz -> {"status": "ok", "model": ..., "params_m": ...}

Two engines (``--engine``):

- ``locked`` (default) — generation serialized by a lock (one chip, one
  compiled decode); concurrent requests queue. Byte-compatible with the
  pre-engine server.
- ``batch`` — the continuous-batching engine (serve/): concurrent
  requests share one batched decode step over a slotted KV pool. A full
  admission queue returns 429; a missed deadline returns 504. Requests
  whose effective sampling knobs reshape logits (top_p / min_p /
  repetition_penalty) fall back to the locked path — the batched step
  samples by temperature only.

Streaming: a ``"stream": true`` body turns the response into SSE
(text/event-stream) — one ``data: {"token": id, "text": delta}`` event
per sampled token, then a final ``data: {"done": true, ...result}``
event. Batch-engine requests stream token-by-token; locked/fallback
requests emit the final event only.

The first request pays the jit compile either way.
"""

from __future__ import annotations

import argparse
import json
import queue as queue_mod
import struct
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional

from ..models import llama
from ..obs.trace import TRACE_HEADER
from ..serve.policy import Deadline
from ..serve.scheduler import QueueFullError
from .generate import generate_text


class InferenceService:
    """Owns the loaded model and serializes generation requests."""

    def __init__(self, params, args, tokenizer, kv_quant: bool = False,
                 run_name: str = "?", max_tokens_limit: int = 4096,
                 speculative: bool = False, draft_len: int = 8):
        self.params = params
        self.args = args
        self.tokenizer = tokenizer
        self.kv_quant = kv_quant
        self.run_name = run_name
        self.max_tokens_limit = max_tokens_limit
        self.speculative = speculative
        self.draft_len = draft_len
        self.lock = threading.Lock()
        self.n_params = llama.num_params(params)
        self.started_at = int(time.time())
        self.engine = None  # set by attach_engine (--engine batch)
        # Fleet lifecycle: a draining replica refuses NEW generation work
        # (503 -> the router unpublishes it) while in-flight requests run
        # to completion — the graceful half of scale-down and weight swap.
        self.draining = False

    def attach_engine(self, cfg=None, mesh=None) -> "object":
        """Start the continuous-batching engine (serve/) and route
        compatible requests through it. The locked path stays available
        for logit-reshaping sampling knobs. ``mesh`` is a prebuilt serving
        mesh (parallel.build_serve_mesh) — the one the params were
        reshard-on-loaded into when the server ran with ``--mesh``."""
        from ..serve import BatchEngine, EngineConfig

        if cfg is None:
            cfg = EngineConfig(kv_quant=self.kv_quant)
        if cfg.max_len > self.args.max_position_embeddings:
            import dataclasses

            cfg = dataclasses.replace(
                cfg, max_len=self.args.max_position_embeddings)
        self.engine = BatchEngine(self.params, self.args, self.tokenizer,
                                  cfg, mesh=mesh).start()
        return self.engine

    def close(self) -> None:
        if self.engine is not None:
            self.engine.stop()
            self.engine = None

    @classmethod
    def from_run(cls, run: str, runs_root: str = "runs",
                 kv_quant: bool = False, max_tokens_limit: int = 4096,
                 speculative: bool = False,
                 draft_len: int = 8, mesh=None,
                 weight_dtype: str = "fp") -> "InferenceService":
        from ..train.trainer import load_trained

        params, args, tok, _cfg = load_trained(run, runs_root=runs_root,
                                               mesh=mesh,
                                               weight_dtype=weight_dtype)
        return cls(params, args, tok, kv_quant=kv_quant, run_name=run,
                   max_tokens_limit=max_tokens_limit,
                   speculative=speculative, draft_len=draft_len)

    @staticmethod
    def _quantize(x: float, step: float = 0.05) -> float:
        """Samplers/processors are STATIC jit args of the decode step and
        cached by identity (lru, maxsize 64): every distinct param combo
        compiles and retains a decode executable. Snapping client floats
        to a 0.05 grid bounds the variant space a long-lived server can
        accumulate (and keeps repeat combos cache-hits)."""
        return round(round(x / step) * step, 2)

    def generate(self, prompt: str, max_tokens: int = 64,
                 temperature: float = 0.0, top_p: float = 0.0,
                 min_p: float = 0.0,
                 repetition_penalty: Optional[float] = None,
                 seed: int = 0,
                 deadline_s: Optional[float] = None,
                 trace_id: Optional[str] = None) -> dict:
        # Cap: an unbounded client value would allocate a huge KV cache
        # while holding the lock (XLA OOM can abort the process).
        max_tokens = max(1, min(int(max_tokens), self.max_tokens_limit))
        q_top_p = self._quantize(top_p)
        q_min_p = self._quantize(min_p)
        q_rep = (self._quantize(repetition_penalty)
                 if repetition_penalty else None)
        # Effective (post-quantization, no-op-filtered) knobs that reshape
        # logits: they gate BOTH speculative decoding and the batch engine
        # (the batched step samples by temperature only).
        reshapes = bool(q_top_p or q_min_p or (q_rep or 1.0) != 1.0)
        spec = self.speculative and not reshapes
        q_temp = self._quantize(temperature)
        if self.engine is not None and not reshapes:
            out = self.engine.generate(prompt, max_tokens=max_tokens,
                                       temperature=q_temp, seed=seed,
                                       deadline_s=deadline_s,
                                       trace_id=trace_id)
            stats_keys = ("generation_tokens", "generation_tps",
                          "mean_logprob", "prompt_tokens",
                          "stopped_on_token", "ttft_ms",
                          "prefix_cached_tokens",
                          "queue_ms", "prefill_ms", "decode_ms")
            return {
                "text": out["text"],
                "tokens": int(out["tokens"]),
                "engine": "batch",
                "finish_reason": out.get("finish_reason"),
                **({"trace_id": out["trace_id"]}
                   if out.get("trace_id") else {}),
                "effective_params": {
                    "temperature": q_temp, "top_p": q_top_p,
                    "min_p": q_min_p, "repetition_penalty": q_rep,
                    "max_tokens": max_tokens,
                },
                **{k: round(float(out[k]), 4)
                   for k in stats_keys if k in out},
            }
        with self.lock:
            text, stats = generate_text(
                self.params, self.args, self.tokenizer, prompt,
                max_new_tokens=max_tokens,
                temperature=q_temp,
                top_p=q_top_p, min_p=q_min_p, repetition_penalty=q_rep,
                seed=seed, kv_quant=self.kv_quant, return_stats=True,
                speculative=spec, draft_len=self.draft_len,
            )
        return {
            "text": text,
            "tokens": int(stats["generation_tokens"]),
            "speculative": spec,
            # The params the decode ACTUALLY ran with: client floats are
            # snapped to a 0.05 grid (see _quantize) and max_tokens is
            # server-clamped, so a client can see when its request was
            # adjusted rather than silently served with different knobs.
            "effective_params": {
                "temperature": q_temp, "top_p": q_top_p, "min_p": q_min_p,
                "repetition_penalty": q_rep, "max_tokens": max_tokens,
            },
            **{k: round(float(v), 4) for k, v in stats.items()},
        }

    def submit_stream(self, prompt: str, max_tokens: int = 64,
                      temperature: float = 0.0, top_p: float = 0.0,
                      min_p: float = 0.0,
                      repetition_penalty: Optional[float] = None,
                      seed: int = 0,
                      deadline_s: Optional[float] = None,
                      trace_id: Optional[str] = None):
        """Submit through the batch engine for token-by-token streaming;
        None when the request must take the locked path instead (no
        engine, or logit-reshaping knobs) — the caller then buffers."""
        if self.engine is None:
            return None
        q_rep = (self._quantize(repetition_penalty)
                 if repetition_penalty else None)
        if self._quantize(top_p) or self._quantize(min_p) \
                or (q_rep or 1.0) != 1.0:
            return None
        max_tokens = max(1, min(int(max_tokens), self.max_tokens_limit))
        return self.engine.submit(prompt, max_tokens=max_tokens,
                                  temperature=self._quantize(temperature),
                                  seed=seed, deadline_s=deadline_s,
                                  stream=True, trace_id=trace_id)

    def health(self) -> dict:
        d = {
            "status": "draining" if self.draining else "ok",
            "run": self.run_name,
            "architecture": "llama",
            "params_m": round(self.n_params / 1e6, 2),
            "vocab_size": self.args.vocab_size,
            "kv_quant": self.kv_quant,
            "max_tokens_limit": self.max_tokens_limit,
            "speculative": self.speculative,
            "draft_len": self.draft_len,
        }
        # Locked mode keeps the pre-engine health shape byte-for-byte;
        # batch mode advertises itself plus a live metrics snapshot.
        if self.engine is not None:
            d["engine"] = "batch"
            d["serve"] = self.engine.metrics()
        return d

    def metrics(self) -> dict:
        base = (self.engine.metrics() if self.engine is not None
                else {"engine": "locked", "role": "any"})
        base["draining"] = self.draining
        return base

    # -- disaggregated fleet -------------------------------------------------
    def prefill_handoff(self, body: dict,
                        trace_id: Optional[str] = None) -> dict:
        """POST /prefill: run a prefill-only request (prompt KV written +
        published, no token sampled), export the block chain, and — when
        ``transfer_to`` names a decode replica — push it there inside a
        ``kv_transfer`` span. Returns a JSON summary either way; the
        router then dispatches the ORIGINAL request to the decode
        replica, whose admission adopts the transferred chain."""
        if self.engine is None:
            raise ValueError("/prefill requires --engine batch")
        prompt = body["prompt"]
        if isinstance(prompt, list):
            prompt = prompt[0]
        if not isinstance(prompt, str):
            raise ValueError("'prompt' must be a string")
        dl = body.get("deadline_s")
        req = self.engine.submit(prompt, max_tokens=1,
                                 temperature=0.0,
                                 seed=int(body.get("seed", 0)),
                                 deadline_s=(float(dl) if dl is not None
                                             else None),
                                 trace_id=trace_id, prefill_only=True)
        # The host-side wait is derived from the request's own budget
        # when the caller did not pin one: waiting longer than the
        # deadline the engine will evict at just burns a handler thread.
        wait_s = body.get("timeout_s")
        if wait_s is None:
            wait_s = float(dl) + 5.0 if dl is not None else 300.0
        if not req.wait(timeout=float(wait_s)):
            raise TimeoutError("prefill did not complete in time")
        if req.error is not None:
            raise TimeoutError(req.error)
        payload = self.engine.export_kv(req.prompt_ids, trace_id=trace_id)
        out = {
            "prefill": True,
            "prompt_tokens": len(req.prompt_ids),
            "blocks": payload.num_blocks,
            "trace_id": req.trace_id,
            **{k: req.result[k] for k in ("queue_ms", "prefill_ms")
               if k in (req.result or {})},
        }
        target = body.get("transfer_to")
        if target and payload.num_blocks:
            from ..serve.kv_transfer import push_payload

            t0 = time.perf_counter()
            try:
                stats = push_payload(target, payload, trace_id=trace_id)
            except Exception as e:  # noqa: BLE001 - degradation, not death
                # Ladder rung 2: a failed push is an OPTIMIZATION lost,
                # never an error surfaced to the client — the decode
                # replica cache-misses and prefills locally. Count it and
                # report the prefill as done.
                self.engine.note_kv_failure("push")
                out["transfer_error"] = f"{type(e).__name__}: {e}"
                return out
            dur = time.perf_counter() - t0
            if self.engine.tracer.enabled:
                # The span that joins the two replicas' trees in
                # scripts/trace_report.py: prefill-side, decode-bound.
                self.engine.tracer.complete(
                    "kv_transfer", dur, trace_id=trace_id,
                    target=target, blocks=payload.num_blocks,
                    bytes=payload.nbytes(), **stats)
            out.update({"transfer_ms": round(dur * 1e3, 2),
                        "transfer_bytes": payload.nbytes(), **stats})
        return out

    def adopt_kv(self, data: bytes, trace_id: Optional[str] = None) -> dict:
        """POST /adopt_kv: install a pushed KV payload into this
        replica's arena (decode side of the handoff). A payload that
        fails the integrity gate is refused (400) AND its claimed chain
        keys are quarantined out of the prefix cache — cached blocks a
        corrupt sender vouched for must not serve future admissions."""
        if self.engine is None:
            raise ValueError("/adopt_kv requires --engine batch")
        from ..serve.kv_transfer import KVTransferPayload

        try:
            payload = KVTransferPayload.from_bytes(data)
        except ValueError:
            self._quarantine_claimed_keys(data)
            raise
        return self.engine.adopt_kv(payload, trace_id=trace_id)

    def _quarantine_claimed_keys(self, data: bytes) -> None:
        """Best-effort: pull the chain keys a refused payload CLAIMED
        from its (possibly damaged) header and drop them from the prefix
        cache. Unparseable headers still count the failure."""
        keys = []
        try:
            (hlen,) = struct.unpack_from("<I", data, 4)
            header = json.loads(data[8:8 + hlen].decode())
            keys = [bytes.fromhex(k) for k in header.get("keys", [])]
        except Exception:  # noqa: BLE001 - header itself may be the damage
            pass
        if keys:
            self.engine.quarantine_kv(keys, reason="corrupt")
        else:
            self.engine.note_kv_failure("corrupt")

    def swap_weights(self, body: dict) -> dict:
        """POST /admin/swap_weights: reshard a checkpoint straight into
        the live engine's mesh (per-device slices, no host gather) and
        cut over between iterations — in-flight requests finish on the
        new weights, nothing is evicted or failed."""
        from ..checkpoint.manager import CheckpointManager, latest_model_path

        model_path = body.get("model_path")
        if not model_path and body.get("run_dir"):
            model_path = latest_model_path(body["run_dir"])
            if model_path is None:
                raise ValueError(
                    f"no complete checkpoint under {body['run_dir']!r}")
        if not model_path:
            raise ValueError("need 'model_path' or 'run_dir'")
        like = self.engine.params if self.engine is not None else self.params
        mesh = self.engine.mesh if self.engine is not None else None
        new = CheckpointManager.load_params(model_path, like=like, mesh=mesh)
        with self.lock:  # the locked path reads self.params per request
            self.params = new
        version = (self.engine.swap_params(new)
                   if self.engine is not None else 0)
        return {"swapped": True, "model_path": model_path,
                "params_version": version}

    def trace(self, clear: bool = False) -> dict:
        """Chrome trace dump of the engine's span ring (GET /trace)."""
        if self.engine is not None:
            return self.engine.tracer.chrome_trace(clear=clear)
        return {"traceEvents": [], "displayTimeUnit": "ms",
                "metadata": {"service": "locked"}}


def _to_openai_completion(out: dict, req: dict, run_name: str,
                          tokenizer=None, effective_max: int = 0) -> dict:
    """Map the native /generate result onto the OpenAI completions shape
    so existing OpenAI-client tooling can point at this server. ``stop``
    strings are applied by truncation (generation stops on EOS; string
    stops are a post-filter); usage counts the RETURNED text after
    truncation, not the discarded tail."""
    import uuid

    text = out["text"]
    completion_tokens = out["tokens"]
    # "stop" when the decode ended on a stop/EOS token (the generator
    # reports this directly — a generation that meets EOS exactly at the
    # token budget is a stop, not a truncation); "length" = it ran out
    # the server-clamped budget (a cap-limited generation IS truncated).
    if out.get("stopped_on_token"):
        finish = "stop"
    else:
        finish = "length" if completion_tokens >= effective_max else "stop"
    stops = req.get("stop")
    if isinstance(stops, str):
        stops = [stops]
    for s in stops or []:
        idx = text.find(s)
        if idx >= 0:
            text = text[:idx]
            finish = "stop"
    if text != out["text"] and tokenizer is not None:
        completion_tokens = len(tokenizer.tokenize(text))
    prompt_tokens = int(out.get("prompt_tokens", 0))
    return {
        "id": f"cmpl-{uuid.uuid4().hex[:24]}",
        "object": "text_completion",
        "model": str(req.get("model") or run_name),
        "choices": [{"text": text, "index": 0, "logprobs": None,
                     "finish_reason": finish}],
        "usage": {"prompt_tokens": prompt_tokens,
                  "completion_tokens": completion_tokens,
                  "total_tokens": prompt_tokens + completion_tokens},
    }


def make_handler(service: InferenceService):
    class Handler(BaseHTTPRequestHandler):
        def log_message(self, fmt, *a):  # quiet by default
            pass

        def _reply(self, code: int, payload: dict):
            body = json.dumps(payload).encode()
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def _sse_begin(self):
            self.send_response(200)
            self.send_header("Content-Type", "text/event-stream")
            self.send_header("Cache-Control", "no-cache")
            self.end_headers()

        def _sse(self, obj: dict):
            self.wfile.write(b"data: " + json.dumps(obj).encode() + b"\n\n")
            self.wfile.flush()

        def _deadline_s(self, body: dict) -> Optional[float]:
            """Effective request budget in seconds. An upstream
            ``X-Deadline-Ms`` (stamped by the router/fleet policy layer)
            is end-to-end: it wins over — or tightens — the body's own
            ``deadline_s``. A budget already spent raises immediately
            (-> 504) instead of admitting work the scheduler will only
            evict."""
            dl = body.get("deadline_s")
            local = float(dl) if dl is not None else None
            d = Deadline.from_header(self.headers)
            if d is None:
                return local
            rem = d.remaining_s()
            if rem <= 0.0:
                raise TimeoutError("deadline exhausted before admission")
            return min(local, rem) if local is not None else rem

        def _stream_generate(self, req: dict, prompt: str,
                             effective_max: int,
                             deadline_s: Optional[float],
                             trace_id: Optional[str] = None) -> None:
            """SSE response: token events as the engine emits them, then
            the final result. Submission errors (429/400) raise BEFORE
            any header is written, so do_POST's handlers still apply."""
            rp = req.get("repetition_penalty")
            kw = dict(max_tokens=effective_max,
                      temperature=float(req.get("temperature", 0.0)),
                      top_p=float(req.get("top_p", 0.0)),
                      min_p=float(req.get("min_p", 0.0)),
                      repetition_penalty=(float(rp) if rp is not None
                                          else None),
                      seed=int(req.get("seed", 0)), deadline_s=deadline_s,
                      trace_id=trace_id)
            sreq = service.submit_stream(prompt, **kw)
            if sreq is None:
                # Locked / logit-reshaping fallback: compute fully (any
                # error still maps to a JSON status), then emit one event.
                out = service.generate(prompt=prompt, **kw)
                self._sse_begin()
                self._sse({"done": True, **out})
                return
            self._sse_begin()
            # Inter-token gap bound derived from the request's own budget
            # (the engine evicts at the deadline, so the queue resolves
            # shortly after it — waiting 600s for a 2s request is a hung
            # handler thread, exactly what graceful degradation forbids).
            gap_s = (deadline_s + 30.0 if deadline_s is not None
                     else 600.0)
            toks: list = []
            prev = ""
            while True:
                try:
                    tok = sreq.stream_q.get(timeout=gap_s)
                except queue_mod.Empty:
                    self._sse({"done": True, "error": "stream timeout"})
                    return
                if tok is None:
                    break
                toks.append(int(tok))
                full = service.tokenizer.detokenize(toks)
                self._sse({"token": int(tok), "text": full[len(prev):]})
                prev = full
            sreq.wait(timeout=30.0)
            if sreq.error is not None:
                self._sse({"done": True, "error": sreq.error})
            else:
                self._sse({"done": True, **(sreq.result or {})})

        def do_GET(self):
            import urllib.parse

            parts = urllib.parse.urlsplit(self.path)
            path = parts.path.rstrip("/")
            if path in ("", "/healthz"):
                self._reply(200, service.health())
            elif path == "/metrics":
                self._reply(200, service.metrics())
            elif path == "/trace":
                # On-demand chrome-trace dump (?clear=1 drains the ring).
                clear = "clear" in urllib.parse.parse_qs(parts.query)
                self._reply(200, service.trace(clear=clear))
            elif path == "/v1/models":
                # OpenAI clients list models before completing against one.
                self._reply(200, {
                    "object": "list",
                    # `created` is required by the OpenAI SDK's Model type;
                    # local runs have no registry timestamp, so serve the
                    # server process start (stable within a server's life).
                    "data": [{"id": service.run_name, "object": "model",
                              "created": service.started_at,
                              "owned_by": "local"}],
                })
            else:
                self._reply(404, {"error": f"unknown path {self.path}"})

        def do_POST(self):
            path = self.path.rstrip("/")
            if path in ("/admin/drain", "/admin/undrain"):
                # Drain: stop admitting (503 below -> the router
                # unpublishes this replica) while in-flight work runs to
                # completion; undrain reopens (e.g. post-swap canary).
                service.draining = path == "/admin/drain"
                m = service.metrics()
                self._reply(200, {
                    "draining": service.draining,
                    "inflight": int(m.get("batch_occupancy", 0)),
                    "queue_depth": int(m.get("queue_depth", 0))})
                return
            if path == "/admin/swap_weights":
                try:
                    length = int(self.headers.get("Content-Length", "0"))
                    body = json.loads(self.rfile.read(length) or b"{}")
                    self._reply(200, service.swap_weights(body))
                except (ValueError, KeyError, TypeError,
                        json.JSONDecodeError) as e:
                    self._reply(400, {"error": str(e)})
                except Exception as e:  # noqa: BLE001
                    self._reply(500, {"error": f"{type(e).__name__}: {e}"})
                return
            if path == "/adopt_kv":
                # Binary GKV1 payload (serve/kv_transfer.py), NOT json —
                # and deliberately allowed while draining: adoption only
                # warms the prefix cache, it admits nothing.
                try:
                    length = int(self.headers.get("Content-Length", "0"))
                    self._reply(200, service.adopt_kv(
                        self.rfile.read(length),
                        trace_id=self.headers.get(TRACE_HEADER)))
                except (ValueError, KeyError, TypeError) as e:
                    self._reply(400, {"error": str(e)})
                except Exception as e:  # noqa: BLE001
                    self._reply(500, {"error": f"{type(e).__name__}: {e}"})
                return
            if path not in ("/generate", "/v1/completions", "/prefill"):
                self._reply(404, {"error": f"unknown path {self.path}"})
                return
            if service.draining:
                self._reply(503, {"error": "draining: not admitting "
                                           "new requests"})
                return
            if path == "/prefill":
                try:
                    length = int(self.headers.get("Content-Length", "0"))
                    body = json.loads(self.rfile.read(length) or b"{}")
                    if not isinstance(body, dict) or "prompt" not in body:
                        raise ValueError(
                            "body must be a JSON object with 'prompt'")
                    eff = self._deadline_s(body)
                    if eff is not None:
                        body["deadline_s"] = eff
                    self._reply(200, service.prefill_handoff(
                        body, trace_id=self.headers.get(TRACE_HEADER)))
                except QueueFullError as e:
                    self._reply(429, {"error": str(e)})
                except TimeoutError as e:
                    self._reply(504, {"error": str(e)})
                except (ValueError, KeyError, TypeError,
                        json.JSONDecodeError) as e:
                    self._reply(400, {"error": str(e)})
                except Exception as e:  # noqa: BLE001
                    self._reply(500, {"error": f"{type(e).__name__}: {e}"})
                return
            try:
                length = int(self.headers.get("Content-Length", "0"))
                req = json.loads(self.rfile.read(length) or b"{}")
                if not isinstance(req, dict) or "prompt" not in req:
                    raise ValueError("body must be a JSON object with 'prompt'")
                rp = req.get("repetition_penalty")
                prompt = req["prompt"]
                if isinstance(prompt, list):  # OpenAI allows str | [str]
                    if len(prompt) != 1 or not isinstance(prompt[0], str):
                        raise ValueError(
                            "list prompts must hold exactly one string "
                            "(batched completions are not supported)")
                    prompt = prompt[0]
                elif not isinstance(prompt, str):
                    raise ValueError("'prompt' must be a string")
                effective_max = max(
                    1, min(int(req.get("max_tokens", 64)),
                           service.max_tokens_limit))
                dl_s = self._deadline_s(req)
                # Router-minted (or client-supplied) trace id: the engine
                # keys this request's spans by it.
                trace_id = self.headers.get(TRACE_HEADER)
                if req.get("stream"):
                    self._stream_generate(req, prompt, effective_max,
                                          dl_s, trace_id=trace_id)
                    return
                out = service.generate(
                    prompt=prompt,
                    max_tokens=effective_max,
                    temperature=float(req.get("temperature", 0.0)),
                    top_p=float(req.get("top_p", 0.0)),
                    min_p=float(req.get("min_p", 0.0)),
                    repetition_penalty=float(rp) if rp is not None else None,
                    seed=int(req.get("seed", 0)),
                    deadline_s=dl_s,
                    trace_id=trace_id,
                )
                if path == "/v1/completions":
                    out = _to_openai_completion(
                        out, req, service.run_name,
                        tokenizer=service.tokenizer,
                        effective_max=effective_max)
                self._reply(200, out)
            except QueueFullError as e:
                self._reply(429, {"error": str(e)})
            except TimeoutError as e:
                # Batch-engine deadline eviction (partial tokens dropped).
                self._reply(504, {"error": str(e)})
            except (ValueError, KeyError, TypeError, json.JSONDecodeError) as e:
                self._reply(400, {"error": str(e)})
            except Exception as e:  # noqa: BLE001 - surface, don't kill the server
                self._reply(500, {"error": f"{type(e).__name__}: {e}"})

    return Handler


def serve(service: InferenceService, host: str = "127.0.0.1",
          port: int = 8400) -> ThreadingHTTPServer:
    """Start serving in a background thread; returns the server — stop
    with ``httpd.shutdown(); httpd.server_close()`` (shutdown alone
    leaves the listening socket open). Port 0 picks a free port."""
    httpd = ThreadingHTTPServer((host, port), make_handler(service))
    t = threading.Thread(target=httpd.serve_forever, daemon=True,
                         name="infer-server")
    t.start()
    return httpd


def request_generate(url: str, prompt: str, timeout: float = 300.0,
                     **kwargs) -> dict:
    """Client helper (reference: modal/client.py posts prompts to the
    deployed endpoint): ``request_generate("http://h:8400", "hi")``."""
    import urllib.request

    body = json.dumps({"prompt": prompt, **kwargs}).encode()
    req = urllib.request.Request(
        url.rstrip("/") + "/generate", data=body,
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read())


def request_stream(url: str, prompt: str, timeout: float = 300.0,
                   **kwargs):
    """Streaming client: yields each decoded SSE event dict from a
    ``"stream": true`` /generate request (token events, then the final
    ``{"done": true, ...}`` summary). Works against a replica server or
    the router front door (serve/router.py) identically."""
    import urllib.request

    body = json.dumps({"prompt": prompt, "stream": True, **kwargs}).encode()
    req = urllib.request.Request(
        url.rstrip("/") + "/generate", data=body,
        headers={"Content-Type": "application/json"})
    resp = urllib.request.urlopen(req, timeout=timeout)
    try:
        buf = b""
        while True:
            chunk = resp.read1(8192)
            if not chunk:
                break
            buf += chunk
            while b"\n\n" in buf:
                raw, buf = buf.split(b"\n\n", 1)
                if raw.startswith(b"data: "):
                    yield json.loads(raw[len(b"data: "):])
    finally:
        resp.close()


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--run", required=True)
    p.add_argument("--runs-root", default="runs")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=8400)
    p.add_argument("--kv-quant", action="store_true")
    p.add_argument("--max-tokens-limit", type=int, default=4096)
    p.add_argument("--spec", action="store_true",
                   help="prompt-lookup speculative decoding for greedy/"
                        "temperature requests (>1 token per device step)")
    p.add_argument("--draft-len", type=int, default=8)
    p.add_argument("--engine", choices=("locked", "batch"), default="locked",
                   help="locked = one request at a time behind a lock "
                        "(default, byte-compatible); batch = continuous-"
                        "batching engine over a paged (or slotted) KV pool")
    p.add_argument("--slots", type=int, default=8,
                   help="batch engine: concurrent decode slots")
    p.add_argument("--kv-len", type=int, default=2048,
                   help="batch engine: per-request KV length bound (clamped "
                        "to the model's max_position_embeddings)")
    p.add_argument("--max-queue", type=int, default=32,
                   help="batch engine: admission queue depth before 429")
    p.add_argument("--prefill-chunk", type=int, default=256,
                   help="batch engine: prompt tokens prefilled per iteration")
    p.add_argument("--kv-backend", choices=("paged", "slotted"),
                   default="paged",
                   help="batch engine: paged = block-table KV arena "
                        "(admission by free blocks); slotted = one fixed "
                        "max-len row per request")
    p.add_argument("--block-size", type=int, default=32,
                   help="paged backend: tokens per KV block (power of two; "
                        "kv-len must be a multiple)")
    p.add_argument("--num-blocks", type=int, default=0,
                   help="paged backend: KV arena size in blocks "
                        "(0 = slotted-equivalent budget slots*kv_len/block)")
    p.add_argument("--spec-draft-len", type=int, default=0,
                   help="paged backend: in-batch speculative decoding — "
                        "prompt-lookup drafts verified per decode step "
                        "(0 = off)")
    p.add_argument("--spec-max-ngram", type=int, default=3,
                   help="paged backend: longest suffix n-gram for prompt-"
                        "lookup drafting")
    p.add_argument("--no-prefix-cache", action="store_true",
                   help="paged backend: disable automatic prefix caching "
                        "(content-hash KV block reuse across requests)")
    p.add_argument("--prefix-min-hit-blocks", type=int, default=1,
                   help="paged backend: shortest cached block-chain worth "
                        "adopting at admission")
    p.add_argument("--deadline-s", type=float, default=None,
                   help="batch engine: default per-request deadline")
    p.add_argument("--trace", action="store_true",
                   help="batch engine: record per-request spans "
                        "(queue_wait/prefill/decode; dump via GET /trace)")
    p.add_argument("--trace-sample", type=float, default=1.0,
                   help="fraction of requests traced (deterministic by "
                        "trace id)")
    p.add_argument("--stats-url", default=None,
                   help="batch engine: ws:// URL of the obs stats server "
                        "for per-iteration serving metrics")
    p.add_argument("--mesh", default=None,
                   help="batch engine: serving mesh spec, e.g. tp=2 or "
                        "tp=2,dp=2 — GSPMD-shards every prefill/decode "
                        "step over the device mesh; the checkpoint "
                        "reshards straight into it on load (yaml: "
                        "serving.mesh)")
    p.add_argument("--weight-dtype", choices=("fp", "int8", "int4"),
                   default="fp",
                   help="weight-only quantization of the serving weights "
                        "(models/quantize.py): per-output-channel scales, "
                        "quantized at checkpoint load — the fp safetensors "
                        "file stays canonical; embeddings/norms stay fp "
                        "(yaml: serving.weight_dtype)")
    p.add_argument("--role", choices=("any", "prefill", "decode"),
                   default="any",
                   help="fleet pool this replica serves (surfaced via "
                        "/metrics; the fleet router routes accordingly)")
    p.add_argument("--fleet-dir", default=None,
                   help="fleet membership directory (serve/fleet.py): "
                        "register this replica and heartbeat so the "
                        "controller sees liveness/death")
    p.add_argument("--fleet-index", type=int, default=0,
                   help="membership slot index under --fleet-dir")
    a = p.parse_args(argv)

    from ..utils.compile_cache import enable_compilation_cache

    print(enable_compilation_cache(), file=sys.stderr)
    mesh = None
    if a.mesh:
        if a.engine != "batch":
            p.error("--mesh requires --engine batch")
        from ..parallel import build_serve_mesh

        mesh = build_serve_mesh(a.mesh)
    if a.weight_dtype != "fp" and a.engine != "batch":
        p.error("--weight-dtype requires --engine batch")
    service = InferenceService.from_run(a.run, a.runs_root,
                                        kv_quant=a.kv_quant,
                                        max_tokens_limit=a.max_tokens_limit,
                                        speculative=a.spec,
                                        draft_len=a.draft_len, mesh=mesh,
                                        weight_dtype=a.weight_dtype)
    if a.engine == "batch":
        from ..parallel import parse_mesh_spec
        from ..serve import EngineConfig

        service.attach_engine(EngineConfig(
            num_slots=a.slots, max_len=a.kv_len, max_queue=a.max_queue,
            prefill_chunk=a.prefill_chunk, kv_quant=a.kv_quant,
            kv_backend=a.kv_backend, block_size=a.block_size,
            num_blocks=a.num_blocks, spec_draft_len=a.spec_draft_len,
            spec_max_ngram=a.spec_max_ngram,
            prefix_cache=not a.no_prefix_cache,
            prefix_min_hit_blocks=a.prefix_min_hit_blocks,
            default_deadline_s=a.deadline_s, stats_url=a.stats_url,
            trace=a.trace, trace_sample=a.trace_sample, role=a.role,
            weight_dtype=a.weight_dtype,
            mesh=parse_mesh_spec(a.mesh) if a.mesh else None), mesh=mesh)
    httpd = ThreadingHTTPServer((a.host, a.port), make_handler(service))
    if a.fleet_dir:
        from ..serve.fleet import start_heartbeat

        start_heartbeat(a.fleet_dir,
                        f"http://{a.host}:{httpd.server_address[1]}",
                        role=a.role, index=a.fleet_index)
    print(f"serving {a.run} ({service.n_params / 1e6:.1f}M params, "
          f"engine={a.engine}, role={a.role}) "
          f"on http://{a.host}:{httpd.server_address[1]}")
    try:
        httpd.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        service.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
