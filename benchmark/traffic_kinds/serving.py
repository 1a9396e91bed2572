"""What the two serving kinds share: the program's ``InferenceService`` with
the batch engine behind ``infer/server.py::serve`` on a free port, in threads
of the run's one process; weights made from the seed by the benchmark; warm-up
of exactly the step programs the mix's lengths can reach; load from one client
thread; and, once the window has closed and the program's state is freed, the
plain reference over a seeded sample of the requests it finished.
"""

from __future__ import annotations

import gc
import math
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from benchmark import loadgen, synthetic
from benchmark.reference import llama_dense as ref

POLL_S = 0.5


class Served:
    """The program under load: service, engine, HTTP server, client."""

    def __init__(self, ctx):
        import jax

        from mlx_cuda_distributed_pretraining_tpu.infer.server import InferenceService, serve
        from mlx_cuda_distributed_pretraining_tpu.models.llama import LlamaArgs
        from mlx_cuda_distributed_pretraining_tpu.serve import EngineConfig

        c = ctx.config
        self.ctx = ctx
        self.tokenizer = synthetic.IdTokenizer(int(c["vocab_size"]))
        args = LlamaArgs(
            vocab_size=int(c["vocab_size"]), hidden_size=int(c["hidden_size"]),
            intermediate_size=int(c["intermediate_size"]),
            num_layers=int(c["num_hidden_layers"]), num_heads=int(c["num_attention_heads"]),
            num_kv_heads=int(c["num_key_value_heads"]), head_dim=int(c["head_dim"]),
            max_position_embeddings=int(c["max_position_embeddings"]),
            rms_norm_eps=float(c["rms_norm_eps"]), rope_theta=float(c["rope_theta"]),
            tie_word_embeddings=bool(c["tie_word_embeddings"]))
        t0 = time.perf_counter()
        params = ref.init_params(ctx.seed, c)
        jax.block_until_ready(params)
        ctx.say(f"weights: float32 from the seed on the device in {time.perf_counter() - t0:.1f} s")
        self.service = InferenceService(params, args, self.tokenizer,
                                        run_name=ctx.cell["name"])
        del params
        self.engine_cfg = EngineConfig(**c["engine"])
        self.engine = self.service.attach_engine(self.engine_cfg)
        self.httpd = serve(self.service, port=0)
        self.host, self.port = "127.0.0.1", self.httpd.server_address[1]
        self.client = loadgen.StreamClient(self.host, self.port)
        self.snapshots: List[Dict[str, Any]] = []
        self._last_poll = 0.0

    def body(self, req: Dict[str, Any]) -> Dict[str, Any]:
        return {"prompt": synthetic.prompt_text(req["prompt_ids"]),
                "max_tokens": int(req["max_tokens"]),
                "temperature": float(self.ctx.mix.get("temperature", 0.0))}

    def metrics(self) -> Dict[str, Any]:
        snap = loadgen.http_get_json(self.host, self.port, "/metrics")
        snap["t"] = time.perf_counter()
        return snap

    def poll(self, force: bool = False) -> None:
        now = time.perf_counter()
        if force or now - self._last_poll >= POLL_S:
            self._last_poll = now
            self.snapshots.append(self.metrics())

    def programs(self) -> int:
        from mlx_cuda_distributed_pretraining_tpu.serve import batch_step

        return len(batch_step._STEP_CACHE)

    def warm_lengths(self) -> List[int]:
        """One prompt length (BOS included) per attend bucket the mix can
        reach: its last chunk lands in that bucket and its earlier chunks walk
        the buckets below, so every prefill program (with and without
        logits) and every decode program the window can ask for is built."""
        e, mix = self.engine_cfg, self.ctx.mix
        max_prompt = int(mix["prompt_tokens"]["max"])
        reach = max_prompt + int(mix["output_tokens"]["max"])
        chunk, out, b = int(e.prefill_chunk), [], 256
        while True:
            b = min(b, int(e.max_len))
            out.append(max(2, min(b - chunk // 2, max_prompt)))
            if b >= reach or b >= int(e.max_len):
                return out
            b *= 2

    def warm_up(self) -> None:
        t0 = time.perf_counter()
        rng = synthetic.rng_for(self.ctx.seed, 7)
        for p_len in self.warm_lengths():
            ids = rng.integers(3, self.tokenizer.vocab_size, size=p_len - 1).tolist()
            rec = self.client.launch({"prompt": synthetic.prompt_text(ids), "max_tokens": 2,
                                      "temperature": 0.0}, time.perf_counter())
            while rec["end"] is None:
                self.client.pump(0.05)
            if rec["error"] or len(rec["token_ids"]) != 2:
                raise RuntimeError(f"warm-up request of {p_len} tokens failed: {rec['error']}")
        self.client.done.clear()
        self.ctx.say(f"warm-up: prompts of {self.warm_lengths()} tokens, {self.programs()} "
                     f"step programs, {time.perf_counter() - t0:.1f} s")

    def close(self) -> None:
        """Engine first: stopping it resolves every request still in a slot
        or queued, which releases the HTTP handler threads that
        ``server_close`` then waits for."""
        self.client.close()
        self.service.close()
        self.httpd.shutdown()
        self.httpd.server_close()


def drive(ctx, discipline: Callable[["Served", List[Dict[str, Any]], float], Dict[str, Any]]
          ) -> Dict[str, Any]:
    """Set up, warm up, let ``discipline`` generate the load around a window
    of ``ctx.seconds``, reduce, free the program, check against the reference."""
    mix, cfg = ctx.mix, ctx.config
    requests = synthetic.serve_requests(mix, int(cfg["vocab_size"]), ctx.seed)
    p = np.array([r["prompt_tokens"] for r in requests])
    o = np.array([r["max_tokens"] for r in requests])
    ctx.say(f"mix: {len(requests)} requests; prompt tokens median {np.median(p):.0f} "
            f"p95 {np.percentile(p, 95):.0f} max {p.max()}; output tokens median "
            f"{np.median(o):.0f} p95 {np.percentile(o, 95):.0f} max {o.max()}")
    served = Served(ctx)
    try:
        served.warm_up()
        programs_before = served.programs()
        load = discipline(served, requests, time.perf_counter())
        programs_after = served.programs()
        memory = ctx.device_memory()
    finally:
        ctx.stop_trace()
        served.close()
    if programs_after != programs_before:
        raise RuntimeError(f"{programs_after - programs_before} step programs were built "
                           f"inside the window: warm-up missed a shape")
    finished = [r for r in load["counted"] if r["final"] is not None and not r["error"]]
    failed = [r for r in load["counted"] if r["error"]]
    short = [r for r in finished
             if len(r["token_ids"]) != int(r["tag"]["max_tokens"])
             or int(r["final"].get("tokens", -1)) != int(r["tag"]["max_tokens"])]
    ctx.say(f"requests: attempted {len(load['counted'])} finished {len(finished)} "
            f"failed {len(failed)} with the wrong token count {len(short)}"
            + (f"; first error: {failed[0]['error']}" if failed else ""))
    sources = dict(load["sources"], kind=mix["kind"], snapshots=served.snapshots,
                   finished=finished, num_slots=int(served.engine_cfg.num_slots))
    del served
    gc.collect()

    sample = check_sample(ctx, finished)
    t_ref = time.perf_counter()
    gaps = reference_gaps(ctx, sample, ctx.control_precision)
    n_tokens = sum(len(r["token_ids"]) for r in sample)
    ctx.say(f"reference: {len(sample)} requests, {n_tokens} served tokens, longest "
            f"{max(r['tag']['prompt_tokens'] + len(r['token_ids']) for r in sample)} "
            f"positions, {time.perf_counter() - t_ref:.1f} s (not part of setup_s)")
    limits = limits_of(ctx)
    worst_lp = max(gaps["served_logprob_gap"]) if gaps["served_logprob_gap"] else float("nan")
    widest = gaps["served"]["max"]
    checks = {"served_token_gap": {"value": widest, "limit": limits.get("served_token_gap")},
              "served_logprob_gap": {"value": worst_lp, "limit": limits.get("served_logprob_gap")},
              "wrong_token_counts": {"value": float(len(short)), "limit": 0.0}}
    inside = True
    for name, c in checks.items():
        if not math.isfinite(c["value"]):
            c["value"] = None  # no number: printed as null, never inside
        ok = c["value"] is not None and c["limit"] is not None and c["value"] <= c["limit"]
        inside = inside and ok
        ctx.say(f"check {name}: {c['value']} (limit {c['limit']}) {'ok' if ok else 'OUTSIDE'}")
    ctx.say(f"served_token_gap: mean over positions {gaps['served']['mean']:.6g}, "
            f"share of positions where the served token is not the reference's first "
            f"{gaps['served']['flipped_share']:.4f}; |mean logprob, program - reference| per "
            f"request " + " ".join(f"{x:.5f}" for x in gaps["served_logprob_gap"]))
    if gaps["control"] is not None:
        ctx.say(f"control[{ctx.control_precision}] served_token_gap: {gaps['control']['max']:.6g} "
                f"mean {gaps['control']['mean']:.6g} flipped share "
                f"{gaps['control']['flipped_share']:.4f}; |mean logprob, control - reference| "
                + " ".join(f"{x:.5f}" for x in gaps["control_logprob_gap"]))
    return {
        "correct": bool(inside and len(finished) > 0), "checks": checks,
        "attempted": len(load["counted"]), "failed": len(failed) + len(short),
        "end_to_end": dict(load["end_to_end"], setup_s=load["setup_s"]),
        "memory": memory, "sources": sources,
        "check_numbers": {"served_token_gap": widest, "served": gaps["served"],
                          "served_logprob_gap": gaps["served_logprob_gap"],
                          "control_gap": gaps["control"]["max"] if gaps["control"] else None,
                          "control": gaps["control"],
                          "control_logprob_gap": gaps["control_logprob_gap"]},
    }


def limits_of(ctx) -> Dict[str, float]:
    """The cell's own limits (``workloads/<cell>.json``). A number the record
    states no limit for is held to none and so is never inside: a cell that
    waits for its limits cannot come out correct. At ``--rehearse`` alone, the
    kind's ``rehearse_limits`` of ``rehearse.json`` stand in: float32 on the
    CPU agrees with the reference to rounding, and logits at tiny widths are
    so small that a chip's limit would pass any token."""
    limits = {k: float(v) for k, v in (ctx.cell.get("limits") or {}).items()}
    if ctx.rehearse:
        limits.update(ctx.mix.get("rehearse_limits") or {})
    return limits


def check_sample(ctx, finished: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """The longest finished request and ``check_requests - 1`` others drawn
    from the seed."""
    if not finished:
        raise RuntimeError("no request finished inside the window")
    size = lambda r: r["tag"]["prompt_tokens"] + len(r["token_ids"])
    longest = max(range(len(finished)), key=lambda i: size(finished[i]))
    rest = [i for i in range(len(finished)) if i != longest]
    k = min(int(ctx.mix["check_requests"]) - 1, len(rest))
    picks = synthetic.rng_for(ctx.seed, 9).choice(rest, size=k, replace=False) if k else []
    return [finished[longest]] + [finished[int(i)] for i in picks]


def reference_gaps(ctx, sample: List[Dict[str, Any]], control: Optional[str]) -> Dict[str, Any]:
    import functools

    import jax
    import jax.numpy as jnp

    cfg = ctx.config
    params = ref.init_params(ctx.seed, cfg)
    n_out = int(ctx.mix["output_tokens"]["max"])
    fn = jax.jit(functools.partial(ref.served_token_gaps, cfg=cfg, control=control))
    rows: Dict[str, List[float]] = {"gap": [], "control_gap": []}
    lp_gap, control_lp_gap = [], []
    worst: List[Any] = []  # (gap, prompt tokens, index of the served token)
    bos = synthetic.IdTokenizer(int(cfg["vocab_size"])).bos_id
    for r in sample:
        prompt = [bos] + list(r["tag"]["prompt_ids"])
        out = list(r["token_ids"])
        full = (prompt + out)[:-1]
        bucket = 512
        while bucket < len(full) or bucket < len(prompt) - 1 + n_out:
            bucket *= 2
        tokens = np.zeros((1, bucket), np.int32)
        tokens[0, :len(full)] = full
        served = np.zeros(n_out, np.int32)
        served[:len(out)] = out
        got = jax.device_get(fn(params, jnp.asarray(tokens), jnp.int32(len(prompt) - 1),
                                jnp.asarray(served)))
        n = len(out)
        rows["gap"].extend(got["gap"][:n].tolist())
        worst.extend((float(g), len(prompt), j) for j, g in enumerate(got["gap"][:n]))
        ref_lp = float(np.mean(got["logprob"][:n]))
        if "mean_logprob" in r["final"]:
            lp_gap.append(abs(float(r["final"]["mean_logprob"]) - ref_lp))
        if control:
            rows["control_gap"].extend(got["control_gap"][:n].tolist())
            control_lp_gap.append(abs(float(np.mean(got["control_logprob"][:n])) - ref_lp))
    del params
    gc.collect()
    stats = lambda g: {"max": float(max(g)), "mean": float(np.mean(g)),
                       "flipped_share": float(np.mean(np.asarray(g) > 0))}
    ctx.say("reference: widest gaps at (prompt tokens, served index): " + " ".join(
        f"{g:.3f}@({p},{j})" for g, p, j in sorted(worst, reverse=True)[:8]))
    return {"served": stats(rows["gap"]), "served_logprob_gap": lp_gap,
            "control": stats(rows["control_gap"]) if control else None,
            "control_logprob_gap": control_lp_gap if control else None}


def latency_summary(ctx, records: List[Dict[str, Any]], horizon: float) -> Dict[str, Any]:
    """TTFT from the due time and pooled inter-token gaps, with medians, the
    generator's lateness, and the highest percentile the samples support. A
    request that failed or never produced a token counts as the worst: the
    whole time from its due instant to ``horizon``."""
    ttft = []
    for r in records:
        if r["token_times"] and not r["error"]:
            ttft.append(1e3 * (r["token_times"][0] - r["due"]))
        else:
            ttft.append(1e3 * (horizon - r["due"]))
    itl = loadgen.inter_token_gaps_ms([r for r in records if not r["error"]])
    lag = [1e3 * (r["sent"] - r["due"]) for r in records]
    ctx.say(f"ttft ms over {len(ttft)} requests: median {loadgen.percentile(ttft, 0.5):.1f} "
            f"p95 {loadgen.percentile(ttft, 0.95):.1f} max {max(ttft):.1f} (highest supported "
            f"percentile {100 * loadgen.highest_supported_percentile(len(ttft)):.1f})")
    ctx.say(f"inter-token ms over {len(itl)} gaps: median {loadgen.percentile(itl, 0.5):.2f} "
            f"p95 {loadgen.percentile(itl, 0.95):.2f} max {max(itl):.1f}")
    ctx.say(f"generator lag ms: median {loadgen.percentile(lag, 0.5):.3f} "
            f"p95 {loadgen.percentile(lag, 0.95):.3f} max {max(lag):.3f}")
    return {"ttft_ms": ttft, "itl_ms": itl, "lag_ms": lag}
