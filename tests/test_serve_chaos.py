"""The serving-plane chaos drill (graftchaos, slow tier), in-process.

A 1 prefill + 1 decode fleet behind the fleet router is flooded with
scripts/load_gen.py's mixed traffic while the fault registry
(serve/faults.py) tears at it:

  kv_transfer.corrupt   one KV payload bit-flipped on the wire (the decode
                        replica must refuse and quarantine; the router
                        falls back to local prefill)
  kv_transfer.drop      one KV push swallowed (same fallback)
  scrape.timeout        decode-replica /metrics scrapes time out (the
                        poller must NOT mark the replica dead)
  http.connect_refused  decode replica hard-down for a window (the
                        router's circuit breaker must open, traffic
                        degrades to the surviving pool, and the breaker
                        closes after recovery)

Engines, services and router share this process, so one armed rule set
covers every hop. The bars are robustness, not speed: every flooded
request resolves 200/429/504 (none hang, none surface a transport
error), the same greedy probe decodes to the same text before and after
the storm, the decode replica's breaker opens AND recovers, and
decode-class TTFT p99 stays within 3x the fault-free flood's (+0.5 s)
on the same fleet. Faults are seeded and decode is greedy, so a failure
here is a regression, not flake."""

import threading
import time

import jax
import pytest
from conftest import load_script

from mlx_cuda_distributed_pretraining_tpu.config import DataConfig
from mlx_cuda_distributed_pretraining_tpu.infer.server import (
    InferenceService,
    request_generate,
    serve,
)
from mlx_cuda_distributed_pretraining_tpu.models import llama
from mlx_cuda_distributed_pretraining_tpu.serve import (
    BatchEngine,
    EngineConfig,
    FleetRouter,
    PolicyConfig,
    faults,
    serve_router,
)
from mlx_cuda_distributed_pretraining_tpu.tokenizer import TokenizerManager

MIX = "prefill-heavy:decode-heavy"
SHAPES = {"prefill-heavy": (192, 8), "decode-heavy": (16, 48)}
FLOOD, CONC = 24, 6
PROBE = {"prompt": "chaos parity probe: the fleet must answer the "
                   "same tokens before and after the storm",
         "max_tokens": 16, "temperature": 0.0, "seed": 7}


def _decode_ttft_p99(summary):
    v = summary["mix"]["decode-heavy"]["ttft_p99_s"]
    return v if v is not None else 0.0


@pytest.mark.slow
def test_chaos_serve_drill_meets_every_bar():
    load_gen = load_script("load_gen")
    tok = TokenizerManager(DataConfig())
    args = llama.LlamaArgs(
        vocab_size=tok.vocab_size, max_position_embeddings=256,
        hidden_size=128, intermediate_size=256, num_layers=4,
        num_heads=8, num_kv_heads=8, head_dim=16)
    params = llama.init_params(jax.random.PRNGKey(0), args)

    def replica(role):
        svc = InferenceService(params, args, tok, run_name="chaos")
        svc.engine = BatchEngine(
            params, args, tok,
            EngineConfig(num_slots=8, max_len=256, prefill_chunk=64,
                         max_queue=128, kv_backend="paged", block_size=32,
                         prefix_cache=True, role=role)).start()
        httpd = serve(svc, port=0)
        return svc, httpd, f"http://127.0.0.1:{httpd.server_address[1]}"

    faults.reset()
    pre_svc, pre_httpd, pre_url = replica("prefill")
    dec_svc, dec_httpd, dec_url = replica("decode")
    # 128: prefill-heavy prompts (~192 bytes) hand their KV off (the
    # corrupt/drop faults need real pushes to bite) while decode-heavy
    # ones (~16 bytes) prefill locally.
    router = FleetRouter([pre_url], [dec_url], poll_interval_s=0.2,
                         handoff_min_prompt_bytes=128,
                         policy=PolicyConfig(breaker_open_s=0.5))
    rhttpd = serve_router(router, port=0)
    rurl = f"http://127.0.0.1:{rhttpd.server_address[1]}"

    def flood():
        return load_gen.run_load(
            rurl, concurrency=CONC, requests=FLOOD, prompt="",
            max_tokens=8, temperature=0.0, deadline_s=30.0,
            timeout=600.0, mix=MIX, mix_shapes=SHAPES)

    def await_breaker(state, budget_s=8.0):
        t0 = time.monotonic()
        while time.monotonic() - t0 < budget_s:
            if router.policy.breaker_state(dec_url) == state:
                return True
            time.sleep(0.02)
        return False

    try:
        # Warm every compile variant, then the fault-free reference run.
        load_gen.run_load(rurl, concurrency=2, requests=4, prompt="",
                          max_tokens=8, temperature=0.0, deadline_s=None,
                          timeout=600.0, mix=MIX, mix_shapes=SHAPES)
        text_before = request_generate(rurl, timeout=120.0, **PROBE)["text"]
        clean = flood()

        # Chaos window. The KV faults fire inside the prefill service's
        # push (same process, same registry); the HTTP faults fire at the
        # router's egress choke point against the decode replica.
        faults.inject("kv_transfer.corrupt", nth=1)
        faults.inject("kv_transfer.drop", nth=1)
        faults.inject("scrape.timeout", every=3, times=3,
                      match=dec_url + "/metrics")
        result = {}
        t = threading.Thread(target=lambda: result.update(chaos=flood()))
        t.start()
        time.sleep(0.3)  # flood in flight before the replica "dies"
        # times=30: KV pushes to the dead replica ALSO match (they feed
        # kv_transfer's own policy, not the router's), so the window
        # must outlast that dilution for the router-side scrape stream
        # alone to reach the breaker threshold.
        faults.inject("http.connect_refused", times=30, every=1,
                      match=dec_url)
        breaker_opened = await_breaker("open")
        breaker_recovered = await_breaker("closed", budget_s=15.0)
        t.join(timeout=900.0)
        assert not t.is_alive(), "the chaos flood hung"
        chaos = result["chaos"]
        fires = faults.counts()
        faults.reset()
        text_after = request_generate(rurl, timeout=120.0, **PROBE)["text"]
    finally:
        faults.reset()
        rhttpd.shutdown()
        rhttpd.server_close()
        router.stop()
        for svc, httpd in ((pre_svc, pre_httpd), (dec_svc, dec_httpd)):
            httpd.shutdown()
            httpd.server_close()
            svc.close()

    # The drill actually exercised every armed fault point.
    assert fires.get("kv_transfer.corrupt", 0) >= 1, fires
    assert fires.get("kv_transfer.drop", 0) >= 1, fires
    assert fires.get("http.connect_refused", 0) >= 1, fires
    # Every flooded request resolved, with a clean status.
    outcomes = chaos["outcomes"]
    assert chaos["completed"] == FLOOD, chaos
    assert outcomes["ok"] + outcomes["429"] + outcomes["504"] == FLOOD, outcomes
    assert outcomes["error"] == 0 and outcomes["ok"] > 0, outcomes
    assert text_after == text_before
    assert breaker_opened and breaker_recovered
    bound_s = 3.0 * _decode_ttft_p99(clean) + 0.5
    assert _decode_ttft_p99(chaos) <= bound_s, (
        _decode_ttft_p99(chaos), bound_s)
