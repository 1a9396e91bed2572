"""Disaggregated serving fleet (serve/fleet.py + serve/kv_transfer.py).

KV handoff correctness is anchored on greedy parity: a decode engine
that adopted a prefill engine's transferred blocks must emit exactly the
tokens a standalone engine emits for the same prompt (the final prompt
token is always recomputed receiver-side, so the sampler's logits — and
thus seeded sampling — are independent of who ran the prefill). Fleet
lifecycle (autoscale, drain, canary swap) runs against stub HTTP
replicas so policy is tested without devices; the end-to-end handoff
runs real in-process servers and joins both replicas' trace dumps under
one trace id."""

import json
import threading
import time
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from types import SimpleNamespace

import jax
import numpy as np
import pytest
from conftest import load_script
from jax.sharding import NamedSharding

from mlx_cuda_distributed_pretraining_tpu.checkpoint.manager import (
    CheckpointManager,
)
from mlx_cuda_distributed_pretraining_tpu.checkpoint.safetensors_io import (
    save_safetensors,
)
from mlx_cuda_distributed_pretraining_tpu.config import DataConfig
from mlx_cuda_distributed_pretraining_tpu.infer.server import (
    InferenceService,
    serve,
)
from mlx_cuda_distributed_pretraining_tpu.models import llama
from mlx_cuda_distributed_pretraining_tpu.models.llama import LlamaArgs
from mlx_cuda_distributed_pretraining_tpu.parallel import (
    build_mesh,
    build_serve_mesh,
)
from mlx_cuda_distributed_pretraining_tpu.parallel.elastic import (
    _atomic_write_json,
    _read_json,
)
from mlx_cuda_distributed_pretraining_tpu.parallel.sharding_rules import (
    tree_pspecs,
)
from mlx_cuda_distributed_pretraining_tpu.serve import (
    BatchEngine,
    EngineConfig,
    FleetConfig,
    FleetController,
    FleetRouter,
    KVTransferPayload,
    PagedKVPool,
)
from mlx_cuda_distributed_pretraining_tpu.serve.fleet import (
    fleet_generation,
    read_fleet,
    register_replica,
    start_heartbeat,
)
from mlx_cuda_distributed_pretraining_tpu.serve.kv_transfer import (
    build_payload,
)
from mlx_cuda_distributed_pretraining_tpu.tokenizer import TokenizerManager
from mlx_cuda_distributed_pretraining_tpu.utils.tree import flatten_dict

TOK = TokenizerManager(DataConfig())
ARGS = LlamaArgs(
    vocab_size=TOK.vocab_size, hidden_size=32, intermediate_size=64,
    num_layers=2, num_heads=4, num_kv_heads=2, head_dim=8,
    max_position_embeddings=128,
)
PARAMS = llama.init_params(jax.random.PRNGKey(0), ARGS)
MAX_LEN = 128
SHARED = "the quick brown fox jumps over the lazy dog again and "


def _engine(**kw):
    cfg = EngineConfig(**{"num_slots": 2, "max_len": MAX_LEN,
                          "prefill_chunk": 16, **kw})
    return BatchEngine(PARAMS, ARGS, TOK, cfg, mesh=kw.pop("mesh", None))


def _pool(**kw):
    return PagedKVPool(ARGS, num_seqs=2, max_len=MAX_LEN,
                       **{"block_size": 32, "num_blocks": 8,
                          "prefix_cache": True, **kw})


def _fill_and_register(pool, seq, ids):
    pool.lengths[seq] = len(ids)
    pool.ensure_capacity(seq, len(ids))
    pool.register_upto(seq, ids)


def _stamp(pool, seed=0):
    """Give the arena distinctive per-position bytes so a transfer test
    proves data actually moved (zeros would vacuously compare equal)."""
    import jax.numpy as jnp

    cache = []
    for li, layer in enumerate(pool.cache):
        stamped = {}
        for ni, (name, arr) in enumerate(sorted(layer.items())):
            vals = (np.arange(np.prod(arr.shape), dtype=np.float64)
                    + 13 * li + 7 * ni + seed) % 31
            stamped[name] = jnp.asarray(
                vals.reshape(arr.shape).astype(np.dtype(arr.dtype)))
        cache.append(stamped)
    pool.cache = cache


# -- wire format --------------------------------------------------------------

@pytest.mark.parametrize("quantize", [False, True],
                         ids=["fp", "int8"])
def test_payload_roundtrip_and_integrity_gate(quantize):
    pool = _pool(quantize=quantize)
    ids = list(range(70))  # 2 full blocks + tail
    s = pool.allocate(len(ids), token_ids=ids)
    _fill_and_register(pool, s, ids)
    _stamp(pool)
    export = pool.export_blocks(ids)
    payload = build_payload(export, ids, pool.block_size, pool.quantize)
    pool.release_export(export)
    assert payload.num_blocks == 2
    assert payload.quantized == quantize
    assert len(payload.token_ids) == 64  # only the covered full blocks

    back = KVTransferPayload.from_bytes(payload.to_bytes())
    assert back.keys == payload.keys
    assert back.token_ids == payload.token_ids
    assert back.block_size == payload.block_size
    assert back.nbytes() == payload.nbytes() > 0
    for blk_a, blk_b in zip(payload.blocks, back.blocks):
        for la, lb in zip(blk_a, blk_b):
            assert sorted(la) == sorted(lb)
            for name in la:
                np.testing.assert_array_equal(np.asarray(la[name]),
                                              np.asarray(lb[name]))

    # Integrity gate: token ids that do not hash to the claimed chain
    # are refused before any block could land.
    evil = KVTransferPayload(
        token_ids=[9] + payload.token_ids[1:],
        block_size=payload.block_size, quantized=payload.quantized,
        keys=list(payload.keys), blocks=payload.blocks)
    with pytest.raises(ValueError, match="do not match"):
        KVTransferPayload.from_bytes(evil.to_bytes())
    # Truncated payloads are refused too.
    with pytest.raises(Exception):
        KVTransferPayload.from_bytes(payload.to_bytes()[:-3])


# -- pool export/adopt bookkeeping -------------------------------------------

def test_pool_export_pins_and_double_release_raises():
    pool = _pool()
    ids = list(range(70))
    s = pool.allocate(len(ids), token_ids=ids)
    _fill_and_register(pool, s, ids)
    e1 = pool.export_blocks(ids)
    e2 = pool.export_blocks(ids)  # overlapping export: pins nest
    assert e1.blocks == e2.blocks and len(e1.blocks) == 2
    assert all(pool._ref[b] >= 3 for b in e1.blocks)  # seq + 2 exports
    pool.free(s)
    # Pinned blocks survive the owner's free (refcount held by exports).
    assert pool.prefix.lookup(e1.keys[0]) is not None
    pool.release_export(e1)
    with pytest.raises(ValueError, match="already released"):
        pool.release_export(e1)
    pool.release_export(e2)
    assert all(pool._ref[b] == 0 for b in e2.blocks)
    assert pool.prefix.retired_blocks == 2  # back on the LRU, adoptable
    # Short prompt: nothing published -> empty export, trivially safe.
    e3 = pool.export_blocks(list(range(10)))
    assert e3.keys == [] and e3.blocks == []
    pool.release_export(e3)


def test_pool_adopt_roundtrip_reuse_and_layout_gate():
    src, dst = _pool(), _pool()
    ids = list(range(70))
    s = src.allocate(len(ids), token_ids=ids)
    _fill_and_register(src, s, ids)
    _stamp(src)
    export = src.export_blocks(ids)
    payload = build_payload(export, ids, src.block_size, False)
    src.release_export(export)

    stats = dst.adopt_blocks(payload.keys, payload.blocks)
    assert stats == {"adopted": 2, "reused": 0, "skipped": 0}
    # The bytes landed under the right content addresses.
    for i, key in enumerate(payload.keys):
        b = dst.prefix.lookup(key)
        assert b is not None
        for li, layer in enumerate(payload.blocks[i]):
            for name, arr in layer.items():
                np.testing.assert_array_equal(
                    np.asarray(dst.cache[li][name][b]), np.asarray(arr))
    # Idempotent: the same chain transfers at most once.
    again = dst.adopt_blocks(payload.keys, payload.blocks)
    assert again == {"adopted": 0, "reused": 2, "skipped": 0}
    # The adopted chain is a plain prefix hit for admission.
    s2 = dst.allocate(len(ids), token_ids=ids)
    assert dst.lengths[s2] == 64
    dst.free(s2)

    # Layout gate: a payload whose tensor names do not match the arena
    # (e.g. fp blocks into an int8 arena) is refused before mutation.
    qdst = _pool(quantize=True)
    with pytest.raises(ValueError, match="mismatch|names"):
        qdst.adopt_blocks(payload.keys, payload.blocks)
    assert qdst.blocks_in_use == 0


def test_pool_adopt_after_evict_reinstalls():
    src = _pool()
    ids = list(range(70))
    s = src.allocate(len(ids), token_ids=ids)
    _fill_and_register(src, s, ids)
    export = src.export_blocks(ids)
    payload = build_payload(export, ids, src.block_size, False)
    src.release_export(export)

    dst = _pool(num_blocks=3)  # tiny arena: adoption then pressure
    assert dst.adopt_blocks(payload.keys, payload.blocks)["adopted"] == 2
    # Unrelated traffic needs every block -> the adopted chain evicts.
    other = list(range(1000, 1070))
    s1 = dst.allocate(len(other), token_ids=other)
    assert s1 is not None and dst.prefix.evictions >= 1
    assert dst.prefix.lookup(payload.keys[1]) is None
    dst.free(s1)
    # A re-transfer simply re-installs the evicted chain (or its tail).
    stats = dst.adopt_blocks(payload.keys, payload.blocks)
    assert stats["adopted"] >= 1 and stats["skipped"] == 0
    s2 = dst.allocate(len(ids), token_ids=ids)
    assert dst.lengths[s2] == 64


def test_pool_adopt_arena_full_keeps_chain_prefix():
    src = _pool(num_blocks=8, block_size=16)
    ids = list(range(100))  # 6 full 16-token blocks
    s = src.allocate(len(ids), token_ids=ids)
    _fill_and_register(src, s, ids)
    export = src.export_blocks(ids)
    payload = build_payload(export, ids, 16, False)
    src.release_export(export)
    assert payload.num_blocks == 6

    dst = _pool(num_blocks=4, block_size=16)
    stats = dst.adopt_blocks(payload.keys, payload.blocks)
    # Arena smaller than the chain: a contiguous PREFIX lands, the rest
    # is skipped (a chain with holes would never match).
    assert stats["adopted"] == 4 and stats["skipped"] == 2
    assert all(dst.prefix.lookup(k) is not None for k in payload.keys[:4])
    assert all(dst.prefix.lookup(k) is None for k in payload.keys[4:])


# -- engine-level handoff -----------------------------------------------------

def test_engine_kv_handoff_greedy_parity():
    prompt = SHARED + SHARED + "handoff"
    base_eng = _engine(prefix_cache=True, block_size=16)
    base_eng.start()
    try:
        base = base_eng.generate(prompt, max_tokens=16, temperature=0.0,
                                 timeout=300.0)
    finally:
        base_eng.stop()

    pre = _engine(prefix_cache=True, block_size=16, role="prefill").start()
    dec = _engine(prefix_cache=True, block_size=16, role="decode").start()
    try:
        req = pre.submit(prompt, max_tokens=1, prefill_only=True)
        assert req.wait(timeout=300.0)
        assert req.finish_reason == "prefill"
        assert req.result["tokens"] == 0  # prefill-only: nothing sampled
        payload = pre.export_kv(req.prompt_ids)
        assert payload.num_blocks >= 2
        stats = dec.adopt_kv(payload)
        assert stats["adopted"] == payload.num_blocks

        out = dec.generate(prompt, max_tokens=16, temperature=0.0,
                           timeout=300.0)
        assert out["text"] == base["text"]  # greedy parity across the wire
        assert out["tokens"] == base["tokens"]
        assert out["prefix_cached_tokens"] >= 16  # adopted, not recomputed
        assert dec.metrics()["prefix_cache_hits"] >= 1
        assert pre.metrics()["role"] == "prefill"
        # Mismatched geometry is refused at the engine door.
        wrong = KVTransferPayload(
            token_ids=payload.token_ids, block_size=payload.block_size * 2,
            quantized=payload.quantized, keys=payload.keys,
            blocks=payload.blocks)
        with pytest.raises(ValueError, match="block_size"):
            dec.adopt_kv(wrong)
    finally:
        pre.stop()
        dec.stop()


def test_engine_swap_params_mid_request_greedy_identity():
    # Satellite: an fsdp2-sharded checkpoint hot-swaps into a LIVE tp2
    # decode engine with a greedy request straddling the cutover; the
    # weights are value-identical, so the token stream must be too.
    devs = jax.devices()
    fsdp_mesh = build_mesh(SimpleNamespace(mesh={"fsdp": 2}), devs[:2])
    placed = jax.tree_util.tree_map(
        lambda leaf, spec: jax.device_put(
            leaf, NamedSharding(fsdp_mesh, spec)),
        PARAMS, tree_pspecs(PARAMS, fsdp_mesh))
    flat_host = {k: np.asarray(v) for k, v in flatten_dict(placed).items()}

    import tempfile
    with tempfile.TemporaryDirectory() as td:
        path = f"{td}/model.safetensors"
        save_safetensors(path, flat_host)
        tp_mesh = build_serve_mesh({"tp": 2}, devices=devs[:2])
        eng = _engine(mesh=tp_mesh, role="decode")
        eng.start()
        try:
            prompt = SHARED + "swap me"
            base = eng.generate(prompt, max_tokens=20, temperature=0.0,
                                timeout=300.0)
            loaded = CheckpointManager.load_params(path, like=PARAMS,
                                                   mesh=tp_mesh)
            req = eng.submit(prompt + " again", max_tokens=20,
                             temperature=0.0)
            deadline = time.monotonic() + 120.0
            while not req.tokens and time.monotonic() < deadline:
                time.sleep(0.005)  # let the request into decode
            version = eng.swap_params(loaded)  # cutover mid-generation
            assert version == 1
            assert req.wait(timeout=300.0) and req.error is None
            # The straddling request finished cleanly on the new weights.
            assert req.result["tokens"] == 20

            post = eng.generate(prompt, max_tokens=20, temperature=0.0,
                                timeout=300.0)
            assert post["text"] == base["text"]  # bit-identical pre/post
            assert eng.metrics()["params_version"] == 1
        finally:
            eng.stop()


# -- fleet membership ---------------------------------------------------------

def test_membership_heartbeat_and_staleness(tmp_path):
    fdir = str(tmp_path / "fleet")
    assert fleet_generation(fdir) == 0
    stop = start_heartbeat(fdir, "http://127.0.0.1:9001", role="prefill",
                           index=0, interval_s=0.05)
    register_replica(fdir, "http://127.0.0.1:9002", role="decode", index=1)
    try:
        view = read_fleet(fdir, stale_after_s=5.0)
        assert view["generation"] == 1
        assert [m["role"] for m in view["members"]] == ["prefill", "decode"]
        assert all(m["alive"] for m in view["members"])

        # Age member 1's stamp far into the past: it reads dead, while
        # the heartbeat keeps member 0 alive through the same window.
        path = str(tmp_path / "fleet" / "members" / "gen_1_p1.json")
        rec = _read_json(path)
        rec["t"] = time.time() - 3600.0
        _atomic_write_json(path, rec)
        time.sleep(0.15)  # >= two heartbeat intervals
        view = read_fleet(fdir, stale_after_s=1.0)
        alive = {m["index"]: m["alive"] for m in view["members"]}
        assert alive == {0: True, 1: False}

        # A new generation makes the old epoch invisible, not just dead.
        register_replica(fdir, "http://127.0.0.1:9003", role="decode",
                         index=0, generation=2)
        view = read_fleet(fdir, stale_after_s=5.0)
        assert view["generation"] == 2 and len(view["members"]) == 1
    finally:
        stop.set()


# -- stub replicas: lifecycle policy without devices --------------------------

class _StubReplica:
    """Minimal HTTP replica: /metrics from a mutable dict, /admin/*
    mutate it, swap bumps params_version (or fails on demand)."""

    def __init__(self, role="decode"):
        self.state = {"queue_depth": 0, "batch_occupancy": 0, "role": role,
                      "draining": False, "params_version": 0,
                      "kv_blocks_free": 64, "kv_num_blocks": 64,
                      "kv_free_watermark": 64}
        self.fail_swap = False
        stub = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, fmt, *a):
                pass

            def _reply(self, code, payload):
                body = json.dumps(payload).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                if self.path.rstrip("/") in ("", "/healthz"):
                    self._reply(200, {"status": "ok"})
                else:
                    self._reply(200, stub.state)

            def do_POST(self):
                path = self.path.rstrip("/")
                self.rfile.read(
                    int(self.headers.get("Content-Length", "0") or 0))
                if path == "/admin/drain":
                    stub.state["draining"] = True
                    self._reply(200, {"draining": True})
                elif path == "/admin/undrain":
                    stub.state["draining"] = False
                    self._reply(200, {"draining": False})
                elif path == "/admin/swap_weights":
                    if stub.fail_swap:
                        self._reply(500, {"error": "bad checkpoint"})
                        return
                    stub.state["params_version"] += 1
                    self._reply(200, {
                        "swapped": True,
                        "params_version": stub.state["params_version"]})
                else:
                    self._reply(404, {"error": path})

        self.httpd = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
        threading.Thread(target=self.httpd.serve_forever,
                         daemon=True).start()
        self.url = f"http://127.0.0.1:{self.httpd.server_address[1]}"

    def close(self):
        self.httpd.shutdown()
        self.httpd.server_close()


def test_controller_autoscale_spawn_and_drain():
    d0, d1 = _StubReplica(), _StubReplica()
    router = FleetRouter([], [d0.url])
    spawned, stopped = [], []
    cfg = FleetConfig(scale_up_queue_depth=8, scale_down_idle_ticks=2,
                      min_replicas_per_pool=1, max_replicas_per_pool=2,
                      drain_timeout_s=5.0)
    ctl = FleetController(router, cfg,
                          spawn_fn=lambda role: (spawned.append(role)
                                                 or d1.url),
                          stop_fn=stopped.append)
    try:
        router.poll_once()
        assert ctl.autoscale_tick() == []  # healthy: no action

        d0.state["queue_depth"] = 20  # sustained queueing
        router.poll_once()
        actions = ctl.autoscale_tick()
        assert spawned == ["decode"] and len(router.replicas) == 2
        assert any(a.startswith("spawn decode") for a in actions)
        # At the pool cap: more pressure does not spawn again.
        router.poll_once()
        assert ctl.autoscale_tick() == []

        d0.state["queue_depth"] = 0  # idle again
        router.poll_once()
        assert ctl.autoscale_tick() == []  # tick 1 of 2: patience
        actions = ctl.autoscale_tick()    # tick 2: drain the newest
        assert any(a.startswith("drain decode r1") for a in actions)
        assert stopped == [d1.url]
        assert len(router.replicas) == 1
        assert d1.state["draining"] is True  # told to stop admitting
    finally:
        d0.close()
        d1.close()
        router.stop()


def test_controller_rolling_swap_canary_promotes_each_replica():
    d0, d1 = _StubReplica(), _StubReplica()
    p0 = _StubReplica(role="prefill")
    router = FleetRouter([p0.url], [d0.url, d1.url], canary_fraction=0.5)
    ctl = FleetController(router, FleetConfig())
    router.poll_once()

    # Simulated traffic: deliveries tick every replica's ok counter while
    # the canary window is open (the router normally does this in _pipe).
    stop_traffic = threading.Event()

    def traffic():
        while not stop_traffic.wait(0.01):
            for r in router.replicas.values():
                if r.canary:
                    r.ok_count += 1

    t = threading.Thread(target=traffic, daemon=True)
    t.start()
    try:
        out = ctl.rolling_swap(model_path="new.safetensors",
                               canary_requests=3, canary_timeout_s=10.0)
        assert out["failed"] == []
        assert [s["replica"] for s in out["swapped"]] == ["r1", "r2", "r0"]
        assert all(s["canary_ok"] >= 3 for s in out["swapped"])
        assert d0.state["params_version"] == 1
        assert d1.state["params_version"] == 1
        assert p0.state["params_version"] == 1
        assert not any(r.canary for r in router.replicas.values())

        # A swap failure halts the rollout before later replicas touch
        # the bad checkpoint.
        d0.fail_swap = True
        out = ctl.rolling_swap(model_path="worse.safetensors",
                               canary_requests=1, canary_timeout_s=5.0)
        assert [f["replica"] for f in out["failed"]] == ["r1"]
        assert out["swapped"] == []
        assert d1.state["params_version"] == 1  # untouched by the halt
    finally:
        stop_traffic.set()
        t.join(timeout=2.0)
        for s in (d0, d1, p0):
            s.close()
        router.stop()


def test_controller_sync_membership_adopts_and_reaps(tmp_path):
    fdir = str(tmp_path / "fleet")
    d0 = _StubReplica()
    fresh = _StubReplica(role="prefill")
    router = FleetRouter([], [d0.url])
    ctl = FleetController(router, FleetConfig(heartbeat_stale_s=1.0),
                          fleet_dir=fdir)
    try:
        router.poll_once()
        # d0 registered long ago and stopped beating; `fresh` is new.
        register_replica(fdir, d0.url, role="decode", index=0)
        path = str(tmp_path / "fleet" / "members" / "gen_1_p0.json")
        rec = _read_json(path)
        rec["t"] = time.time() - 60.0
        _atomic_write_json(path, rec)
        register_replica(fdir, fresh.url, role="prefill", index=1)

        actions = ctl.tick()
        assert any(a.startswith("adopt") for a in actions)
        assert any(a.startswith("reap") for a in actions)
        by_url = {r.url: r for r in router.replicas.values()}
        assert by_url[fresh.url].role == "prefill"
        assert by_url[d0.url].up is False
        assert by_url[d0.url].last_error == "heartbeat stale"
    finally:
        d0.close()
        fresh.close()
        router.stop()


def test_canary_gate_deterministic_fraction():
    router = FleetRouter(["http://p0"], ["http://d0", "http://d1"],
                         canary_fraction=0.25)
    try:
        router.set_canary("r2", True)
        cands = [router.replicas["r1"], router.replicas["r2"]]
        picks = {}
        for i in range(400):
            tid = f"trace-{i}"
            gated = router._gate_canary(cands, tid)
            assert gated == router._gate_canary(cands, tid)  # deterministic
            picks[tid] = gated[0].canary if gated[0].canary else False
            if not picks[tid]:
                # Ungated requests never see the canary at all.
                assert all(not r.canary for r in gated)
        frac = sum(picks.values()) / len(picks)
        assert 0.15 < frac < 0.35  # ~canary_fraction of traffic
        # Whole pool canary: gating would be an outage, so it is off.
        router.set_canary("r1", True)
        assert router._gate_canary(cands, "any") == cands
    finally:
        router.stop()


# -- end-to-end: HTTP handoff joined under one trace id -----------------------

def _fleet_replica(role):
    service = InferenceService(PARAMS, ARGS, TOK, run_name="tiny")
    service.engine = _engine(prefix_cache=True, block_size=16, role=role,
                             trace=True).start()
    httpd = serve(service, port=0)
    return service, httpd, f"http://127.0.0.1:{httpd.server_address[1]}"


def test_fleet_http_handoff_trace_join_and_drain(tmp_path):
    pre_s, pre_h, pre_url = _fleet_replica("prefill")
    dec_s, dec_h, dec_url = _fleet_replica("decode")
    router = FleetRouter([pre_url], [dec_url], poll_interval_s=0.1,
                         handoff_min_prompt_bytes=32, trace=True)
    from mlx_cuda_distributed_pretraining_tpu.serve.router import (
        serve_router,
    )
    rhttpd = serve_router(router, port=0)
    rurl = f"http://127.0.0.1:{rhttpd.server_address[1]}"
    try:
        prompt = SHARED + SHARED + "fleet e2e"
        req = urllib.request.Request(
            rurl + "/generate",
            data=json.dumps({"prompt": prompt, "max_tokens": 8}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=300.0) as resp:
            assert resp.status == 200
            out = json.loads(resp.read())
        # The decode replica served it off the transferred chain.
        assert out["tokens"] == 8
        assert out["prefix_cached_tokens"] >= 16
        assert dec_s.engine.metrics()["completed"] == 1
        assert pre_s.engine.metrics()["completed"] == 1  # the prefill leg
        assert router._mc_handoffs.value(outcome="ok") == 1

        # Both replicas' spans + the router's join under ONE trace id,
        # with the kv_transfer span bridging the two request trees.
        files = []
        for name, doc in (("router", router.tracer.chrome_trace()),
                          ("pre", pre_s.engine.tracer.chrome_trace()),
                          ("dec", dec_s.engine.tracer.chrome_trace())):
            path = str(tmp_path / f"{name}.json")
            with open(path, "w") as fh:
                json.dump(doc, fh)
            files.append(path)
        lines = load_script("trace_report").report(files, top=1)
        acct = next(ln for ln in lines if "requests_complete=" in ln)
        assert "requests_complete=1" in acct
        assert "handoffs=1" in acct and "kv_transfers=1" in acct
        assert any(ln.startswith("component=kv_transfer") for ln in lines)
        tree = [ln for ln in lines if "span=kv_transfer" in ln]
        assert tree and "service=serve" in tree[0]

        # Drain the decode replica: it 503s new work, the router sees
        # `draining` on the next poll and unpublishes it.
        urllib.request.urlopen(urllib.request.Request(
            dec_url + "/admin/drain", data=b"{}", method="POST"),
            timeout=10.0)
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(urllib.request.Request(
                dec_url + "/generate",
                data=json.dumps({"prompt": "x", "max_tokens": 2}).encode(),
                headers={"Content-Type": "application/json"}),
                timeout=10.0)
        assert exc.value.code == 503
        router.poll_once()
        rid = next(r.id for r in router.replicas.values()
                   if r.url == dec_url)
        assert router.replicas[rid].state == "draining"
        assert router.replicas[rid] not in router.candidates(None,
                                                             role="decode")
        urllib.request.urlopen(urllib.request.Request(
            dec_url + "/admin/undrain", data=b"{}", method="POST"),
            timeout=10.0)
        router.poll_once()
        assert router.replicas[rid].state == "active"
    finally:
        rhttpd.shutdown()
        rhttpd.server_close()
        router.stop()
        for s, h in ((pre_s, pre_h), (dec_s, dec_h)):
            s.close()
            h.shutdown()
            h.server_close()


def test_rolling_swap_under_flood_fails_no_request(tmp_path):
    # A live canary weight swap rolls through a 1 prefill + 1 decode fleet
    # while mixed traffic keeps flowing through the router (the new
    # checkpoint is value-identical, as in a deploy of retrained weights):
    # both replicas cut over, and not one request of the flood fails.
    from mlx_cuda_distributed_pretraining_tpu.serve.router import (
        serve_router,
    )

    load_gen = load_script("load_gen")

    path = str(tmp_path / "model.safetensors")
    save_safetensors(path, {k: np.asarray(v)
                            for k, v in flatten_dict(PARAMS).items()})
    pre_s, pre_h, pre_url = _fleet_replica("prefill")
    dec_s, dec_h, dec_url = _fleet_replica("decode")
    # 48: the prefill-heavy class (~80 bytes) hands its KV off, the
    # decode-heavy one (~12 bytes) prefills on the decode replica.
    router = FleetRouter([pre_url], [dec_url], poll_interval_s=0.1,
                         handoff_min_prompt_bytes=48)
    rhttpd = serve_router(router, port=0)
    rurl = f"http://127.0.0.1:{rhttpd.server_address[1]}"
    batch, floods, swapped = 8, [], threading.Event()

    def flood():
        return load_gen.run_load(
            rurl, concurrency=4, requests=batch, prompt="", max_tokens=4,
            temperature=0.0, deadline_s=None, timeout=300.0,
            mix="prefill-heavy:decode-heavy",
            mix_shapes={"prefill-heavy": (80, 4), "decode-heavy": (12, 16)})

    def keep_flooding():
        while not swapped.is_set():
            floods.append(flood())
        floods.append(flood())  # and once more on the new weights

    try:
        flood()  # warm every compile variant both classes will see
        t = threading.Thread(target=keep_flooding)
        t.start()
        try:
            out = FleetController(router, FleetConfig()).rolling_swap(
                model_path=path, canary_requests=2, canary_timeout_s=120.0)
        finally:
            swapped.set()
            t.join(timeout=300.0)
        assert not t.is_alive()
        assert out["failed"] == []
        assert sorted(s["replica"] for s in out["swapped"]) == ["r0", "r1"]
        assert all(s["canary_ok"] >= 2 and s["params_version"] == 1
                   for s in out["swapped"])
        assert pre_s.engine.metrics()["params_version"] == 1
        assert dec_s.engine.metrics()["params_version"] == 1
        assert len(floods) >= 2
        for summary in floods:
            assert summary["completed"] == summary["ok"] == batch, summary
            assert summary["outcomes"]["error"] == 0
    finally:
        rhttpd.shutdown()
        rhttpd.server_close()
        router.stop()
        for s, h in ((pre_s, pre_h), (dec_s, dec_h)):
            s.close()
            h.shutdown()
            h.server_close()


import urllib.error  # noqa: E402  (used in the e2e drain assertions)
