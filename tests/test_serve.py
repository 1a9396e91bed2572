"""Continuous-batching engine (serve/): pool, scheduler, engine and the
HTTP front end. Everything runs CPU-side on the tiny test shape."""

import json
import threading
import time
import urllib.error
import urllib.request

import jax
import numpy as np
import pytest

from mlx_cuda_distributed_pretraining_tpu.config import DataConfig
from mlx_cuda_distributed_pretraining_tpu.infer.generate import generate_text
from mlx_cuda_distributed_pretraining_tpu.infer.server import (
    InferenceService,
    serve,
)
from mlx_cuda_distributed_pretraining_tpu.models import llama
from mlx_cuda_distributed_pretraining_tpu.models.llama import LlamaArgs
from mlx_cuda_distributed_pretraining_tpu.serve import (
    BatchEngine,
    EngineConfig,
    PagedKVPool,
    QueueFullError,
    Request,
    Scheduler,
    SlotKVPool,
)
from mlx_cuda_distributed_pretraining_tpu.tokenizer import TokenizerManager

TOK = TokenizerManager(DataConfig())
ARGS = LlamaArgs(
    vocab_size=TOK.vocab_size, hidden_size=32, intermediate_size=64,
    num_layers=2, num_heads=4, num_kv_heads=2, head_dim=8,
    max_position_embeddings=128,
)
PARAMS = llama.init_params(jax.random.PRNGKey(0), ARGS)

# One pool max_len for the whole module: with the tiny shape this matches
# the locked path's bucketed cache length, so identity tests compare the
# same attend shapes.
MAX_LEN = 128


def _engine(**kw):
    cfg = EngineConfig(**{"num_slots": 2, "max_len": MAX_LEN,
                          "prefill_chunk": 16, **kw})
    return BatchEngine(PARAMS, ARGS, TOK, cfg)


# -- kv pool ------------------------------------------------------------------

def test_pool_allocate_free_reset():
    pool = SlotKVPool(ARGS, num_slots=3, max_len=MAX_LEN)
    assert pool.capacity == MAX_LEN - 1  # last position is reserved
    slots = [pool.allocate() for _ in range(3)]
    assert sorted(slots) == [0, 1, 2]
    assert pool.allocate() is None  # full pool: no slot, no exception
    assert pool.num_used == 3 and pool.occupancy() == 1.0
    pool.lengths[slots[0]] = 7
    pool.free(slots[0])
    with pytest.raises(ValueError):
        pool.free(slots[0])  # double free
    with pytest.raises(ValueError):
        pool.free(99)  # out of range
    s = pool.allocate()
    assert s == slots[0] and pool.lengths[s] == 0  # reuse resets length
    pool.reset()
    assert pool.num_free == 3 and pool.lengths == [0, 0, 0]
    # int8 pool builds the quantized quartet per layer
    qpool = SlotKVPool(ARGS, num_slots=2, max_len=MAX_LEN, quantize=True)
    assert "k_q" in qpool.cache[0] and "k" not in qpool.cache[0]


# -- scheduler (no device) ----------------------------------------------------

def test_scheduler_admit_evict_under_full_pool():
    pool = SlotKVPool(ARGS, num_slots=2, max_len=MAX_LEN)
    sched = Scheduler(max_queue=3)
    reqs = [Request([1, 2, 3], max_tokens=4) for _ in range(3)]
    for r in reqs:
        sched.submit(r)
    admitted = sched.admit(pool)
    assert [r.id for r in admitted] == [reqs[0].id, reqs[1].id]  # FIFO
    assert sched.queue_depth() == 1 and pool.num_free == 0
    assert all(r.state == "prefill" for r in admitted)
    # finishing one frees its slot; the queued request takes it next admit
    sched.finish(pool, admitted[0], "stop")
    assert pool.num_free == 1
    assert [r.id for r in sched.admit(pool)] == [reqs[2].id]
    assert sched.admitted == 3 and sched.completed == 1


def test_scheduler_queue_full_and_deadline_eviction():
    pool = SlotKVPool(ARGS, num_slots=1, max_len=MAX_LEN)
    sched = Scheduler(max_queue=2)
    running = Request([1], max_tokens=4, deadline_s=0.01)
    sched.submit(running)
    sched.admit(pool)
    queued = Request([1], max_tokens=4, deadline_s=0.01)
    sched.submit(queued)
    with pytest.raises(QueueFullError):
        sched.submit(Request([1], max_tokens=4))
        sched.submit(Request([1], max_tokens=4))
    # both the running and the queued request expire; the slot is freed
    evicted = sched.expire(pool, now=time.monotonic() + 1.0)
    assert {r.id for r in evicted} == {running.id, queued.id}
    assert all(r.finish_reason == "deadline" and r.error for r in evicted)
    assert pool.num_free == 1 and sched.evicted == 2


# -- engine -------------------------------------------------------------------

def test_batch1_greedy_token_identity_with_generate_text():
    prompt = "the quick brown fox"
    locked_text, stats = generate_text(
        PARAMS, ARGS, TOK, prompt, max_new_tokens=16, temperature=0.0,
        return_stats=True)
    eng = _engine().start()
    try:
        out = eng.generate(prompt, max_tokens=16, temperature=0.0,
                           timeout=300.0)
    finally:
        eng.stop()
    assert out["text"] == locked_text
    assert out["generation_tokens"] == stats["generation_tokens"]
    assert out["stopped_on_token"] == stats["stopped_on_token"]
    assert out["prompt_tokens"] == stats["prompt_tokens"]


def test_engine_concurrent_more_requests_than_slots():
    eng = _engine().start()
    outs = [None] * 5
    try:
        def run(i):
            outs[i] = eng.generate(f"prompt {i}", max_tokens=6,
                                   temperature=0.5, seed=i, timeout=300.0)

        threads = [threading.Thread(target=run, args=(i,)) for i in range(5)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        m = eng.metrics()
    finally:
        eng.stop()
    assert all(o is not None and o["tokens"] == 6 for o in outs)
    # sampled requests with distinct seeds should not all collapse to one
    # output (each slot runs its own rng chain)
    assert m["admitted"] == 5 and m["completed"] == 5
    assert m["batch_occupancy"] == 0 and m["queue_depth"] == 0


def test_engine_deadline_eviction_reported():
    eng = _engine(num_slots=1).start()
    try:
        with pytest.raises(TimeoutError, match="deadline"):
            eng.generate("slow request", max_tokens=64, deadline_s=1e-4,
                         timeout=300.0)
        assert eng.metrics()["evicted"] == 1
    finally:
        eng.stop()


def test_engine_rejects_oversized_prompt():
    eng = _engine()
    with pytest.raises(ValueError):
        eng._submit_ids(list(range(MAX_LEN + 5)), max_tokens=4,
                        temperature=0.0, seed=0)


# -- HTTP front end -----------------------------------------------------------

def _post(url, body, timeout=300.0):
    req = urllib.request.Request(
        url + "/generate", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, json.loads(resp.read())


def test_server_batch_engine_429_past_max_queue_depth():
    service = InferenceService(PARAMS, ARGS, TOK, run_name="tiny")
    # Engine NOT started: submissions stack up in the admission queue so
    # the over-depth rejection is deterministic.
    service.engine = _engine(max_queue=2)
    httpd = serve(service, port=0)
    url = f"http://127.0.0.1:{httpd.server_address[1]}"
    try:
        # fill the queue without the engine draining it
        for i in range(2):
            service.engine.submit(f"fill {i}", max_tokens=4)
        with pytest.raises(urllib.error.HTTPError) as exc:
            _post(url, {"prompt": "overflow", "max_tokens": 4}, timeout=60.0)
        assert exc.value.code == 429
        assert service.engine.metrics()["rejected"] == 1
        # start the engine: the queued fills drain and new requests serve
        service.engine.start()
        status, out = _post(url, {"prompt": "after drain", "max_tokens": 4})
        assert status == 200 and out["engine"] == "batch"
        assert out["finish_reason"] in ("stop", "length")
        # health/metrics surfaces the engine
        with urllib.request.urlopen(url + "/healthz", timeout=30) as resp:
            h = json.loads(resp.read())
        assert h["engine"] == "batch" and "serve" in h
        with urllib.request.urlopen(url + "/metrics", timeout=30) as resp:
            m = json.loads(resp.read())
        assert m["num_slots"] == 2
    finally:
        httpd.shutdown()
        httpd.server_close()
        service.close()


# -- paged pool ---------------------------------------------------------------

def test_paged_pool_block_alloc_free_reuse_invariants():
    pool = PagedKVPool(ARGS, num_seqs=2, max_len=MAX_LEN, block_size=32,
                       num_blocks=6)
    assert pool.max_blocks == 4 and pool.capacity == MAX_LEN - 1
    # arena holds num_blocks + 1 buffers: block 0 is the reserved junk block
    assert pool.cache[0]["k"].shape[0] == 7
    assert pool.blocks_for(0) == 0 and pool.blocks_for(1) == 1
    assert pool.blocks_for(32) == 1 and pool.blocks_for(33) == 2
    s0 = pool.allocate(40)  # 2 blocks
    assert s0 is not None and pool.blocks_in_use == 2
    assert sorted(set(pool.tables[s0][:2])) != [0]  # mapped, non-junk
    assert all(b == 0 for b in pool.tables[s0][2:])  # tail unmapped -> junk
    # on-demand growth maps exactly the missing blocks
    assert pool.ensure_capacity(s0, 65)  # 3 blocks
    assert pool.blocks_in_use == 3
    assert pool.ensure_capacity(s0, 65)  # idempotent
    assert pool.blocks_in_use == 3
    s1 = pool.allocate(96)  # 3 blocks -> arena full (6/6)
    assert s1 is not None and pool.free_blocks == 0
    # exhaustion: growth refused with NO state change
    assert not pool.ensure_capacity(s0, 100)
    assert pool.blocks_in_use == 6
    # beyond the table extent is always refused
    assert not pool.ensure_capacity(s0, MAX_LEN + 1)
    pool.free(s0)
    assert pool.free_blocks == 3 and all(b == 0 for b in pool.tables[s0])
    with pytest.raises(ValueError):
        pool.free(s0)  # double free
    # freed blocks are reusable; allocation still honours the arena bound
    assert pool.allocate(MAX_LEN) is None  # 4 blocks > 3 free
    s2 = pool.allocate(96)
    assert s2 == s0 and pool.lengths[s2] == 0
    # watermark saw the full-arena moment; fragmentation counts slack
    assert pool.read_watermark() == 0
    assert pool.read_watermark() == 0  # reset to current free level
    pool.lengths[s1] = 65  # 3 blocks mapped, 96 positions, 65 live
    pool.lengths[s2] = 96
    frag = pool.fragmentation()
    assert 0.0 < frag < 1.0 and abs(frag - (1 - 161 / 192)) < 1e-9
    pool.reset()
    assert pool.free_blocks == 6 and pool.num_free == 2
    # int8 arena builds the quantized quartet per layer
    qpool = PagedKVPool(ARGS, num_seqs=2, max_len=MAX_LEN, quantize=True)
    assert "k_q" in qpool.cache[0] and "k" not in qpool.cache[0]
    with pytest.raises(ValueError):
        PagedKVPool(ARGS, num_seqs=1, max_len=MAX_LEN, block_size=24)
    with pytest.raises(ValueError):
        PagedKVPool(ARGS, num_seqs=1, max_len=100, block_size=32)


def test_paged_admission_gated_on_free_blocks():
    # 3 blocks of 32: two 40-token prompts (2 blocks each) cannot both be
    # admitted even though batch rows are free.
    pool = PagedKVPool(ARGS, num_seqs=2, max_len=MAX_LEN, block_size=32,
                       num_blocks=3)
    sched = Scheduler(max_queue=4)
    r0 = Request(list(range(40)), max_tokens=4)
    r1 = Request(list(range(40)), max_tokens=4)
    sched.submit(r0)
    sched.submit(r1)
    admitted = sched.admit(pool)
    assert [r.id for r in admitted] == [r0.id]  # head admitted, FIFO kept
    assert sched.queue_depth() == 1 and pool.num_free == 1
    # finishing the head releases its blocks; the waiter admits next round
    sched.finish(pool, r0, "stop")
    assert [r.id for r in sched.admit(pool)] == [r1.id]


def test_engine_429_when_blocks_exhausted_backs_up_queue():
    # Arena sized so ONE request's prompt occupies every block: the second
    # waits in the queue and the third submission overflows -> 429 path.
    eng = _engine(num_blocks=2, block_size=32, max_queue=1)
    ids = list(range(50))  # 2 blocks
    eng._submit_ids(ids, max_tokens=4, temperature=0.0, seed=0)
    eng.scheduler.admit(eng.pool)
    assert eng.pool.free_blocks == 0
    eng._submit_ids(ids, max_tokens=4, temperature=0.0, seed=0)
    assert eng.scheduler.admit(eng.pool) == []  # blocks exhausted: waits
    with pytest.raises(QueueFullError):
        eng._submit_ids(ids, max_tokens=4, temperature=0.0, seed=0)
    assert eng.metrics()["rejected"] == 1


# -- paged engine parity ------------------------------------------------------

def _collect(eng, prompts, max_tokens=40, **gen_kw):
    eng.start()
    outs = [None] * len(prompts)
    try:
        def run(i):
            outs[i] = eng.generate(prompts[i], max_tokens=max_tokens,
                                   timeout=300.0, **gen_kw)

        threads = [threading.Thread(target=run, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        metrics = eng.metrics()
    finally:
        eng.stop()
    return outs, metrics


PARITY_PROMPTS = ["the quick brown fox", "pack my box with", "a b c a b c a",
                  "hello world hello world hello", "zzz"]


def test_paged_vs_slotted_greedy_parity():
    # Token-for-token identity under concurrency: mixed-length prompts,
    # generations long enough to cross several block boundaries.
    slotted, _ = _collect(_engine(kv_backend="slotted", num_slots=3),
                          PARITY_PROMPTS, temperature=0.0)
    paged, _ = _collect(_engine(kv_backend="paged", num_slots=3,
                                block_size=16), PARITY_PROMPTS,
                        temperature=0.0)
    for s, p in zip(slotted, paged):
        assert p["text"] == s["text"]
        assert p["tokens"] == s["tokens"]
        assert p["finish_reason"] == s["finish_reason"]


def test_paged_int8_roundtrip_parity_with_slotted_int8():
    slotted, _ = _collect(_engine(kv_backend="slotted", kv_quant=True),
                          PARITY_PROMPTS[:2], temperature=0.0)
    eng = _engine(kv_backend="paged", kv_quant=True)
    assert "k_q" in eng.pool.cache[0]
    paged, _ = _collect(eng, PARITY_PROMPTS[:2], temperature=0.0)
    for s, p in zip(slotted, paged):
        assert p["text"] == s["text"]


def test_batched_spec_matches_single_stream_spec_greedy():
    from mlx_cuda_distributed_pretraining_tpu.infer.generate import (
        generate_speculative,
    )

    # Repetitive prompts so prompt-lookup actually lands acceptances.
    prompts = ["a b c a b c a b", "the cat and the cat and the"]
    singles = []
    for p in prompts:
        ids = [TOK.bos_id] + TOK.tokenize(p)
        toks, stats = generate_speculative(
            PARAMS, ARGS, ids, max_tokens=32, draft_len=4, max_ngram=3,
            stop_tokens=[TOK.eos_id], temperature=0.0)
        singles.append(TOK.detokenize(toks))
    outs, m = _collect(_engine(spec_draft_len=4, spec_max_ngram=3),
                       prompts, max_tokens=32, temperature=0.0)
    for single, out in zip(singles, outs):
        assert out["text"] == single
    assert m["spec_proposed"] > 0
    assert 0 < m["spec_accepted"] <= m["spec_proposed"]
    assert m["spec_acceptance_rate"] > 0.0


def test_batched_spec_sampled_still_terminates_and_counts():
    outs, m = _collect(_engine(spec_draft_len=3), PARITY_PROMPTS[:3],
                       max_tokens=8, temperature=0.7)
    assert all(o is not None and 0 < o["tokens"] <= 8 for o in outs)
    assert m["spec_proposed"] >= m["spec_accepted"] >= 0


def test_paged_preemption_recompute_keeps_greedy_output():
    # Arena deliberately too small for both sequences at full length
    # (2 rows x up to 3 blocks needed, 4 blocks total): the younger
    # request must be preempted and recomputed, with identical output.
    reference, _ = _collect(_engine(num_slots=2), PARITY_PROMPTS[:2],
                            max_tokens=60, temperature=0.0)
    tight, m = _collect(_engine(num_slots=2, num_blocks=4, block_size=32),
                        PARITY_PROMPTS[:2], max_tokens=60, temperature=0.0)
    for ref, out in zip(reference, tight):
        assert out["text"] == ref["text"]
        assert out["tokens"] == ref["tokens"]
    assert m["preempted"] >= 1
    assert m["kv_blocks_used"] == 0 and m["kv_blocks_free"] == 4


# -- a decode step writes the rows that decode and the junk block --------------

def _ids(seed, n, first=None):
    """``n`` byte ids behind BOS, none of them 0 (the token a row that sits a
    step out rides with), a seed's own unless ``first`` gives the leading ones."""
    rng = np.random.default_rng(seed)
    ids = [TOK.bos_id] + [int(t) for t in rng.integers(1, 256, n - 1)]
    if first is not None:
        ids[:len(first)] = first
    return ids


def _drive(eng, reqs, until=None):
    """The engine's iterations in the test's own thread (nothing swallows an
    assertion), until ``until()`` holds or every request of ``reqs`` is done."""
    for _ in range(2000):
        if until() if until else all(r.state == "done" for r in reqs):
            return reqs
        eng._iteration()
    raise AssertionError("the engine did not get there in 2000 iterations")


def _alone(ids, max_tokens, **kw):
    eng = _engine(**kw)
    return _drive(eng, [eng._submit_ids(ids, max_tokens, 0.0, 0)])[0]


def _assert_streams_alike(got, want):
    assert got.error is None and got.finish_reason == want.finish_reason
    assert got.tokens == want.tokens
    np.testing.assert_allclose(got.logprobs, want.logprobs, rtol=0, atol=1e-5)


@pytest.mark.parametrize("draft_len", [0, 2])
def test_decode_iteration_leaves_every_other_rows_blocks_bit_for_bit(draft_len):
    eng = _engine(num_slots=4, block_size=16, spec_draft_len=draft_len)
    pool, seen, beside_prefill = eng.pool, {}, []
    grow, decode = eng._grow_or_preempt, eng._decode

    def grow_then_snapshot(dec, S):
        # the last place a table changes before the step is dispatched
        dec = grow(dec, S)
        seen["may_write"] = {0} | {int(b) for r in dec for b in pool.tables[r.slot]}
        seen["arena"] = jax.tree_util.tree_map(np.array, pool.cache)
        return dec

    def decode_then_compare(dec):
        between_chunks = any(r.state == "prefill" and r.prefilled
                             for r in eng.scheduler.running.values())
        decode(dec)
        kept = [b for b in range(pool.num_blocks + 1) if b not in seen["may_write"]]
        for before, after in zip(jax.tree_util.tree_leaves(seen.pop("arena")),
                                 jax.tree_util.tree_leaves(pool.cache)):
            np.testing.assert_array_equal(np.asarray(after)[kept], before[kept])
        beside_prefill.append(between_chunks)

    eng._grow_or_preempt, eng._decode = grow_then_snapshot, decode_then_compare
    lengths = [5, 44, 9, 58, 7, 51, 12, 40]
    reqs = _drive(eng, [eng._submit_ids(_ids(i, n), 12, 0.0, i)
                        for i, n in enumerate(lengths)])
    assert all(r.error is None and r.finish_reason in ("length", "stop") for r in reqs)
    # the case at stake was walked: rows decoded while another sat between two chunks
    assert sum(beside_prefill) >= 4


def test_multi_chunk_prompt_in_a_crowd_streams_what_it_streams_alone():
    long_ids = _ids(100, 56)  # four chunks of 16
    want = _alone(long_ids, 16, num_slots=6)
    eng = _engine(num_slots=6)
    crowd = [eng._submit_ids(_ids(i, 6 + i), 40, 0.0, i) for i in range(5)]
    _drive(eng, crowd, until=lambda: all(r.state == "decode" for r in crowd))
    got = eng._submit_ids(long_ids, 16, 0.0, 0)
    _drive(eng, [got])
    assert all(r.state in ("decode", "done") and r.error is None for r in crowd)
    _assert_streams_alike(got, want)


def test_shared_first_block_survives_a_prefill_beside_its_owners_decode():
    head = _ids(200, 16)  # one whole block of 16, BOS first
    a_ids, b_ids = _ids(201, 50, first=head), _ids(202, 56, first=head)
    kw = dict(num_slots=3, block_size=16, prefix_cache=True)
    want_a, want_b = _alone(a_ids, 24, **kw), _alone(b_ids, 8, **kw)
    eng = _engine(**kw)
    a = eng._submit_ids(a_ids, 24, 0.0, 0)
    _drive(eng, [a], until=lambda: a.state == "decode")
    shared = int(eng.pool.tables[a.slot][0])
    before = [np.array(leaf[shared]) for leaf in jax.tree_util.tree_leaves(eng.pool.cache)]
    b = eng._submit_ids(b_ids, 8, 0.0, 0)
    _drive(eng, [b], until=lambda: b.state == "prefill" and b.prefilled > 16)
    # b adopted a's first block and is between two chunks while a decodes
    assert b.cached_tokens == 16 and int(eng.pool.tables[b.slot][0]) == shared
    assert a.state == "decode"
    _drive(eng, [a, b])
    for was, leaf in zip(before, jax.tree_util.tree_leaves(eng.pool.cache)):
        np.testing.assert_array_equal(np.asarray(leaf[shared]), was)
    _assert_streams_alike(a, want_a)
    _assert_streams_alike(b, want_b)


def test_tables_for_hands_out_the_named_rows_and_junk_for_the_rest():
    pool = PagedKVPool(ARGS, num_seqs=4, max_len=MAX_LEN, block_size=16,
                       num_blocks=16)
    a, b, c = (pool.allocate(n) for n in (40, 20, 33))
    (free,) = set(range(4)) - {a, b, c}
    own = pool.tables.copy()
    assert own[b].any()  # b holds blocks, and sits this step out
    got = pool.tables_for([a, c])
    assert got.shape == own.shape and got.dtype == own.dtype
    assert (got[[a, c]] == own[[a, c]]).all() and got[a].any() and got[c].any()
    assert not got[[b, free]].any()
    assert not pool.tables_for([]).any()
    got[:] = 7  # the step's copy, never the pool's own
    assert (pool.tables == own).all()
    assert not np.shares_memory(pool.tables_for([a, b, c]), pool.tables)


def test_paged_holds_twice_the_sequences_of_worst_case_rows():
    # One KV budget, 2,048 cache positions, spent two ways: 8 rows sized
    # for the worst case (8 x 256), or 64 blocks of 32 behind 24 lanes. A
    # flood of 24 short-skewed requests: rows admit 8 at a time whatever
    # their lengths; blocks admit by actual length, so at least twice as
    # many run at once. Same greedy tokens either way.
    import dataclasses

    import numpy as np

    max_len, budget, block, new = 256, 2048, 32, 32
    args = dataclasses.replace(ARGS, max_position_embeddings=max_len)
    rng = np.random.default_rng(0)
    lens = [16, 24, 32, 48, 16, 80, 24, 32] * 3
    prompts = [rng.integers(2, ARGS.vocab_size, size=n).tolist() for n in lens]

    def flood(**kw):
        eng = BatchEngine(PARAMS, args, TOK, EngineConfig(
            max_len=max_len, prefill_chunk=64, max_queue=64, **kw))
        # The peak falls right after an admission: read it there, on the
        # engine's own thread, not from a poller that can miss it.
        peak, allocate = [0], eng.pool.allocate

        def counting_allocate(*a, **k):
            got = allocate(*a, **k)
            peak[0] = max(peak[0], eng.pool.num_used)
            return got

        eng.pool.allocate = counting_allocate
        eng.start()
        try:
            reqs = [eng._submit_ids(ids, new, 0.0, 0) for ids in prompts]
            assert all(r.wait(300.0) for r in reqs)
            metrics = eng.metrics()
        finally:
            eng.stop()
        assert all(r.error is None for r in reqs)
        return peak[0], [r.tokens for r in reqs], metrics

    rows_peak, rows_tokens, _ = flood(
        kv_backend="slotted", num_slots=budget // max_len)
    paged_peak, paged_tokens, m = flood(
        kv_backend="paged", num_slots=len(prompts), block_size=block,
        num_blocks=budget // block)
    assert rows_peak == budget // max_len == 8
    assert paged_peak >= 2 * rows_peak, (paged_peak, rows_peak)
    assert m["preempted"] == 0 and m["kv_blocks_used"] == 0
    assert paged_tokens == rows_tokens
    assert all(len(t) > 0 for t in paged_tokens)


def test_moe_model_batch_engine_greedy_matches_generate_text(no_mesh_left_behind):
    # (no_mesh_left_behind: under a mesh another file's Trainer left in its worker the expert
    # layer takes its shard_map form and refuses a batch of one.)
    # The batch engine's step shares moe_block with training: a MoE
    # checkpoint must greedy-decode under --engine batch token-for-token
    # with the single-stream locked path (grouped dispatch is dropless and
    # deterministic, so decode-time routing is capacity-independent).
    import dataclasses

    margs = dataclasses.replace(
        ARGS, num_local_experts=4, num_experts_per_tok=2,
        moe_aux_weight=0.01, router_z_weight=0.001)
    mparams = llama.init_params(jax.random.PRNGKey(1), margs)
    prompts = PARITY_PROMPTS[:3]
    singles = [
        generate_text(mparams, margs, TOK, p, max_new_tokens=16,
                      temperature=0.0)
        for p in prompts
    ]
    cfg = EngineConfig(num_slots=3, max_len=MAX_LEN, prefill_chunk=16)
    eng = BatchEngine(mparams, margs, TOK, cfg)
    outs, _ = _collect(eng, prompts, max_tokens=16, temperature=0.0)
    for ref, out in zip(singles, outs):
        assert out["text"] == ref
        assert out["finish_reason"] in ("length", "stop")


def test_server_locked_path_unchanged_and_reshaping_knobs_fall_back():
    service = InferenceService(PARAMS, ARGS, TOK, run_name="tiny")
    service.engine = _engine().start()
    try:
        # top_p reshapes logits -> served by the locked path even with the
        # engine attached (the batched step samples by temperature only)
        out = service.generate("abc", max_tokens=4, temperature=0.8,
                               top_p=0.9)
        assert "engine" not in out and "speculative" in out
        out2 = service.generate("abc", max_tokens=4)
        assert out2["engine"] == "batch"
    finally:
        service.close()
    # without an engine, health keeps the pre-engine shape
    plain = InferenceService(PARAMS, ARGS, TOK, run_name="tiny")
    assert "engine" not in plain.health()
    assert plain.metrics() == {"engine": "locked", "role": "any",
                               "draining": False}
