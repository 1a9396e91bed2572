"""The copied and the new arithmetic: percentiles, schedules, length draws,
FLOPs per token, interval unions."""

import json
import os

import numpy as np
import pytest

from benchmark import loadgen, synthetic, trace_reduce
from benchmark.flops import llama_dense as flops

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)


def _cfg(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def _mix(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


def test_percentile_is_nearest_rank_like_load_gen():
    vals = list(range(1, 101))
    assert loadgen.percentile(vals, 0.5) == 51
    assert loadgen.percentile(vals, 0.95) == 96
    assert loadgen.percentile(vals, 0.999) == 100
    assert loadgen.percentile([7.0], 0.95) == 7.0
    assert loadgen.percentile([], 0.95) is None


@pytest.mark.parametrize("n,want", [(10, 0.0), (20, 0.0), (100, 0.9), (200, 0.95), (1000, 0.99)])
def test_highest_percentile_a_sample_supports(n, want):
    assert loadgen.highest_supported_percentile(n) == pytest.approx(want)


def test_inter_token_gaps_pool_over_requests():
    recs = [{"token_times": [0.0, 0.010, 0.030]}, {"token_times": [1.0]},
            {"token_times": [2.0, 2.005]}]
    assert loadgen.inter_token_gaps_ms(recs) == pytest.approx([10.0, 20.0, 5.0])


def test_open_loop_schedule_keeps_rate_and_set_across_seeds():
    mix = _mix("chat-open-0.8knee")
    a = synthetic.poisson_arrivals(mix, 40.0, seed=1)
    b = synthetic.poisson_arrivals(mix, 40.0, seed=2 ** 31 + 5)
    span = 40.0 + mix["ramp_s"]
    assert len(a) == len(b) == round(mix["rate_per_s"] * span)
    assert a[-1] < span and b[-1] < span and np.all(np.diff(a) > 0)
    # the same set of gaps in another order: equal multisets, different sequence
    assert np.allclose(np.sort(np.diff(a, prepend=0)), np.sort(np.diff(b, prepend=0)))
    assert not np.allclose(np.diff(a, prepend=0), np.diff(b, prepend=0))
    assert np.array_equal(a, synthetic.poisson_arrivals(mix, 40.0, seed=1))


def test_requests_same_shapes_other_order_other_ids():
    mix = dict(_mix("chat-open-0.8knee"), num_requests=200)
    a = synthetic.serve_requests(mix, 92544, seed=3)
    b = synthetic.serve_requests(mix, 92544, seed=4)
    shape = lambda rs: sorted((r["prompt_tokens"], r["max_tokens"]) for r in rs)
    assert shape(a) == shape(b)
    assert [r["prompt_tokens"] for r in a] != [r["prompt_tokens"] for r in b]
    assert a == synthetic.serve_requests(mix, 92544, seed=3)
    p = mix["prompt_tokens"]
    assert all(p["min"] <= r["prompt_tokens"] <= p["max"] for r in a)
    assert all(len(r["prompt_ids"]) == r["prompt_tokens"] - 1 for r in a)  # BOS is the program's
    assert all(3 <= t < 92544 for r in a for t in r["prompt_ids"])
    # the program is handed token ids as text, never the seed
    tok = synthetic.IdTokenizer(92544)
    assert tok.tokenize(synthetic.prompt_text(a[0]["prompt_ids"])) == a[0]["prompt_ids"]
    assert tok.eos_id == 92544  # no logit column: greedy decoding cannot stop early


def test_token_shards_load_in_the_program(tmp_path):
    from mlx_cuda_distributed_pretraining_tpu.data.token_shards import TokenShardDataManager

    job = dict(_mix("pack4k-b4"), seq_len=64, batch_size=4,
               documents={"median": 20, "sigma": 1.0, "min": 4, "max": 64, "zipf_exponent": 1.1})
    info = synthetic.write_token_shards(job, 70000, 9, str(tmp_path), steps=6)
    dm = TokenShardDataManager(str(tmp_path), 4, 64, seed=9)
    assert dm.index["vocab_size"] == 70000 and dm.index["dtype"] == "uint32"
    assert dm.batches_per_epoch >= 6
    b = dm.generate_batch(0)
    assert b["inputs"].shape == (4, 64) and b["inputs"].max() < 70000
    rows = {tuple(r) for r in b["inputs"]}
    assert len(rows) == 4  # rows all differ
    assert (dm.tokens == job["eos_id"]).sum() >= info["documents"] - 2


@pytest.mark.parametrize("name,params,gflops", [
    ("internlm2-1_8b", 1_889_110_016, 11.404836864),
    ("mistral-7b-v0_3-l4", 1_140_887_552, 6.442450944),
])
def test_flops_per_token_against_hand_count(name, params, gflops):
    cfg = _cfg(name)
    assert flops.total_params(cfg) == params
    # 6 * (weights a token is multiplied by, no input table) + 6 * L * S * heads * head_dim
    assert flops.train_flops_per_token(cfg, 4096) / 1e9 == pytest.approx(gflops, rel=1e-9)
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    assert flops.matmul_params(cfg) == params - v * d - (2 * cfg["num_hidden_layers"] + 1) * d


def test_mistral_at_published_depth_counts_7_248b():
    assert flops.total_params(dict(_cfg("mistral-7b-v0_3-l4"), num_hidden_layers=32)) \
        == 7_248_023_552


def test_interval_arithmetic():
    assert trace_reduce.merge([(0, 2), (1, 3), (5, 6), (6, 6)]) == [(0, 3), (5, 6)]
    assert trace_reduce.total([(0, 3), (5, 6)]) == 4
    assert trace_reduce.subtract([(0, 10)], [(2, 3), (5, 7)]) == [(0, 2), (3, 5), (7, 10)]
    assert trace_reduce.subtract([(0, 1), (4, 6)], [(0, 5)]) == [(5, 6)]
    assert trace_reduce.base_name("%fusion.123 = f32[] fusion(...)") == "fusion"
    assert trace_reduce.is_collective("all-gather-start.4") and not trace_reduce.is_collective("fusion.1")


def test_reduce_events_busy_idle_and_exposed_collectives():
    ev = {"devices": {"/device:TPU:0": [("fusion.1", 0.0, 1.0), ("all-gather.2", 0.5, 2.0),
                                         ("fusion.3", 3.0, 4.0)],
                      "/device:TPU:1": [("fusion.1", 0.0, 4.0)]},
          "steps": {"/device:TPU:0": [("0", 0.0, 2.0), ("1", 2.0, 4.0)]},
          "host": [("train", 2.0, 3.0)], "device_planes": 2}
    r = trace_reduce.reduce_events(ev)
    assert r["window_s"] == 4.0 and r["busy_s"] == pytest.approx((3.0 + 4.0) / 2)
    assert r["busy_s_per_device"] == pytest.approx([3.0, 4.0])
    assert r["exposed_collective_s"] in (pytest.approx(1.0), pytest.approx(0.0))
    assert r["steps"] == 2
    assert r["idle_gaps"][0][1] == pytest.approx(1.0) and "train" in r["idle_gaps"][0][0]
    assert r["device_ops"][0][0] == "fusion"


def _reader(name):
    import importlib.util
    import sys

    readers = os.path.join(BENCH, "layer_metrics")
    if readers not in sys.path:
        sys.path.insert(0, readers)
    spec = importlib.util.spec_from_file_location("reader_" + name.replace(".", "_"),
                                                  os.path.join(readers, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@pytest.mark.parametrize("name", ["iter_device_ms.steady", "iter_device_ms.closed"])
def test_iteration_time_is_over_the_iterations_counted_between_the_trace_marks(name):
    """The kinds read ``busy_iterations`` at the marks (``iterations`` also
    ticks on idle turns): 2 s busy over 50 of them is 40 ms an iteration."""
    read = _reader(name)
    src = {"trace": {"busy_s": 2.0, "window_s": 4.0}, "trace_iterations": {"start": 950, "stop": 1000}}
    assert read(src) == pytest.approx(40.0)
    assert read(dict(src, trace_iterations={"start": 7, "stop": 7})) is None
    assert read(dict(src, trace=None)) is None


@pytest.mark.parametrize("name", ["generator_lag_p95_ms", "generator_lag_p95_ms.closed",
                                  "batch_occupancy_pct.closed", "kv_blocks_used_peak_pct.closed"])
def test_serving_readers_return_nothing_where_there_is_nothing_to_read(name):
    assert _reader(name)({"window": (0.0, 1.0)}) is None


def test_closed_readers_read_what_the_open_ones_read():
    snaps = [{"t": 0.5, "batch_occupancy": 24, "kv_num_blocks": 100, "kv_blocks_free": 60,
              "kv_free_watermark": 40}]
    src = {"window": (0.0, 1.0), "snapshots": snaps, "num_slots": 32,
           "latency": {"lag_ms": [1.0, 2.0, 3.0]}}
    assert _reader("batch_occupancy_pct.closed")(src) == pytest.approx(75.0)
    assert _reader("kv_blocks_used_peak_pct.closed")(src) == pytest.approx(60.0)
    assert _reader("generator_lag_p95_ms.closed")(src) == 3.0
