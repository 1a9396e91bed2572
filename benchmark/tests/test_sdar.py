"""The plain reference of ``architecture: sdar_moe``, the count functions, the
cell's files and the readers this configuration brought, at a tiny size. (The
reference imports nothing of the program; the tests that compare them import
both.) Not tier-1: ``tests/test_sdar.py`` holds the program to the reference
there."""

import importlib.util
import itertools
import json
import os
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import run as harness
from benchmark.flops import flash_blockdiff, sdar_moe as flops
from benchmark.reference import sdar_moe as ref

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "sdar-30b-a3b-ep8.train-bd8k"
with open(os.path.join(ROOT, "benchmark/configs/sdar-30b-a3b-ep8.json")) as f:
    FULL = json.load(f)
with open(os.path.join(ROOT, "benchmark/rehearse_sdar.json")) as f:
    TINY = json.load(f)
CFG = harness.merge_into(FULL, TINY["config"])
READERS = ("kernel_peak_pct.bd_flash_fwd", "kernel_peak_pct.bd_flash_bwd",
           "step_device_ms.bd_rows", "bd_live_tile_pct", "bd_loss_row_pct")


def _handed(B=2, L=64, seed=0):
    """A batch as the reference takes it: [noised copy, clean copy] and the weights."""
    rng = np.random.default_rng(seed)
    clean = rng.integers(3, CFG["mask_token_id"], size=(B, L))
    rate = np.repeat(1e-3 + (1 - 1e-3) * rng.random((B, L // 4)), 4, axis=1)
    replaced = rng.random((B, L)) < rate
    return (jnp.asarray(np.stack([np.where(replaced, CFG["mask_token_id"], clean), clean], 1), jnp.int32),
            jnp.asarray(replaced / rate, jnp.float32))


def test_the_cells_files_load():
    bench, cell, config, mix = harness.load_cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("sdar-30b-a3b-ep8",
                                                                 "pack8k-blockdiff", 1)
    assert mix["kind"] == "train_job_sdar" and (mix["seq_len"], mix["batch_size"]) == (8192, 1)
    assert set(cell["limits"]) == {"loss_gap", "first_grad_norm_gap", "first_grad_profile_gap",
                                   "param_change_gap", "unrouted_grad_norm_gap",
                                   "unrouted_grad_profile_gap"}
    assert config["architecture"] == "sdar_moe" and config["mask_token_id"] == config["vocab_size"] - 1
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert entry["source"] == config["source"] and set(entry["reduced"]) == set(config["reduced"])
    for m in bench["per_layer"]:
        if CELL in m.get("workloads", ()):
            assert os.path.isfile(os.path.join(ROOT, "benchmark/layer_metrics", m["name"] + ".py"))


def test_weights_depend_on_seed_only_and_count_as_the_flops_module_says():
    a, b, c = ref.init_params(7, CFG), ref.init_params(7, CFG), ref.init_params(2 ** 31 + 9, CFG)
    la, lb, lc = (jax.tree_util.tree_leaves(t) for t in (a, b, c))
    assert all(np.array_equal(x, y) for x, y in zip(la, lb))
    assert any(not np.array_equal(x, y) for x, y in zip(la, lc))
    assert sum(int(x.size) for x in la) == flops.total_params(CFG)
    shapes = jax.tree_util.tree_leaves(ref.param_shapes(CFG), is_leaf=ref._is_spec)
    assert [tuple(x.shape) for x in la] == [s for s, _ in shapes]
    assert "shared" not in a["layers"][0]["feed_forward"] and "bias" not in a["layers"][0]["feed_forward"]["router"]


def test_the_program_initialises_the_same_tree():
    from benchmark.traffic_kinds import train_job_sdar as kind
    from mlx_cuda_distributed_pretraining_tpu.config import Config
    from mlx_cuda_distributed_pretraining_tpu.models import sdar

    model = kind.arch.MODEL_SECTIONS["sdar_moe"](CFG, {"attention_type": "simple"})
    args = sdar.SdarArgs.from_config(Config.from_dict({"name": "t", "model": model}).model,
                                     CFG["vocab_size"])
    mine = jax.eval_shape(lambda: sdar.init_params(jax.random.PRNGKey(0), args))
    theirs = ref.init_params(7, CFG)
    assert jax.tree_util.tree_structure(mine) == jax.tree_util.tree_structure(theirs)
    assert sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(mine)) == flops.total_params(CFG)
    inputs, _ = _handed()
    got, _ = sdar.forward(theirs, inputs[:, 1], args, noised_tokens=inputs[:, 0])
    assert float(jnp.max(jnp.abs(got - ref.logits_at(theirs, inputs, CFG)))) < 2e-5


def test_the_loss_is_the_weighted_cross_entropy_of_the_noised_rows():
    params = ref.init_params(11, CFG)
    inputs, weights = _handed()
    logp = jax.nn.log_softmax(ref.logits_at(params, inputs, CFG))
    nll = -(weights * jnp.take_along_axis(logp, inputs[:, 1][..., None], -1)[..., 0]).sum() / weights.size
    assert float(ref.loss(params, inputs, weights, CFG)) == pytest.approx(float(nll), rel=1e-5)
    (value,), grads = ref.loss_and_grads(params, inputs, weights, CFG)
    assert float(value) == pytest.approx(float(nll), rel=1e-5)
    assert all(float(jnp.linalg.norm(g)) > 0 for g in jax.tree_util.tree_leaves(grads))
    # attention in blocks of query rows is attention on the whole square
    whole = ref.logits_at(params, inputs, CFG)
    old, ref.ATTN_BLOCK = ref.ATTN_BLOCK, 32
    try:
        np.testing.assert_allclose(ref.logits_at(params, inputs, CFG), whole, atol=2e-5)
    finally:
        ref.ATTN_BLOCK = old


def test_blockdiff_pairs_are_the_masks_sum():
    for L, Bp in ((24, 4), (64, 8), (32, 32), (40, 1)):
        r, c = np.arange(2 * L)[:, None], np.arange(2 * L)[None, :]
        assert int(np.asarray(ref.seen(r, c, L, Bp)).sum()) == flash_blockdiff.pairs(L, Bp) \
            == L * L + L * Bp
    assert flash_blockdiff.bwd_dq(1, 32, 16384, 128, 4) / flash_blockdiff.fwd(1, 32, 16384, 128, 4) == 1.5
    assert flash_blockdiff.bwd_dkv(1, 32, 16384, 128, 4) / flash_blockdiff.fwd(1, 32, 16384, 128, 4) == 2.0
    assert flash_blockdiff.fwd(1, 32, 16384, 128, 4) == 4.0 * 32 * (8192 * 8192 + 8192 * 4) * 128
    with pytest.raises(ValueError):
        flash_blockdiff.pairs(10, 4)


def test_count_functions():
    assert flops.layer_params(FULL) == 94_638_336
    assert flops.total_params(FULL) == FULL["num_hidden_layers"] * 94_638_336 + 77_791_232 + 2048
    S = 8192
    assert flops.train_flops_per_token(FULL, S) == pytest.approx(
        6 * flops.matmul_params(FULL) + 12.0 * 32 * 128 * FULL["num_hidden_layers"] * (S + 4))
    assert flops.uniform_held_experts_per_token(FULL) == 2.0 and flops.routed_layers(FULL) == FULL["num_hidden_layers"]


@pytest.mark.parametrize("precision", ["fp8"])  # bfloat16: the CPU backend has no such dot here
def test_lower_precisions_differ_from_the_reference(precision):
    params = ref.init_params(11, CFG)
    inputs, _ = _handed()
    want = ref.logits_at(params, inputs, CFG)
    got = ref.logits_at(params, inputs, CFG, precision)
    gap = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
    assert 1e-4 < gap < 0.2, gap


def _reader(name):
    readers = os.path.join(ROOT, "benchmark", "layer_metrics")
    if readers not in sys.path:
        sys.path.insert(0, readers)
    spec = importlib.util.spec_from_file_location("m_" + name.replace(".", "_"),
                                                  os.path.join(readers, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@pytest.mark.parametrize("name", READERS)
def test_every_new_reader_returns_none_on_empty_sources(name, tmp_path):
    read = _reader(name)
    assert read({}) is None
    assert read({"step_window_events": [], "trace_dir": None, "peaks": None}) is None
    # a trace directory with nothing in it, events of a program without the counters (the parent)
    peaks = {"bf16_flops": 197e12}
    assert read({"step_window_events": [{"steps": 1, "toks": 8192, "moe_rows_held": 9}],
                 "trace_dir": str(tmp_path), "peaks": peaks, "block_length": 4}) is None


def test_the_traffic_kind_rehearses(tmp_path, monkeypatch):
    from benchmark.traffic_kinds import train_job_sdar as kind

    ticks = itertools.count()
    monkeypatch.setattr(kind.arch.base, "time", types.SimpleNamespace(
        perf_counter=lambda: 0.008 * next(ticks)))
    _, cell, config, mix = harness.load_cell(CELL)
    config, mix = harness.merge_into(config, TINY["config"]), harness.merge_into(mix, TINY["traffic"])
    cell = dict(cell, limits={k: 0.05 for k in cell["limits"]})
    ctx = harness.Context(cell, config, mix, seed=3_000_000_019, seconds=1.5, trace=False,
                          rehearse=True, workdir=str(tmp_path), quiet=True)
    res = kind.run(ctx)
    assert res["correct"], res["check_numbers"]
    assert res["sources"]["block_length"] == 4
    assert all("bd_loss_rows" in e for e in res["sources"]["step_window_events"])
