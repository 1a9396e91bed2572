"""Architecture ``sdar_moe`` (models/sdar.py) against the benchmark's plain
reference (benchmark/reference/sdar_moe.py, which imports nothing of the
program), at tiny widths on seeded random weights, and the pieces this
architecture brought: the block-diffusion batch and its draw, the doubled rows
under the four-case mask, the softmax router's held share without a shared
expert, the new scopes, counters and readers, and the benchmark's traffic kind.
"""

import dataclasses
import importlib.util
import itertools
import json
import os
import re
import shutil
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import run as harness
from benchmark.flops import flash_blockdiff
from benchmark.flops import sdar_moe as flops
from benchmark.reference import sdar_moe as ref
from benchmark.traffic_kinds import train_job
from benchmark.traffic_kinds import train_job_sdar as kind
from mlx_cuda_distributed_pretraining_tpu.config import Config
from mlx_cuda_distributed_pretraining_tpu.data import block_diffusion as bd
from mlx_cuda_distributed_pretraining_tpu.data.token_shards import TokenShardDataManager
from mlx_cuda_distributed_pretraining_tpu.models import moe as moe_lib
from mlx_cuda_distributed_pretraining_tpu.models import sdar
from mlx_cuda_distributed_pretraining_tpu.models.registry import resolve_architecture
from mlx_cuda_distributed_pretraining_tpu.ops import masks
from mlx_cuda_distributed_pretraining_tpu.ops.attention import core_counts

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "sdar-30b-a3b-ep8.train-bd8k"
B, L = 2, 128


def _load(path):
    with open(os.path.join(REPO, path)) as f:
        return json.load(f)


FULL = _load("benchmark/configs/sdar-30b-a3b-ep8.json")
TINY = _load("benchmark/rehearse_sdar.json")


def _args(cfg, attention_type="simple"):
    model = kind.arch.MODEL_SECTIONS["sdar_moe"](cfg, {"attention_type": attention_type})
    return sdar.SdarArgs.from_config(Config.from_dict({"name": "t", "model": model}).model,
                                     cfg["vocab_size"])


def _noised(cfg, clean, seed=5, index=0):
    batch = {"inputs": clean, "targets": np.roll(clean, -1, axis=1),
             "mask": np.ones(clean.shape, np.float32)}
    return bd.noise_batch(batch, seed, index, cfg["block_length"], cfg["noise_eps"],
                          cfg["mask_token_id"])


@pytest.fixture(scope="module")
def tiny():
    """(configuration at tiny widths, the program's args for it, seeded weights, a noised batch)."""
    cfg = harness.merge_into(FULL, TINY["config"])
    params = ref.init_params(7, cfg)
    clean = np.random.default_rng(0).integers(3, cfg["mask_token_id"], size=(B, L)).astype(np.int32)
    batch = {k: jnp.asarray(v) for k, v in _noised(cfg, clean).items()}
    return cfg, _args(cfg), params, batch


@pytest.fixture(scope="module")
def reference_step(tiny):
    cfg, _, params, batch = tiny
    handed = kind.reference_batch(batch)
    return jax.jit(lambda p: ref.loss_and_grads(p, handed["inputs"], handed["targets"], cfg))(params)


def _leaf_gaps(got, want):
    return {jax.tree_util.keystr(path): float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-12))
            for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                                    jax.tree_util.tree_leaves(want))}


def _flash_blocks(monkeypatch, block=128):
    """The kernels' default blocks fitted to the tiny rows (2 x 128: one tile a copy)."""
    from mlx_cuda_distributed_pretraining_tpu.ops import flash_attention as fa

    monkeypatch.setattr(fa, "_RESIDENT_BLOCKS", (block, block))
    monkeypatch.setattr(fa, "_STREAMED_BLOCKS", (block, block))
    fa._cached_core.cache_clear()


@pytest.mark.parametrize("attention_type", ["simple", "flash"])
@pytest.mark.parametrize("scan_layers", [False, True], ids=["loop", "scan"])
def test_program_matches_reference_loss_and_every_gradient(tiny, reference_step, scan_layers,
                                                           attention_type, monkeypatch):
    cfg, _, params, batch = tiny
    _flash_blocks(monkeypatch)
    (want_loss,), want = reference_step
    args = _args(cfg, attention_type)
    step = lambda p: sdar.loss_fn(p, batch, args, remat="full", scan_layers=scan_layers,
                                  with_moe_stats=True)
    (loss, (count, stats)), got = jax.jit(jax.value_and_grad(step, has_aux=True))(params)
    assert float(count) == B * L
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=2e-6)
    gaps = _leaf_gaps(got, want)
    assert len(gaps) == len(jax.tree_util.tree_leaves(params))
    assert max(gaps.values()) < 5e-4, sorted(gaps.items(), key=lambda kv: -kv[1])[:3]
    # both copies of every row choose their experts in every layer
    assert float(stats["moe_load"].sum()) == 2 * B * L * cfg["num_experts_per_tok"] * len(params["layers"])
    assert float(stats["moe_dropped"]) == 0
    assert float(stats["bd_loss_rows"]) == float((batch["loss_weights"] > 0).sum())
    tiles = {k: int(stats[f"bd_tiles_{k}"]) for k in ("live", "grid", "masked", "narrow")}
    # one tile a copy: the three live ones cut by the mask, none wide enough for squares
    assert tiles == (dict(live=3, grid=4, masked=3, narrow=0) if attention_type == "flash"
                     else dict(live=0, grid=0, masked=0, narrow=0))


def test_program_logits_match_reference(tiny):
    cfg, args, params, batch = tiny
    handed = kind.reference_batch(batch)
    want = ref.logits_at(params, handed["inputs"], cfg)
    got, _ = sdar.forward(params, batch["inputs"], args, noised_tokens=batch["noised_inputs"])
    assert got.shape == (B, L, cfg["vocab_size"])
    np.testing.assert_allclose(got, want, atol=2e-5)
    with pytest.raises(NotImplementedError, match="no cached decode"):
        sdar.forward(params, batch["inputs"], args, cache=[])


def test_a_batch_without_noise_is_refused(tiny):
    _, args, params, batch = tiny
    plain = {k: batch[k] for k in ("inputs", "targets", "mask")}
    with pytest.raises(KeyError, match="block-diffusion batch"):
        sdar.loss_fn(params, plain, args)


@pytest.mark.parametrize("attention_type", ["simple", "flash"])
def test_a_block_sees_its_own_noise_and_the_clean_past_and_nothing_else(tiny, attention_type,
                                                                        monkeypatch):
    """The leak test: block ``b``'s logits are bit-equal when ``x_0`` changes
    in blocks ``>= b`` or ``x_t`` changes outside block ``b``; and they do read
    the clean past and their own noised block."""
    cfg, _, params, batch = tiny
    _flash_blocks(monkeypatch)
    args = _args(cfg, attention_type)
    Bp, b = cfg["block_length"], 9
    own = slice(b * Bp, (b + 1) * Bp)
    logits = jax.jit(lambda clean, noised: sdar.forward(params, clean, args, noised_tokens=noised)[0])
    clean, noised = np.asarray(batch["inputs"]), np.asarray(batch["noised_inputs"])
    base = np.asarray(logits(clean, noised))[:, own]
    other = lambda a: (a + 7) % cfg["mask_token_id"]
    future = clean.copy()
    future[:, b * Bp:] = other(future[:, b * Bp:])
    elsewhere = noised.copy()
    elsewhere[:, :b * Bp], elsewhere[:, (b + 1) * Bp:] = (other(elsewhere[:, :b * Bp]),
                                                          other(elsewhere[:, (b + 1) * Bp:]))
    assert np.array_equal(np.asarray(logits(future, noised))[:, own], base)
    assert np.array_equal(np.asarray(logits(clean, elsewhere))[:, own], base)
    past = clean.copy()
    past[:, (b - 1) * Bp] = other(past[:, (b - 1) * Bp])
    mine = noised.copy()
    mine[:, b * Bp + 1] = other(mine[:, b * Bp + 1])
    for changed in (logits(past, noised), logits(clean, mine)):
        assert not np.array_equal(np.asarray(changed)[:, own], base)


def test_the_mask_is_the_four_cases():
    """``masks.block_diffusion`` materialised, the reference's ``seen`` and the
    cases one row and one key at a time."""
    L_, Bp = 24, 4
    got = np.asarray(masks.materialize_mask(masks.block_diffusion(L_, Bp), 2 * L_, 2 * L_))
    r, c = np.arange(2 * L_)[:, None], np.arange(2 * L_)[None, :]
    assert np.array_equal(got, np.asarray(ref.seen(r, c, L_, Bp)))
    blk = lambda i: (i % L_) // Bp
    for i in range(2 * L_):
        for j in range(2 * L_):
            if i < L_ and j < L_:
                want = blk(j) == blk(i)
            elif i < L_:
                want = blk(j) < blk(i)
            elif j >= L_:
                want = blk(j) <= blk(i)
            else:
                want = False
            assert got[i, j] == want, (i, j)
    assert int(got.sum()) == flash_blockdiff.pairs(L_, Bp) == L_ * L_ + L_ * Bp
    with pytest.raises(ValueError, match="does not divide"):
        masks.block_diffusion(10, 4)


def test_the_eight_shares_add_up_to_the_uncut_layer(tiny):
    """Section 4 of the model-configs guide: the parts of a routed layer's
    result that the 8 shares give (here 4 shares of 2 of 8 experts) add up to
    what the uncut reference layer gives; nothing is computed by every chip
    alike (no shared expert). The program's share is the reference's share."""
    cfg, args, _, _ = tiny
    E, C, Fe = cfg["num_experts"], cfg["hidden_size"], cfg["moe_intermediate_size"]
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    bank = lambda k, shape: jax.random.normal(k, shape, jnp.float32) * 0.05
    whole = {"router": {"weight": bank(ks[0], (C, E))},
             "experts": {"w_gate": {"weight": bank(ks[1], (E, C, Fe))},
                         "w_up": {"weight": bank(ks[2], (E, C, Fe))},
                         "w_down": {"weight": bank(ks[3], (E, Fe, C))}}}
    x = jax.random.normal(ks[4], (B, 2 * L, C), jnp.float32)
    uncut = ref.routed_layer(whole, x, cfg, "float32", first=0, count=E)
    count = cfg["experts_held"]["count"]
    total = jnp.zeros_like(uncut)
    for first in range(0, E, count):
        share = {"router": whole["router"], "experts": jax.tree_util.tree_map(
            lambda a: a[first:first + count], whole["experts"])}
        part = ref.routed_layer(share, x, cfg, "float32", first=first, count=count)
        mine, stats = sdar.routed_ffn(share, x, dataclasses.replace(args, experts_held=(first, count)))
        np.testing.assert_allclose(mine, part, atol=2e-6)
        assert float(stats["moe_load"].sum()) == B * 2 * L * cfg["num_experts_per_tok"]
        total = total + part
    np.testing.assert_allclose(total, uncut, atol=5e-6)
    assert float(jnp.abs(uncut).max()) > 1e-3


def test_softmax_route_renormalises_the_chosen():
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 5, 16))
    router = {"weight": jax.random.normal(jax.random.PRNGKey(1), (16, 8))}
    idx, w, probs = moe_lib.softmax_route(x, router, 3)
    np.testing.assert_allclose(probs.sum(-1), 1.0, rtol=1e-6)
    np.testing.assert_allclose(w.sum(-1), 1.0, rtol=1e-6)
    top = np.sort(np.asarray(probs), -1)[..., ::-1][..., :3]
    np.testing.assert_allclose(np.sort(np.asarray(w), -1)[..., ::-1], top / top.sum(-1, keepdims=True),
                               rtol=1e-6)
    assert np.array_equal(np.sort(np.asarray(idx), -1),
                          np.sort(np.argsort(-np.asarray(probs), -1)[..., :3], -1))


# -- the draw -------------------------------------------------------------------------
def test_the_draw_is_a_function_of_seed_and_index(tiny):
    cfg = tiny[0]
    clean = np.random.default_rng(1).integers(3, cfg["mask_token_id"], size=(4, 4096)).astype(np.int32)
    a, b, c, d = (_noised(cfg, clean, seed, index) for seed, index in ((5, 3), (5, 3), (5, 4), (6, 3)))
    for key in ("noised_inputs", "loss_weights"):
        assert np.array_equal(a[key], b[key])
        assert not np.array_equal(a[key], c[key]) and not np.array_equal(a[key], d[key])
    assert np.array_equal(a["targets"], clean) and np.array_equal(a["inputs"], clean)
    replaced = a["noised_inputs"] == cfg["mask_token_id"]
    assert np.array_equal(replaced, a["loss_weights"] > 0)
    assert np.array_equal(a["noised_inputs"][~replaced], clean[~replaced])
    assert 0.45 < replaced.mean() < 0.55                     # rates uniform on [eps, 1)
    # one rate a block: a replaced position's weight is 1 / t of its block
    Bp = cfg["block_length"]
    weights = a["loss_weights"].reshape(4, -1, Bp)
    rates = np.where(weights > 0, weights, np.nan)
    assert np.nanmax(rates, -1)[~np.isnan(np.nanmax(rates, -1))].min() >= 1.0
    assert np.all((np.nanmax(rates, -1) == np.nanmin(rates, -1)) | np.isnan(np.nanmax(rates, -1)))
    # and a masked-out position (padding) carries no loss
    padded = bd.noise_batch({"inputs": clean, "targets": clean,
                             "mask": np.zeros(clean.shape, np.float32)}, 5, 3, Bp, 1e-3, 511)
    assert not padded["loss_weights"].any()
    with pytest.raises(ValueError, match="does not divide"):
        bd.noise_batch({"inputs": clean[:, :4094], "mask": np.ones((4, 4094))}, 5, 3, Bp, 1e-3, 511)


def test_a_resumed_loader_continues_the_draw(tiny, tmp_path):
    cfg = tiny[0]
    mix = harness.merge_into(_load("benchmark/traffic/pack8k-blockdiff.json"), TINY["traffic"])
    kind.write_shards(kind.synthetic.write_token_shards, cfg["mask_token_id"])(
        mix, cfg["vocab_size"], 11, str(tmp_path), 12)
    assert _load(os.path.join(tmp_path, "index.json"))["vocab_size"] == cfg["vocab_size"]
    loader = lambda: bd.BlockDiffusionBatches(
        TokenShardDataManager(str(tmp_path), 2, 128, seed=9), 9, **_args(cfg).diffusion)
    first = loader()
    run = [first.generate_batch(i) for i in range(6)]
    assert all(int(b["inputs"].max()) < cfg["mask_token_id"] for b in run)   # ids below the MASK id
    resumed = loader()
    resumed.load_state_dict(first.state_dict())
    for i in (4, 5):
        again = resumed.generate_batch(i)
        assert all(np.array_equal(again[k], run[i][k]) for k in again)
    assert not np.array_equal(run[0]["loss_weights"], run[1]["loss_weights"])
    # validation: its own stream, the same noise at every pass
    assert first.has_validation_data and first.batches_per_epoch == first.loader.batches_per_epoch
    (val_a,), (val_b,) = list(first.iter_validation(1)), list(resumed.iter_validation(1))
    assert all(np.array_equal(val_a[k], val_b[k]) for k in val_a)
    assert {"noised_inputs", "loss_weights"} <= set(val_a)


# -- the files, the counts, the readers -------------------------------------------------
def test_configuration_file_keeps_every_published_number():
    published = {"attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
                 "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
                 "max_position_embeddings": 32768, "max_window_layers": 48, "mlp_only_layers": [],
                 "model_type": "sdar_moe", "moe_intermediate_size": 768, "norm_topk_prob": True,
                 "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
                 "num_hidden_layers": 48, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
                 "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
                 "tie_word_embeddings": False, "use_sliding_window": False, "vocab_size": 151936}
    entry = next(c for c in _load("BENCHMARK.json")["configs"] if c["name"] == FULL["name"])
    assert entry["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert set(entry["reduced"]) == set(FULL["reduced"])
    for key, value in published.items():
        if key in ("num_hidden_layers", "vocab_size"):
            assert FULL["published"][key] == value
        else:
            assert FULL[key] == value, key
    assert FULL["num_hidden_layers"] >= 4 and FULL["vocab_size"] == 151936 // 8
    assert FULL["experts_held"] == {"first": 0, "count": 16}
    assert FULL["mask_token_id"] == FULL["vocab_size"] - 1 and FULL["block_length"] == 4
    assert "8 chips" in FULL["deployment"] and FULL["precision"]["control"] == "fp8"
    for key in ("block_length", "noise_schedule", "loss_weight", "no_shift", "mask_token_id",
                "qk_norm", "aux_loss", "rope_convention", "attention_mask", "weights",
                "source_checked"):
        assert FULL["assumed"][key]


def test_parameter_and_flop_arithmetic(tiny):
    cfg, args, params, _ = tiny
    n = sum(int(a.size) for a in jax.tree_util.tree_leaves(params))
    assert n == flops.total_params(cfg)
    shapes = jax.eval_shape(lambda: sdar.init_params(jax.random.PRNGKey(0), args))
    assert jax.tree_util.tree_structure(shapes) == jax.tree_util.tree_structure(params)
    assert all(a.shape == b.shape for a, b in zip(jax.tree_util.tree_leaves(shapes),
                                                  jax.tree_util.tree_leaves(params)))
    # the cell's own numbers (ISSUE 48)
    assert flops.layer_params(FULL) == 94_638_336
    assert flops.total_params(FULL) == FULL["num_hidden_layers"] * 94_638_336 + 2 * 38_895_616 + 2048
    assert flops.uniform_held_experts_per_token(FULL) == 2.0
    assert flash_blockdiff.pairs(8192, 4) == 8192 * 8192 + 8192 * 4
    # the program's count is the benchmark's, a uniform router assumed
    full_args = _args(FULL)
    assert sdar.flops_per_token(full_args, 8192) == pytest.approx(
        flops.train_flops_per_token(FULL, 8192), rel=1e-12)
    # two rows a token through the layers, one through the head
    C = FULL["hidden_size"]
    layer = 2 * (flops.attention_params(flops._sizes(FULL)) + C * 128) + 2.0 * 3 * C * 768
    assert flops.matmul_params(FULL) == FULL["num_hidden_layers"] * layer + C * FULL["vocab_size"]
    assert flops.matmul_params(FULL, 3.0) - flops.matmul_params(FULL) == \
        FULL["num_hidden_layers"] * 3 * C * 768


NEW_READERS = ("kernel_peak_pct.bd_flash_fwd", "kernel_peak_pct.bd_flash_bwd",
               "step_device_ms.bd_rows", "bd_live_tile_pct", "bd_loss_row_pct")


def _read_metric(name, sources):
    readers = os.path.join(REPO, "benchmark", "layer_metrics")
    spec = importlib.util.spec_from_file_location("m_" + name.replace(".", "_"),
                                                  os.path.join(readers, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    if readers not in sys.path:
        sys.path.insert(0, readers)
    spec.loader.exec_module(mod)
    return mod.read(sources)


def test_the_cell_is_declared_with_its_readers():
    bench = _load("BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("sdar-30b-a3b-ep8",
                                                                 "pack8k-blockdiff", 1)
    assert bench["workloads"][4] is cell and len(cell["why"]) <= 200   # the fifth cell
    declared = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_READERS:
        assert declared[name]["workloads"] == [CELL]
        assert declared[name]["moves"] == "train_tokens_per_s_per_chip"
    listed = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", ())}
    assert {"train_mfu_pct", "step_device_ms.attn_core", "step_device_ms.moe", "kernel_peak_pct.gmm",
            "moe_rows_held_per_step", "moe_whole_buffer_chunks_per_step",
            "device_idle_pct.train"} <= listed
    # no layer of this model runs under ``ffn`` (no dense layer, no shared expert)
    assert "step_device_ms.ffn" not in listed
    for name in listed:
        assert os.path.isfile(os.path.join(REPO, "benchmark/layer_metrics", name + ".py"))


def test_new_readers_find_nothing_in_sources_without_their_counters():
    for sources in ({}, {"step_window_events": [{"steps": 1, "toks": 8192}], "peaks": None,
                         "trace_dir": None, "block_length": 4}):
        for name in NEW_READERS:
            assert _read_metric(name, sources) is None, name


def test_counter_readers_read_the_window_events():
    events = [{"steps": 1, "toks": 8192, "bd_loss_rows": 4000, "bd_tiles_live": 288, "bd_tiles_grid": 1024},
              {"steps": 1, "toks": 8192, "bd_loss_rows": 4192, "bd_tiles_live": 288, "bd_tiles_grid": 1024}]
    sources = {"step_window_events": events}
    assert _read_metric("bd_live_tile_pct", sources) == pytest.approx(28.125)
    assert _read_metric("bd_loss_row_pct", sources) == pytest.approx(50.0)


# -- through the trainer ----------------------------------------------------------------
def test_the_cell_rehearses_through_its_traffic_kind(tmp_path, monkeypatch):
    """``run.py --rehearse`` looks a kind up in rehearse.json, which is closed;
    this is the new cell's rehearsal: a Context at tiny widths, the kind's own
    ``run``: Trainer.train() on architecture sdar_moe from a dict config with
    the loader's noise, the window, the events' counters, the reference's three
    steps on the recorded batches' own noise, the comparison."""
    # the window counts steps, not this machine's seconds (tests/test_xing.py has the reason)
    ticks = itertools.count()
    monkeypatch.setattr(kind.arch.base, "time", types.SimpleNamespace(
        perf_counter=lambda: 0.008 * next(ticks)))
    bench, cell, config, mix = harness.load_cell(CELL)
    assert mix["kind"] == "train_job_sdar" and cell["chips"] == 1
    base_mix = _load("benchmark/traffic/pack16k-afmoe.json")
    differs = ("kind", "seq_len", "documents", "assumed")
    assert {k: v for k, v in mix.items() if k not in differs} == \
        {k: v for k, v in base_mix.items() if k not in differs}
    assert mix["seq_len"] == 8192 and mix["documents"] == dict(base_mix["documents"], max=8192)
    config = harness.merge_into(config, TINY["config"])
    mix = harness.merge_into(mix, TINY["traffic"])
    # every number held to 0.05 but the weights' change, which the cell's own limit holds:
    # at these widths the second layer's router reads 0.137 (64 x 8 numbers whose update Adafactor
    # scales from a small gradient; the other seven numbers read 9e-6 to 0.013)
    cell = dict(cell, limits={k: v if k == "param_change_gap" else 0.05
                              for k, v in cell["limits"].items()})
    assert cell["limits"]["param_change_gap"] == 0.3
    ctx = harness.Context(cell, config, mix, seed=3_000_000_019, seconds=1.5, trace=False,
                          rehearse=True, workdir=str(tmp_path), quiet=True)
    res = kind.run(ctx)
    assert res["correct"], res["check_numbers"]
    assert max(v for k, v in res["check_numbers"].items() if k != "param_change_gap") < 0.05
    assert res["sources"]["block_length"] == 4 and res["sources"]["tokens_per_step"] == 2 * 128
    # one loss term a step, three steps; the three worst-leaf gaps; the two over the unrouted leaves
    assert len(res["check_numbers"]) == 3 + 3 + 2
    assert {"unrouted_grad_norm_gap", "unrouted_grad_profile_gap"} <= set(res["check_numbers"])
    assert res["check_numbers"]["unrouted_grad_norm_gap"] <= res["check_numbers"]["first_grad_norm_gap"] * 4
    assert max(v for k, v in res["check_numbers"].items() if k.startswith("loss_gap")) < 1e-3
    events = res["sources"]["step_window_events"]
    assert events and all({"moe_rows_held", "moe_chunks_whole", "moe_drop", "bd_loss_rows",
                           "bd_tiles_live", "bd_tiles_grid", "bd_tiles_masked",
                           "bd_tiles_narrow"} <= set(e) for e in events)
    assert all(e["moe_drop"] == 0 and e["moe_rows_held"] > 0 for e in events)
    assert all(0.3 * e["toks"] < e["bd_loss_rows"] < 0.7 * e["toks"] for e in events)
    assert all(e["toks"] == 256 and e["bd_tiles_grid"] == 0 for e in events)   # no kernel here
    assert all(e["bd_tiles_masked"] == e["bd_tiles_narrow"] == 0 for e in events)
    assert 30 < _read_metric("bd_loss_row_pct", res["sources"]) < 70
    assert _read_metric("bd_live_tile_pct", res["sources"]) is None
    # what the patches of the run swapped in is put back
    assert kind.arch.base.StepRecorder is not kind.NoiseRecorder
    assert kind.arch.base.compare is train_job.compare
    assert kind.synthetic.write_token_shards.__name__ == "write_token_shards"
    run_dir, = (os.path.join(tmp_path, "runs", d) for d in os.listdir(os.path.join(tmp_path, "runs")))
    first = next(e for e in train_job._read_events(run_dir) if e.get("type") == "step_window")
    assert first["attn_plan"]["blockdiff_layers"] >= 1
    assert first["attn_plan"]["blockdiff_simple"] == first["attn_plan"]["blockdiff_layers"]
    assert first["moe_plan"]["dispatch_gather"] == first["moe_plan"]["combine_gather"] >= 1
    assert first["moe_plan"]["chunk_loop_tail"] == 0      # the residual add needs no tail


def test_the_control_noises_the_first_batches_as_the_loader_does(tmp_path):
    """``control_sdar.py`` hands the reference what the trainer's loader would
    have drawn for the same job: the recorder's batches of a run on that seed."""
    from benchmark import control_sdar

    cfg = harness.merge_into(FULL, TINY["config"])
    mix = harness.merge_into(_load("benchmark/traffic/pack8k-blockdiff.json"), TINY["traffic"])
    seed = 3_000_000_019
    batches = control_sdar.noised_first_batches(cfg, mix, seed, str(tmp_path / "control"))
    assert len(batches) == mix["checked_steps"]
    for b in batches:
        assert b["inputs"].shape == (2, 2, 128) and b["targets"].shape == (2, 128)
        assert int(b["inputs"][:, 1].max()) < cfg["mask_token_id"]
        replaced = b["inputs"][:, 0] == cfg["mask_token_id"]
        assert np.array_equal(replaced, b["targets"] > 0) and replaced.any() and not replaced.all()
    want = bd.noise_batch({"inputs": batches[1]["inputs"][:, 1], "mask": np.ones((2, 128), np.float32)},
                          seed % 2 ** 31, 1, 4, cfg["noise_eps"], cfg["mask_token_id"])
    assert np.array_equal(want["noised_inputs"], batches[1]["inputs"][:, 0])


def test_the_train_step_carries_the_scopes_the_metrics_read(tiny):
    """``attn_blockdiff`` encloses ``attn_core``; ``bd_rows`` holds what builds
    and splits the doubled rows, forward and backward."""
    cfg, args, params, batch = tiny
    step = lambda p: sdar.loss_fn(p, batch, args, remat="full", scan_layers=True)[0]
    before = core_counts()
    text = jax.jit(jax.grad(step)).lower(params).as_text(debug_info=True)
    traced = {k: n - before.get(k, 0) for k, n in core_counts().items() if n - before.get(k, 0)}
    assert traced["blockdiff_layers"] >= 1 and traced["blockdiff_simple"] == traced["blockdiff_layers"]
    names = set(re.findall(r'loc\("([^"]+)"', text))
    stack = lambda n: re.split(r"[/()]", n)
    core = [n for n in names if "attn_core" in stack(n)]
    assert core and all("attn_blockdiff" in stack(n) for n in core)
    rows = [n for n in names if "bd_rows" in stack(n)]
    assert any("concatenate" in n for n in rows) and any("transpose" in n or "jvp" in n for n in rows)
    for scope in ("moe_router", "moe_experts", "lm_head_ce", "embed", "final_norm"):
        assert any(scope in stack(n) for n in names), scope
    assert not any("ffn" in stack(n) for n in names)        # no dense layer, no shared expert


def test_registered_lazily_and_sized_by_the_trainer(tiny):
    cfg, args, _, _ = tiny
    arch = resolve_architecture("sdar_moe")
    assert arch.args_cls is sdar.SdarArgs and "attn_plan" in arch.plans
    assert args.diffusion == {"block_length": 4, "eps": 1e-3, "mask_id": cfg["mask_token_id"]}
    assert args.is_moe and args.num_local_experts == cfg["num_experts"]
    assert (args.experts_held, args.held_chunk_rows) == ((2, 2), 128)
    with pytest.raises(ValueError, match="no id of a vocabulary"):
        _args(dict(cfg, mask_token_id=cfg["vocab_size"]))
    with pytest.raises(ValueError, match="no range"):
        _args(dict(cfg, experts_held={"first": 7, "count": 2}))


def test_the_sample_config_trains_through_the_cli(tmp_path):
    """``train.py --config configs/model-config-sdar-sample.yaml`` on the CPU: a
    tokenised corpus through the noising loader, training and validation."""
    (tmp_path / "train.jsonl").write_text("".join(
        json.dumps({"text": "the quick brown fox jumps over the lazy dog " * 8}) + "\n"
        for _ in range(200)))
    shutil.copy(tmp_path / "train.jsonl", tmp_path / "val.jsonl")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "train.py"), "--config",
         os.path.join(REPO, "configs/model-config-sdar-sample.yaml"), "--runs-root",
         str(tmp_path / "runs"), "--iters", "6", "--batch-size", "2",
         "--set", "logging.steps.logging_interval=2", "--set", "logging.steps.validation_interval=3"],
        cwd=tmp_path, capture_output=True, text=True, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    log = out.stdout + out.stderr
    assert re.search(r"Step 6: loss=", log), log[-1500:]
    assert re.search(r"Step 3 validation: val_loss=", log), log[-1500:]
    assert "attention layers (traced, by kind and kernel path): " in log
    assert re.search(r"blockdiff_layers=\d+, blockdiff_simple=\d+", log)
    assert re.search(r"bd_loss_rows=\d+ \| bd_tiles_live=0 \| bd_tiles_grid=0 \| bd_tiles_masked=0 "
                     r"\| bd_tiles_narrow=0", log), log[-1500:]


def test_trains_under_fsdp_as_on_one_device(tmp_path):
    """Trainer.train() on the architecture from a dict config, with and
    without an fsdp mesh: the same losses and counters, so the five arrays of a
    block-diffusion batch and the new leaves go through the sharded step, and
    the draw does not depend on the layout."""
    from mlx_cuda_distributed_pretraining_tpu.parallel.sharding_rules import tree_pspecs
    from mlx_cuda_distributed_pretraining_tpu.train.trainer import Trainer
    from mlx_cuda_distributed_pretraining_tpu.utils.tree import flatten_dict

    cfg = harness.merge_into(FULL, TINY["config"])
    cfg = dict(cfg, vocab_size=259, mask_token_id=258)       # the tokenizer's vocabulary
    corpus = tmp_path / "train.jsonl"
    corpus.write_text("".join(json.dumps({"text": "the quick brown fox jumps over the lazy dog " * 6})
                              + "\n" for _ in range(120)))

    def run(name, mesh):
        d = {"name": name, "overwrite": True,
             "data": {"input_file": str(corpus), "validation_file": str(corpus),
                      "preprocessing": {"max_context_size": 64}, "tokenizer": {"normal_vocab_size": 256}},
             "model": kind.arch.MODEL_SECTIONS["sdar_moe"](cfg, {"attention_type": "simple"}),
             "training": {"hyperparameters": {"batch_size": 4, "learning_rate": 1e-2, "iters": 4},
                          "scheduler": {"type": "constant"}, "optimization": {"optimizer": "adafactor"}},
             "logging": {"steps": {"logging_interval": 1, "checkpoint_interval": 0,
                                   "validation_interval": 0}},
             "system": {"seed": 0, "scan_layers": True, "remat": "full", "mesh": mesh}}
        tr = Trainer(Config.from_dict(d), runs_root=str(tmp_path / "runs"), quiet=True)
        assert isinstance(tr.data, bd.BlockDiffusionBatches)
        if tr.mesh is not None:   # every matrix gets a rule and is split along fsdp
            specs, shapes = flatten_dict(tree_pspecs(tr.state["params"], tr.mesh)), \
                {k: v.shape for k, v in flatten_dict(tr.state["params"]).items()}
            assert all("fsdp" in specs[k] for k, shape in shapes.items() if len(shape) >= 2)
        tr.train()
        with open(os.path.join(tr.run_dir, "events.jsonl")) as f:
            events = [json.loads(l) for l in f]
        return [(e["loss"], e["moe_rows_held"], e["bd_loss_rows"]) for e in events
                if e.get("type") == "step_window"]

    one, sharded = run("one", {}), run("fsdp", {"fsdp": 2})
    assert len(one) == len(sharded) == 4
    np.testing.assert_allclose([s[0] for s in sharded], [o[0] for o in one], rtol=2e-4)
    # the same draw; the same choices at the seeded weights, and after an update to within
    # the selections that another order of the sums moves across a rounding-sized margin
    assert [s[2] for s in sharded] == [o[2] for o in one] and all(o[2] > 0 for o in one)
    assert sharded[0][1] == one[0][1]
    np.testing.assert_allclose([s[1] for s in sharded], [o[1] for o in one], rtol=0.05)


def test_the_unrouted_leaves_are_compared_apart():
    """``train_job_sdar.compare``: the harness's numbers, and the two gradient
    gaps again over the leaves no router feeds; a fault in either set fails."""
    names = ["layers/0/attention/wq/weight", "layers/0/ffn_norm/weight",
             "layers/0/feed_forward/router/weight", "layers/0/feed_forward/experts/w_down/weight",
             "norm/weight", "output/weight"]
    assert [kind.unrouted(n) for n in names] == [True, False, False, False, True, True]
    want = {"losses": [1.0], "grad_norms": [1.0] * 6, "grad_profiles": [np.ones(4)] * 6,
            "changes": [1.0] * 6, "names": names}
    limits = {"loss_gap": 0.01, "first_grad_norm_gap": 0.3, "first_grad_profile_gap": 0.3,
              "param_change_gap": 0.1, "unrouted_grad_norm_gap": 0.05, "unrouted_grad_profile_gap": 0.05}
    said = []
    routed_off = dict(want, grad_norms=[1.0, 1.0, 1.2, 1.2, 1.0, 1.0],
                      grad_profiles=[np.ones(4)] * 2 + [np.ones(4) * 1.2] * 2 + [np.ones(4)] * 2)
    verdict = kind.compare(routed_off, want, limits, said.append)
    assert verdict["ok"] and verdict["numbers"]["first_grad_norm_gap"] == pytest.approx(0.2)
    assert verdict["numbers"]["unrouted_grad_norm_gap"] == 0.0
    unrouted_off = dict(want, grad_norms=[1.1, 1.0, 1.0, 1.0, 1.0, 1.0])
    verdict = kind.compare(unrouted_off, want, limits, said.append)
    assert not verdict["ok"] and verdict["numbers"]["unrouted_grad_norm_gap"] == pytest.approx(0.1)
    assert any("unrouted_grad_norm_gap" in line and "OUTSIDE" in line and "wq" in line for line in said)
