"""Architecture ``xing_mla_moe`` (models/xing.py) against the benchmark's
plain reference (benchmark/reference/xing_mla_moe.py, which imports nothing of
the program), at tiny widths on seeded random weights, and the pieces this
architecture brought: the expert layer that holds a share, the sigmoid router
with its selection bias, the mixed residual streams, the next-next-token head,
and the benchmark's traffic kind for it.
"""

import dataclasses
import itertools
import json
import os
import re
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import activation_scatters, tail_after_loop
from benchmark import run as harness
from benchmark.flops import flash_mla
from benchmark.flops import xing_mla_moe as flops
from benchmark.reference import xing_mla_moe as ref
from benchmark.traffic_kinds import train_job_arch as kind
from mlx_cuda_distributed_pretraining_tpu.config import Config
from mlx_cuda_distributed_pretraining_tpu.models import moe as moe_lib
from mlx_cuda_distributed_pretraining_tpu.models import xing
from mlx_cuda_distributed_pretraining_tpu.models.registry import resolve_architecture

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "xing4_0-29b-a4b-ep8.train-1chip"
B, S = 2, 128


def _load(path):
    with open(os.path.join(REPO, path)) as f:
        return json.load(f)


FULL = _load("benchmark/configs/xing4_0-29b-a4b-ep8.json")
TINY = _load("benchmark/rehearse_arch.json")


@pytest.fixture(scope="module")
def tiny():
    """(configuration at tiny widths, the program's args for it, seeded weights, a batch)."""
    cfg = harness.merge_into(FULL, TINY["config"])
    model = kind.MODEL_SECTIONS["xing_mla_moe"](cfg, {"attention_type": "simple"})
    args = xing.XingArgs.from_config(Config.from_dict({"name": "t", "model": model}).model,
                                     cfg["vocab_size"])
    params = ref.init_params(7, cfg)
    toks = np.random.default_rng(0).integers(3, cfg["vocab_size"], size=(B, S + 1)).astype(np.int32)
    batch = {"inputs": jnp.asarray(toks[:, :-1]), "targets": jnp.asarray(toks[:, 1:]),
             "mask": jnp.ones((B, S), jnp.float32)}
    return cfg, args, params, batch


@pytest.fixture(scope="module")
def reference_step(tiny):
    cfg, _, params, batch = tiny
    return jax.jit(lambda p: ref.loss_and_grads(p, batch["inputs"], batch["targets"], cfg))(params)


def _leaf_gaps(got, want):
    return {jax.tree_util.keystr(path): float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-12))
            for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                                    jax.tree_util.tree_leaves(want))}


@pytest.mark.parametrize("scan_layers", [False, True], ids=["loop", "scan"])
def test_program_matches_reference_loss_terms_and_every_gradient(tiny, reference_step, scan_layers):
    cfg, args, params, batch = tiny
    (total, main, mtp), want = reference_step
    step = lambda p: xing.loss_fn(p, batch, args, remat="full", scan_layers=scan_layers,
                                  with_moe_stats=True)
    (loss, (count, stats)), got = jax.jit(jax.value_and_grad(step, has_aux=True))(params)
    assert float(count) == B * S
    np.testing.assert_allclose([float(loss), float(stats["main_loss"]), float(stats["mtp_loss"])],
                               [float(total), float(main), float(mtp)], rtol=2e-6)
    assert abs(float(total) - float(main) - cfg["mtp_loss_weight"] * float(mtp)) < 1e-5
    gaps = _leaf_gaps(got, want)
    assert len(gaps) == len(jax.tree_util.tree_leaves(params))
    assert max(gaps.values()) < 5e-4, sorted(gaps.items(), key=lambda kv: -kv[1])[:3]
    # the selection bias is a buffer: no gradient, in the program or the reference
    for tree in (got, want):
        for layer in tree["layers"] + [tree["mtp"]["layer"]]:
            assert not np.any(np.asarray(layer["feed_forward"]["router"]["bias"]))


def test_program_logits_match_reference(tiny):
    cfg, args, params, batch = tiny
    got, _ = xing.forward(params, batch["inputs"], args)
    want, want_mtp = ref.logits_at(params, batch["inputs"], batch["targets"], cfg)
    assert want_mtp.shape == want.shape == (B, S, cfg["vocab_size"])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)


def test_reference_by_sequence_is_the_batch_gradient(tiny, reference_step):
    cfg, _, params, batch = tiny
    terms, grads = ref.grads_by_sequence(params, batch["inputs"], batch["targets"], cfg)
    np.testing.assert_allclose([float(t) for t in terms], [float(t) for t in reference_step[0]],
                               rtol=2e-6)
    assert max(_leaf_gaps(grads, reference_step[1]).values()) < 1e-4


def test_eval_loss_is_the_main_head_alone(tiny, reference_step):
    _, args, params, batch = tiny
    loss, count = xing.loss_fn(params, batch, args, include_aux=False)
    np.testing.assert_allclose(float(loss), float(reference_step[0][1]), rtol=2e-6)


def test_the_eight_shares_add_up_to_the_uncut_layer(tiny):
    """One routed layer cut into shares of ``count`` experts each: every share
    computes shared expert + its held experts with weights normalised over all
    chosen. Their sum, the shared expert counted once, is the layer that holds
    every expert: in the reference, and the program's share equals the
    reference's share."""
    cfg, args, _, _ = tiny
    E, count = cfg["n_routed_experts"], cfg["experts_held"]["count"]
    whole_cfg = dict(cfg, experts_held={"first": 0, "count": E})
    layer = ref.make_params(jnp.uint32(5), dict(whole_cfg, num_hidden_layers=2))["layers"][0]
    ff = layer["feed_forward"]                                    # banks of all E experts
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, cfg["hidden_size"]), jnp.float32)
    whole = ref.routed_layer(ff, x, whole_cfg, "float32")
    shared = ref._swiglu(ff["shared"], x, "float32")
    cut = lambda first: {**ff, "experts": jax.tree_util.tree_map(
        lambda w: w[first:first + count], ff["experts"])}
    total = jnp.zeros_like(whole)
    for first in range(0, E, count):
        share = ref.routed_layer(cut(first), x, dict(cfg, experts_held={"first": first, "count": count}),
                                 "float32")
        mine, stats = xing.routed_ffn(cut(first), x, dataclasses.replace(
            args, experts_held=(first, count)))
        np.testing.assert_allclose(np.asarray(mine), np.asarray(share), atol=2e-6)
        assert float(stats["moe_load"].sum()) == B * S * cfg["num_experts_per_tok"]
        total = total + (share - shared)
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(whole), atol=5e-6)
    # every share held something: the test would pass trivially on empty shares
    assert float(jnp.abs(whole - shared).max()) > 1e-3


@pytest.mark.parametrize("crowded", [False, True], ids=["as_routed", "every_choice_held"])
@pytest.mark.parametrize("chunk_rows", [10 ** 9, 128, 32], ids=["whole", "4_chunks", "16_chunks"])
def test_a_held_share_drops_nothing(tiny, monkeypatch, chunk_rows, crowded):
    """The held share has no capacity: its buffer has a row for every
    selection, so the layer equals the reference's (which drops nothing) even
    when the router sends every token's every choice to the held experts, and
    taking the tokens in chunks changes neither the output nor a gradient. And
    the layer around it, whose write into the streams runs inside the chunk
    loop: the bits of the write after the loop, every chunk size's output, the
    reference's output, and the reference's gradient to every weight and to ``X``; the write alone, as the
    loop's tail, gives ``X``, ``h_post`` and ``h_res`` the gradients the write
    after the loop gives them."""
    cfg, args, params, _ = tiny
    ff = jax.tree_util.tree_map(jnp.asarray, params["layers"][0]["feed_forward"])
    first, count = args.experts_held
    assert count == args.num_experts_per_tok            # a token can choose all of the held
    if crowded:
        ff = {**ff, "router": {**ff["router"], "bias": ff["router"]["bias"].at[first:first + count].set(10.0)}}
    x = jax.random.normal(jax.random.PRNGKey(2), (B, S, cfg["hidden_size"]), jnp.float32)
    monkeypatch.setattr(xing, "HELD_CHUNK_ROWS", chunk_rows)
    assert moe_lib.held_chunks(B * S, 2, count, cfg["n_routed_experts"], chunk_rows) == \
        (max(1, B * S * 2 // min(chunk_rows, B * S * 2)),) * 2   # a quarter held: one size, one count
    got, stats = xing.routed_ffn(ff, x, args)
    held = float(stats["moe_load"][first:first + count].sum())
    assert held == B * S * count if crowded else 0 < held < B * S
    assert float(stats["moe_dropped"]) == 0
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref.routed_layer(ff, x, cfg, "float32")),
                               atol=3e-6)
    loss = lambda ff, x: jnp.sum(jnp.sin(xing.routed_ffn(ff, x, args)[0]))
    grads = jax.grad(loss, (0, 1))(ff, x)

    n, C = args.hc_mult, cfg["hidden_size"]
    layer = {**jax.tree_util.tree_map(jnp.asarray, params["layers"][0]), "feed_forward": ff}
    positions = jnp.arange(S, dtype=jnp.int32)
    X = jax.random.normal(jax.random.PRNGKey(4), (n, B, S, C), jnp.float32)
    block = lambda p, X: xing.block(p, X, positions, args, True)[0]
    want = lambda p, X: jnp.moveaxis(
        ref._layer(p, jnp.moveaxis(X, 0, 2), cfg, "float32", True), 2, 0)
    grad = lambda f: jax.grad(lambda p, X: jnp.sum(jnp.sin(f(p, X))), (0, 1))(layer, X)
    out, block_grads = block(layer, X), grad(block)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want(layer, X)), atol=1e-5)
    gaps = _leaf_gaps(block_grads, grad(want))
    assert len(gaps) == len(jax.tree_util.tree_leaves(layer)) + 1
    assert max(gaps.values()) < 5e-4, sorted(gaps.items(), key=lambda kv: -kv[1])[:3]
    # the write as the loop's tail against the write after the loop (the form until PR 36)
    h_post = jax.random.uniform(jax.random.PRNGKey(5), (n, B, S), jnp.float32, 0.0, 2.0)
    h_res = jax.random.uniform(jax.random.PRNGKey(6), (n, n, B, S), jnp.float32) / n
    inside = lambda ff, x, X, h_post, h_res: jnp.sum(jnp.sin(jnp.stack(xing.routed_ffn(
        ff, x, args, tail=lambda y, *rest: tuple(xing.hc_write_streams(rest[:n], y, *rest[n:])),
        operands=tuple((0, X[j]) for j in range(n)) + ((1, h_post), (2, h_res)))[0])))
    after = lambda ff, x, X, h_post, h_res: jnp.sum(jnp.sin(xing.hc_write(
        X, xing.routed_ffn(ff, x, args)[0], h_post, h_res)))
    tail_grads = jax.grad(inside, (0, 1, 2, 3, 4))(ff, x, X, h_post, h_res)
    compiled = lambda: jax.jit(lambda p, X: block(p, X))(layer, X)   # a fresh trace a call
    with monkeypatch.context() as m:   # compiled: op by op, XLA contracts the loop's body alone
        inside_bits = compiled()
        m.setattr(moe_lib, "sigmoid_routed_ffn", tail_after_loop(moe_lib.sigmoid_routed_ffn))
        np.testing.assert_array_equal(np.asarray(inside_bits), np.asarray(compiled()))

    monkeypatch.setattr(xing, "HELD_CHUNK_ROWS", 10 ** 9)
    np.testing.assert_allclose(   # the experts' own sums differ by 1e-9 with the chunk's rows
        np.asarray(out), np.asarray(block(layer, X)), atol=3e-6)
    for a_, b_ in zip(jax.tree_util.tree_leaves(grads),
                      jax.tree_util.tree_leaves(jax.grad(loss, (0, 1))(ff, x))):
        np.testing.assert_allclose(np.asarray(a_), np.asarray(b_), atol=3e-6)
    for a_, b_ in zip(jax.tree_util.tree_leaves(tail_grads), jax.tree_util.tree_leaves(
            jax.grad(after, (0, 1, 2, 3, 4))(ff, x, X, h_post, h_res))):
        np.testing.assert_allclose(np.asarray(a_), np.asarray(b_), atol=3e-6)


def test_a_held_shares_gradient_has_no_scatter_of_activation_rows(tiny, monkeypatch):
    """The routed layer with a held share in chunks: no scatter or scatter-add
    of rows as wide as the activations in its lowered gradient (``ragged``
    forced: the ``blocked`` backend's own dW is a scatter-add through its
    weight gather), only gathers; the chunk loop traces one dispatch and one
    combine."""
    cfg, args, params, _ = tiny
    monkeypatch.setenv("GMM_BACKEND", "ragged")
    monkeypatch.setattr(xing, "HELD_CHUNK_ROWS", 128)
    assert moe_lib.held_chunks(B * S, 2, args.experts_held[1], args.n_routed_experts, 128) == (4, 4) \
        and args.experts_held[0] > 0
    ff = jax.tree_util.tree_map(jnp.asarray, params["layers"][0]["feed_forward"])
    C = cfg["hidden_size"]
    x = jax.random.normal(jax.random.PRNGKey(2), (B, S, C), jnp.float32)
    seen = moe_lib.plan_counts()
    grad = jax.jit(jax.grad(lambda ff, x: jnp.sum(jnp.sin(xing.routed_ffn(ff, x, args)[0])), (0, 1)))
    hlo = grad.lower(ff, x).as_text(dialect="hlo")
    assert {k: n - seen[k] for k, n in moe_lib.plan_counts().items()} == {
        "dispatch_gather": 1, "combine_gather": 1, "chunk_loop_tail": 0,   # no tail was handed in,
        "chunk_two_sizes": 0,                               # and a quarter held is one buffer size,
        "chunk_trips_small": 0, "chunk_trips_whole": 4,     # whose one loop is the whole buffer's;
        # the combine in the loop's forward and in the backward's recomputation, its dgate_w
        # and the dispatch's backward
        "token_sum_kernel": 0, "token_sum_xla": 4}
    assert " gather(" in hlo and " scatter(" in hlo         # the load's bincount is one
    assert not activation_scatters(hlo, C)


def test_router_bias_moves_the_choice_and_not_the_weights():
    x = jax.random.normal(jax.random.PRNGKey(3), (4, 32, 16), jnp.float32)
    router = {"weight": jax.random.normal(jax.random.PRNGKey(4), (16, 8)) * 0.3,
              "bias": jnp.zeros((8,))}
    idx0, w0, scores = moe_lib.sigmoid_route(x, router, 2, 2.0)
    np.testing.assert_allclose(np.asarray(w0.sum(-1)), 2.0, rtol=1e-6)   # normalised, then scaled
    pushed = dict(router, bias=jnp.zeros((8,)).at[5].set(10.0))
    idx1, w1, scores1 = moe_lib.sigmoid_route(x, pushed, 2, 2.0)
    assert np.all(np.any(np.asarray(idx1) == 5, axis=-1)) and not np.all(np.any(np.asarray(idx0) == 5, axis=-1))
    np.testing.assert_array_equal(np.asarray(scores), np.asarray(scores1))
    # the weight of expert 5 is its own score's share, not the biased one's
    chosen = np.take_along_axis(np.asarray(scores1), np.asarray(idx1), axis=-1)
    np.testing.assert_allclose(np.asarray(w1), 2.0 * chosen / chosen.sum(-1, keepdims=True), rtol=1e-6)
    grads = jax.grad(lambda r: jnp.sum(moe_lib.sigmoid_route(x, r, 2, 2.0)[1] ** 2))(pushed)
    assert not np.any(np.asarray(grads["bias"])) and np.any(np.asarray(grads["weight"]))


def test_sinkhorn_maps_are_doubly_stochastic(tiny):
    cfg, args, params, _ = tiny
    X = jax.random.normal(jax.random.PRNGKey(6), (args.hc_mult, B, S, cfg["hidden_size"]))
    hc = params["layers"][0]["attn_hc"]                 # the seeded recipe: gains 0.5
    u, h_post, h_res = xing.hc_read(hc, X, args)
    assert h_res.shape == (4, 4, B, S) and u.shape == (B, S, cfg["hidden_size"])
    np.testing.assert_allclose(np.asarray(h_res.sum(axis=0)), 1.0, atol=1e-5)
    np.testing.assert_allclose(np.asarray(h_res.sum(axis=1)), 1.0, atol=1e-5)
    assert float(h_res.max()) < 0.999 and float(h_res.min()) > 0     # no permutation
    assert float(jnp.std(h_res[0, 0])) > 1e-3                        # the input moves the map
    assert 0 < float(h_post.min()) and float(h_post.max()) < 2
    # the reference's maps, in its own layout, are the same numbers
    pre, post, res = ref.mixing_maps(hc, X.transpose(1, 2, 0, 3), cfg, "float32")
    np.testing.assert_allclose(np.asarray(h_res), np.asarray(res), atol=1e-6)
    np.testing.assert_allclose(np.asarray(h_post), np.asarray(post), atol=1e-6)


def test_mtp_targets_are_two_ahead_with_the_last_position_masked():
    tokens = jnp.arange(2 * 9).reshape(2, 9)
    inputs, targets = tokens[:, :-1], tokens[:, 1:]                   # targets[i] = t_{i+1}
    mask = jnp.ones(targets.shape, jnp.float32).at[1, 3].set(0)
    tgt2, mask2 = xing.mtp_targets(targets, mask)
    np.testing.assert_array_equal(np.asarray(tgt2[:, :-1]), np.asarray(inputs[:, :-1] + 2))
    np.testing.assert_array_equal(np.asarray(mask2[0]), [1, 1, 1, 1, 1, 1, 1, 0])
    np.testing.assert_array_equal(np.asarray(mask2[1]), [1, 1, 0, 0, 1, 1, 1, 0])


def test_yarn_frequencies_and_scale_agree_with_the_reference(tiny):
    cfg, args, _, _ = tiny
    inv, cs, scale = ref.yarn(cfg)
    mine, mine_cs = xing.yarn_inv_freq(args)
    np.testing.assert_allclose(np.asarray(inv), mine, rtol=1e-6)
    assert cs == mine_cs == 1.0
    m = 0.1 * 1 * np.log(64) + 1
    np.testing.assert_allclose(scale, (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 * m * m)
    np.testing.assert_allclose(xing.softmax_scale(args), scale, rtol=1e-12)
    assert mine[0] == 1.0 and mine[-1] < args.rope_theta ** (-1 + 2 / args.qk_rope_head_dim) / 32


def test_configuration_file_keeps_every_published_number():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        pytest.skip("no catalog on this machine")
    with open(catalog) as f:
        entry = next(json.loads(l) for l in f if '"Xing4.0-29B-A4B"' in l)
    assert FULL["source"] == entry["source_url"]
    changed = {k for k, v in entry["config"].items() if FULL.get(k) != v}
    assert changed == {"num_hidden_layers", "first_k_dense_replace", "vocab_size"}
    assert set(FULL["reduced"]) == changed | {"n_routed_experts"}
    assert FULL["published"] == {k: entry["config"][k] for k in FULL["reduced"]}
    entry_b = next(c for c in _load("BENCHMARK.json")["configs"] if c["name"] == FULL["name"])
    assert set(entry_b["reduced"]) == set(FULL["reduced"])


def test_parameter_and_flop_arithmetic():
    for layers, total in ((4, 0.913), (5, 1.042), (6, 1.170)):
        assert round(flops.total_params(dict(FULL, num_hidden_layers=1 + layers)) / 1e9, 3) == total
    cfg = harness.merge_into(FULL, TINY["config"])
    n = sum(int(x.size) for x in jax.tree_util.tree_leaves(
        jax.eval_shape(lambda: ref.make_params(jnp.uint32(0), cfg))))
    assert n == flops.total_params(cfg)
    assert flops.uniform_held_experts_per_token(FULL) == 0.5
    # more rows on the held experts, more required work; none, the shared path alone
    base = flops.train_flops_per_token(FULL, 4096, 0.0)
    assert flops.train_flops_per_token(FULL, 4096, 0.5) - base == pytest.approx(
        6 * 0.5 * 3 * 3584 * 1024 * flops.routed_layers(FULL))
    assert flops.train_flops_per_token(FULL, 4096) == flops.train_flops_per_token(FULL, 4096, 0.5)
    # the program's own count (its mfu= line) is the same model's
    model = kind.MODEL_SECTIONS["xing_mla_moe"](FULL, {"attention_type": "flash"})
    args = xing.XingArgs.from_config(Config.from_dict({"name": "t", "model": model}).model, 16384)
    assert xing.flops_per_token(args, 4096) == pytest.approx(flops.train_flops_per_token(FULL, 4096))
    # the kernels' executed operations at 192/128, and the widths they refuse
    assert flash_mla.fwd(4, 32, 4096, 128) == 4 * 32 * 4096 ** 2 * 320
    assert flash_mla.bwd_dq(4, 32, 4096, 192) == 4 * 32 * 4096 ** 2 * 512
    assert flash_mla.bwd_dkv(4, 32, 4096, 192) == 4 * 32 * 4096 ** 2 * 640
    with pytest.raises(ValueError):
        flash_mla.fwd(4, 32, 4096, 192)


def test_new_readers_find_nothing_in_a_trace_without_their_scopes():
    """Run on the parent, whose program has no such scope or kernel, each new
    reader returns None and raises nothing."""
    import gzip
    import importlib.util
    import shutil
    import tempfile

    readers = os.path.join(REPO, "benchmark", "layer_metrics")
    sys.path.insert(0, readers)
    work = tempfile.mkdtemp()
    try:
        where = os.path.join(work, "plugins", "profile", "run")   # as the profiler lays it out
        os.makedirs(where)
        with gzip.open(os.path.join(REPO, "benchmark/tests/data/train_1chip_v5e_scoped.xplane.pb.gz")) as src, \
                open(os.path.join(where, "t.xplane.pb"), "wb") as dst:
            shutil.copyfileobj(src, dst)
        sources = {"trace_dir": work, "peaks": {"bf16_flops": 197e12}, "step_window_events": [
            {"type": "step_window", "step": 9, "steps": 1}]}
        values = {}
        for name in ("step_device_ms.moe", "step_device_ms.residual_mix", "step_device_ms.mtp",
                     "kernel_peak_pct.mla_flash_fwd", "kernel_peak_pct.mla_flash_bwd",
                     "kernel_peak_pct.gmm", "moe_rows_held_per_step",
                     "moe_whole_buffer_chunks_per_step"):
            spec = importlib.util.spec_from_file_location("m_" + name.replace(".", "_"),
                                                          os.path.join(readers, name + ".py"))
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            values[name] = mod.read(sources)
        # the forward's first output is o, 128 wide at 128/128 too: its reader cannot refuse a
        # dense model's trace, which is why the metric lists this cell alone
        assert values.pop("kernel_peak_pct.mla_flash_fwd") is not None
        assert all(v is None for v in values.values()), values
        from _named_scopes import step_ms_under
        assert step_ms_under(sources, "ffn") > 100      # a scope the trace has, read the new way
        assert step_ms_under({}, "hc_mix") is None
    finally:
        sys.path.remove(readers)
        shutil.rmtree(work, ignore_errors=True)


def test_the_whole_buffer_reader_reads_the_programs_counter():
    """``moe_whole_buffer_chunks_per_step``: the events' ``moe_chunks_whole`` over
    their steps; 0.0 (not nothing) where every chunk fit its small buffer."""
    import importlib.util

    spec = importlib.util.spec_from_file_location("m_whole", os.path.join(
        REPO, "benchmark", "layer_metrics", "moe_whole_buffer_chunks_per_step.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    window = lambda *counts: {"step_window_events": [
        {"type": "step_window", "step": i, "steps": 2, "moe_rows_held": 9, "moe_chunks_whole": c}
        for i, c in enumerate(counts)]}
    assert mod.read(window(0, 0, 0)) == 0.0
    assert mod.read(window(0, 8, 4)) == 2.0      # 12 chunk-layers over 6 steps
    assert mod.read(window()) is None and mod.read({}) is None


def test_cell_one_imports_none_of_the_new_modules():
    """A llama run pays nothing for this architecture: the registry imports
    models/xing.py only when a config names it, and the traffic kind its
    reference and FLOP count only when a configuration does."""
    code = ("import sys; sys.path.insert(0, %r)\n"
            "from benchmark.traffic_kinds import train_job\n"
            "from mlx_cuda_distributed_pretraining_tpu.train.trainer import Trainer\n"
            "from mlx_cuda_distributed_pretraining_tpu.models.registry import resolve_architecture\n"
            "resolve_architecture('llama')\n"
            "new = [m for m in sys.modules if m.endswith(('models.xing', 'xing_mla_moe', 'flash_mla',"
            " 'train_job_arch'))]\n"
            "assert not new, new\n"
            "resolve_architecture('xing_mla_moe')\n"
            "assert any(m.endswith('models.xing') for m in sys.modules)\n" % REPO)
    subprocess.run([sys.executable, "-c", code], check=True, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    with pytest.raises(ValueError, match="xing_mla_moe"):
        resolve_architecture("no_such_model")


@pytest.mark.parametrize("held_count", [2, 1], ids=["a_quarter_held", "an_eighth_held"])
def test_the_cell_rehearses_through_its_traffic_kind(held_count, tmp_path, monkeypatch):
    """``run.py --rehearse`` looks a kind up in rehearse.json, which is closed;
    this is the new cell's rehearsal: a Context at tiny widths, the kind's own
    ``run``: Trainer.train() on architecture xing_mla_moe from a dict config,
    the window, the events' counters, the reference's three steps, the
    comparison. With an eighth of the experts held, as in the cell (2 of 8 is
    a quarter), the expert layers run at a small buffer or the whole one, by
    what the router sent, and the reference knows of neither."""
    # The window counts steps here, not this machine's seconds: the recorder's clock ticks
    # once a reading, three readings a step, so its 1.5 s hold 62 whole steps whatever else the
    # machine runs. At these widths the loss falls by 0.07 in 60 steps and varies by 0.015 from
    # batch to batch: on the wall clock an idle machine fitted 21 steps (0.04 down) and a loaded
    # one 5, whose last loss lay above step 1's, which the kind rightly calls not correct.
    ticks = itertools.count()
    monkeypatch.setattr(kind.base, "time", types.SimpleNamespace(
        perf_counter=lambda: 0.008 * next(ticks)))
    bench, cell, config, mix = harness.load_cell(CELL)
    assert mix["kind"] == "train_job_arch" and cell["chips"] == 1
    base_mix = _load("benchmark/traffic/pack4k-b4.json")
    assert {k: v for k, v in mix.items() if k not in ("kind", "batch_size")} == \
        {k: v for k, v in base_mix.items() if k not in ("kind", "batch_size")}
    config = harness.merge_into(config, TINY["config"])
    config["experts_held"] = dict(config["experts_held"], count=held_count)
    mix = harness.merge_into(mix, TINY["traffic"])
    # At these widths the median leaf is small, and the first mixing map of a stack reads
    # replicated streams: two of its three gains have a gradient that is rounding noise, which
    # Adafactor scales to a whole update of either sign. Their change is judged at the real
    # widths (beside a median leaf 15 times larger), not here.
    cell = dict(cell, limits={**{k: 0.05 for k in cell["limits"]}, "param_change_gap": 1.0})
    ctx = harness.Context(cell, config, mix, seed=3_000_000_019, seconds=1.5, trace=False,
                          rehearse=True, workdir=str(tmp_path), quiet=True)
    res = kind.run(ctx)
    assert res["correct"], res["check_numbers"]
    assert len(res["sources"]["timed_steps"]) == 62
    assert len(res["check_numbers"]) == 3 * 3 + 3      # three terms a step, three steps
    assert max(v for k, v in res["check_numbers"].items() if k.startswith("loss_gap")) < 1e-3
    events = res["sources"]["step_window_events"]
    assert events and all(
        {"moe_rows_held", "moe_chunks_whole", "moe_load_max_over_mean", "main_loss", "mtp_loss",
         "moe_drop"} <= set(e) for e in events)
    assert all(e["moe_drop"] == 0 and e["moe_rows_held"] > 0 for e in events)
    # one chunk a layer here: a step counts the layers (the stack's and the module's) whose
    # held rows did not fit half the selections, and with one buffer size none
    assert all(0 <= e["moe_chunks_whole"] <= (held_count == 1) * (config["num_hidden_layers"] - 1 + 1)
               for e in events)
    # the run's first window says the step traced its expert layers (the scanned stack's and
    # the module's) in the gather form: what tells it from an old executable out of a cache
    run_dir, = (os.path.join(tmp_path, "runs", d) for d in os.listdir(os.path.join(tmp_path, "runs")))
    first = next(e for e in kind.base._read_events(run_dir) if e.get("type") == "step_window")
    assert first["moe_plan"]["dispatch_gather"] == first["moe_plan"]["combine_gather"] >= 2
    assert first["moe_plan"]["chunk_loop_tail"] == 0      # 512 selections are one chunk here: no loop
    # 2 of 8 held is one buffer size; 1 of 8 two, in the scanned stack and in the module
    assert first["moe_plan"]["chunk_two_sizes"] == (2 if held_count == 1 else 0)
    assert not {"held_capacity_factor", "held_passes"} & set(FULL)   # no capacity anywhere
    assert res["end_to_end"]["train_tokens_per_s_per_chip"] > 0 and res["end_to_end"]["setup_s"] > 0
    flops_per_token = res["sources"]["flops_per_token"]
    assert flops.train_flops_per_token(config, mix["seq_len"], 0.0) < flops_per_token
    # every metric the cell is listed under has a reader file
    for m in bench["per_layer"]:
        if CELL in m.get("workloads", ()):
            assert os.path.isfile(os.path.join(REPO, "benchmark/layer_metrics", m["name"] + ".py"))


def test_sharding_rules_cover_the_new_leaves(tiny):
    """Every matrix of the architecture gets a rule (none falls to the
    replicated default), and under fsdp each is split along a dimension the
    axis divides."""
    from jax.sharding import Mesh, PartitionSpec as P

    from mlx_cuda_distributed_pretraining_tpu.parallel.sharding_rules import param_pspec, tree_pspecs
    from mlx_cuda_distributed_pretraining_tpu.utils.tree import flatten_dict

    _, _, params, _ = tiny
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("fsdp", "tp"))
    specs = flatten_dict(tree_pspecs(params, mesh))
    shapes = {k: v.shape for k, v in flatten_dict(params).items()}
    assert set(specs) == set(shapes)
    for path, shape in shapes.items():
        spec = specs[path]
        if len(shape) >= 2:
            assert "fsdp" in spec, (path, shape, spec)
        else:
            assert spec in (P(), P(None)), (path, spec)
    assert param_pspec("layers.0.attention.wq_b.weight", (24, 96), mesh) == P("fsdp", "tp")
    assert param_pspec("layers.0.attention.wkv_a.weight", (64, 24), mesh) == P("fsdp", None)
    assert param_pspec("mtp.layer.feed_forward.shared.w_down.weight", (32, 64), mesh) == P("tp", "fsdp")
    assert param_pspec("layers.1.ffn_hc.phi.weight", (256, 24), mesh) == P("fsdp", None)


def test_trains_under_fsdp_as_on_one_device(tmp_path):
    """Trainer.train() on the architecture from a dict config, with and
    without an fsdp mesh: the same losses, so no new leaf breaks the sharded
    step."""
    from mlx_cuda_distributed_pretraining_tpu.train.trainer import Trainer

    cfg = harness.merge_into(FULL, TINY["config"])
    corpus = tmp_path / "train.jsonl"
    corpus.write_text("".join(json.dumps({"text": "the quick brown fox jumps over the lazy dog " * 6})
                              + "\n" for _ in range(120)))

    def run(name, mesh):
        d = {"name": name, "overwrite": True,
             "data": {"input_file": str(corpus), "validation_file": str(corpus),
                      "preprocessing": {"max_context_size": 64}, "tokenizer": {"normal_vocab_size": 256}},
             "model": kind.MODEL_SECTIONS["xing_mla_moe"](cfg, {"attention_type": "simple"}),
             "training": {"hyperparameters": {"batch_size": 4, "learning_rate": 1e-2, "iters": 4},
                          "scheduler": {"type": "constant"}, "optimization": {"optimizer": "adafactor"}},
             "logging": {"steps": {"logging_interval": 1, "checkpoint_interval": 0,
                                   "validation_interval": 0}},
             "system": {"seed": 0, "scan_layers": True, "remat": "full", "mesh": mesh}}
        tr = Trainer(Config.from_dict(d), runs_root=str(tmp_path / "runs"), quiet=True)
        tr.train()
        with open(os.path.join(tr.run_dir, "events.jsonl")) as f:
            events = [json.loads(l) for l in f]
        return [(e["loss"], e["main_loss"], e["mtp_loss"], e["moe_rows_held"])
                for e in events if e.get("type") == "step_window"]

    one, sharded = run("one", {}), run("fsdp", {"fsdp": 2})
    assert len(one) == len(sharded) == 4
    np.testing.assert_allclose(np.asarray(sharded)[:, :3], np.asarray(one)[:, :3], rtol=2e-4)
    assert [s[3] for s in sharded] == [o[3] for o in one]
    assert one[-1][0] < one[0][0]


def test_the_train_step_carries_the_scopes_the_metrics_read(tiny):
    """``hc_mix`` sits inside ``layer`` on every mixing operation, ``mtp`` is
    outermost on the whole module up to its final norm (so its kernels carry
    both names; its head's rows are walked with the main head's, under
    ``lm_head_ce`` alone), and nothing of the model's work is left without a
    scope."""
    _, args, params, batch = tiny
    step = jax.jit(jax.grad(lambda p: xing.loss_fn(p, batch, args, remat="full", scan_layers=True)[0]))
    hlo = step.lower(params).compile().as_text()
    names = set(re.findall(r'op_name="([^"]+)"', hlo))
    stack = lambda n: [t for t in re.split(r"[/()]", n) if t]
    mix = [n for n in names if "hc_mix" in stack(n)]
    assert mix and all("layer" in stack(n) for n in mix)
    assert any("rematted_computation" in stack(n) for n in mix)
    mtp = [n for n in names if "mtp" in stack(n)]
    for inner in ("hc_mix", "attn_core", "attn_qkv", "moe_experts", "moe_router", "ffn", "embed",
                  "final_norm"):
        assert any(inner in stack(n) for n in mtp), inner
    # one walk over both heads' rows, outside the module's scope
    assert not [n for n in mtp if "lm_head_ce" in stack(n)]
    assert [n for n in names if "lm_head_ce" in stack(n)]
    for scope in ("attn_out", "norm"):
        assert any(scope in stack(n) for n in names), scope
    # The expert layer's backward is gathers too (a custom backward: models/moe.py), and they
    # are the layer's: every gather of the backward pass proper, in the scanned layers and in
    # the module, carries ``moe_experts`` innermost, as the forward's and the recomputed do.
    gathers = re.findall(r'\bgather\([^\n]*op_name="([^"]+)"', hlo)
    backward = [n for n in gathers if "transpose" in stack(n)
                and "rematted_computation" not in stack(n)]
    assert len(backward) >= 2 * 4                       # dx, dy_buf's rows and weights, dgate_w's rows
    assert all(stack(n)[-2] == "moe_experts" for n in backward), backward
    assert any("mtp" in stack(n) for n in backward) and any("mtp" not in stack(n) for n in backward)
    for when in ("rematted_computation", "jvp"):
        assert any(when in stack(n) and "moe_experts" in stack(n) for n in gathers), when


def test_a_mixing_map_with_a_wrong_gradient_fails_the_check():
    """Every leaf is in the two first-gradient numbers, the mixed residual's
    maps too: a wrong backward through the Sinkhorn loop has to come out not
    correct, and the run's line names the leaf."""
    from benchmark.traffic_kinds import train_job
    names = ["layers/0/attn_hc/phi/weight", "layers/0/attention/wo/weight", "mtp/layer/ffn_hc/alpha",
             "output/weight"]
    want = {"names": names, "grad_norms": [1.0, 2.0, 3.0, 4.0], "changes": [1.0, 1.0, 1.0, 1.0],
            "grad_profiles": [np.full(3, float(i)) for i in range(1, 5)], "losses": [1.0]}
    limits = {"loss_gap": 0.1, "first_grad_norm_gap": 0.3, "first_grad_profile_gap": 0.3,
              "param_change_gap": 0.5}
    sound = dict(want, grad_norms=[1.1, 2.0, 3.0, 4.1])
    assert train_job.compare(sound, want, limits, lambda _: None)["ok"]
    wrong_map = dict(want, grad_norms=[1.0, 2.0, 4.5, 4.0],
                     grad_profiles=[np.full(3, 1.0), np.full(3, 2.0), np.zeros(3), np.full(3, 4.0)])
    said = []
    kind.say_worst_leaves(wrong_map, want, said.append)
    assert "norm mtp/layer/ffn_hc/alpha 0.5000" in said[0] and "profile mtp/layer/ffn_hc/alpha 1.0000" in said[0]
    verdict = train_job.compare(wrong_map, want, limits, lambda _: None)
    assert not verdict["ok"] and verdict["numbers"]["first_grad_norm_gap"] == pytest.approx(0.5)
    assert verdict["numbers"]["first_grad_profile_gap"] == pytest.approx(1.0)
