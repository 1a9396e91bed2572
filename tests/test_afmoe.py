"""Architecture ``afmoe`` (models/afmoe.py) against the benchmark's plain
reference (benchmark/reference/afmoe.py, which imports nothing of the
program), at tiny widths on seeded random weights, and the pieces this
architecture brought: layers of two kinds (sliding-window with RoPE, full
attention without) in one scanned stack, the gated QK-normed attention, the
8-of-128 router's held share, the new scopes, counters and readers, and the
benchmark's traffic kind for it.
"""

import dataclasses
import gzip
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

from conftest import tail_after_loop
from benchmark import run as harness
from benchmark.flops import afmoe as flops
from benchmark.flops import flash_attention as flash_flops
from benchmark.flops import flash_window
from benchmark.reference import afmoe as ref
from benchmark.traffic_kinds import train_job
from benchmark.traffic_kinds import train_job_afmoe as kind
from mlx_cuda_distributed_pretraining_tpu.config import Config
from mlx_cuda_distributed_pretraining_tpu.models import afmoe
from mlx_cuda_distributed_pretraining_tpu.models import moe as moe_lib
from mlx_cuda_distributed_pretraining_tpu.models import stack
from mlx_cuda_distributed_pretraining_tpu.models.registry import resolve_architecture
from mlx_cuda_distributed_pretraining_tpu.ops.attention import core_counts

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "trinity-mini-ep8.train-seq16k"
B, S = 2, 128
SLIDING, FULL_ATT = afmoe.SLIDING, afmoe.FULL


def _load(path):
    with open(os.path.join(REPO, path)) as f:
        return json.load(f)


FULL = _load("benchmark/configs/trinity-mini-ep8.json")
TINY = _load("benchmark/rehearse_afmoe.json")


def _args(cfg, attention_type="simple"):
    model = kind.arch.MODEL_SECTIONS["afmoe"](cfg, {"attention_type": attention_type})
    return afmoe.AfmoeArgs.from_config(Config.from_dict({"name": "t", "model": model}).model,
                                       cfg["vocab_size"])


@pytest.fixture(scope="module")
def tiny():
    """(configuration at tiny widths, the program's args for it, seeded weights, a batch)."""
    cfg = harness.merge_into(FULL, TINY["config"])
    params = ref.init_params(7, cfg)
    toks = np.random.default_rng(0).integers(3, cfg["vocab_size"], size=(B, S + 1)).astype(np.int32)
    batch = {"inputs": jnp.asarray(toks[:, :-1]), "targets": jnp.asarray(toks[:, 1:]),
             "mask": jnp.ones((B, S), jnp.float32)}
    return cfg, _args(cfg), params, batch


def _reference_step(cfg, params, batch):
    return jax.jit(lambda p: ref.loss_and_grads(p, batch["inputs"], batch["targets"], cfg))(params)


@pytest.fixture(scope="module")
def reference_step(tiny):
    cfg, _, params, batch = tiny
    return _reference_step(cfg, params, batch)


def _leaf_gaps(got, want):
    return {jax.tree_util.keystr(path): float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-12))
            for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                                    jax.tree_util.tree_leaves(want))}


def _program_step(params, batch, args, scan_layers):
    step = lambda p: afmoe.loss_fn(p, batch, args, remat="full", scan_layers=scan_layers,
                                   with_moe_stats=True)
    return jax.jit(jax.value_and_grad(step, has_aux=True))(params)


@pytest.mark.parametrize("attention_type", ["simple", "flash"])
@pytest.mark.parametrize("scan_layers", [False, True], ids=["loop", "scan"])
def test_program_matches_reference_loss_and_every_gradient(tiny, reference_step, scan_layers,
                                                           attention_type):
    """With and without ``scan_layers`` (the scan picks each layer's core by
    ``lax.cond`` on a scanned flag, the loop by a Python bool), through the
    flash kernels (interpreted here) and the simple path."""
    cfg, _, params, batch = tiny
    (want_loss,), want = reference_step
    (loss, (count, stats)), got = _program_step(params, batch, _args(cfg, attention_type), scan_layers)
    assert float(count) == B * S
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=2e-6)
    gaps = _leaf_gaps(got, want)
    assert len(gaps) == len(jax.tree_util.tree_leaves(params))
    assert max(gaps.values()) < 5e-4, sorted(gaps.items(), key=lambda kv: -kv[1])[:3]
    assert float(stats["moe_load"].sum()) == B * S * cfg["num_experts_per_tok"] * len(params["layers"])
    assert float(stats["moe_dropped"]) == 0
    # the selection bias is a buffer: no gradient, in the program or the reference
    for tree in (got, want):
        for layer in tree["layers"]:
            assert not np.any(np.asarray(layer["feed_forward"]["router"]["bias"]))


def test_program_logits_match_reference(tiny):
    cfg, args, params, batch = tiny
    got, _ = afmoe.forward(params, batch["inputs"], args, scan_layers=True)
    want = ref.logits_at(params, batch["inputs"], cfg)
    assert want.shape == (B, S, cfg["vocab_size"])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=5e-6)
    with pytest.raises(NotImplementedError):
        afmoe.forward(params, batch["inputs"], args, cache={})


def test_a_pattern_other_than_the_published_runs_from_layer_types_alone(tiny):
    """``F S F`` (a full-attention dense layer, the scan starting on a sliding
    one): nothing in the code knows a period."""
    cfg, _, _, batch = tiny
    cfg = dict(cfg, num_hidden_layers=3, layer_types=[FULL_ATT, SLIDING, FULL_ATT])
    params = ref.init_params(11, cfg)
    (want_loss,), want = _reference_step(cfg, params, batch)
    before = core_counts()
    (loss, _), got = _program_step(params, batch, _args(cfg), True)
    traced = {k: n - before.get(k, 0) for k, n in core_counts().items()}
    assert traced["global_layers"] == 2 and traced["window_layers"] == 1   # dense; the scan's two
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=2e-6)
    assert max(_leaf_gaps(got, want).values()) < 5e-4
    # and the order matters: the same weights under S F S are another model
    other, _ = afmoe.loss_fn(params, batch, _args(dict(cfg, layer_types=[SLIDING, FULL_ATT, SLIDING])))
    assert abs(float(other) - float(want_loss)) > 1e-4
    with pytest.raises(ValueError, match="layer_types"):
        _args(dict(cfg, layer_types=[FULL_ATT, SLIDING]))
    with pytest.raises(ValueError, match="layer_types"):
        _args(dict(cfg, layer_types=[FULL_ATT, SLIDING, "chunked_attention"]))


def test_one_kind_alone_scans_without_a_flag(tiny):
    """A stack whose scanned layers are all of one kind traces that kind's core
    as a plain call: no ``cond`` in the program."""
    cfg, _, _, batch = tiny
    cfg = dict(cfg, num_hidden_layers=3, layer_types=[SLIDING] * 3)
    params = ref.init_params(3, cfg)
    fn = lambda p: afmoe.loss_fn(p, batch, _args(cfg), scan_layers=True)[0]
    assert " cond" not in str(jax.make_jaxpr(fn)(params))
    mixed = dict(cfg, layer_types=[SLIDING, SLIDING, FULL_ATT])
    assert " cond" in str(jax.make_jaxpr(
        lambda p: afmoe.loss_fn(p, batch, _args(mixed), scan_layers=True)[0])(params))


def _attention_out(params, args, x, sliding, positions=None):
    positions = jnp.arange(x.shape[1], dtype=jnp.int32) if positions is None else positions
    return afmoe.gated_attention(params["layers"][0]["attention"], x, args, positions, sliding)


@pytest.mark.parametrize("attention_type", ["simple", "flash"])
def test_a_sliding_layer_ignores_a_key_a_window_back_and_a_full_layer_does_not(tiny, attention_type):
    cfg, _, params, _ = tiny
    args, W = _args(cfg, attention_type), cfg["sliding_window"]
    x = jax.random.normal(jax.random.PRNGKey(1), (1, S, cfg["hidden_size"]), jnp.float32)
    moved = x.at[:, 0].add(1.0)                       # position 0 feeds key 0 and value 0
    for sliding in (True, False):
        delta = np.abs(np.asarray(_attention_out(params, args, moved, sliding)
                                  - _attention_out(params, args, x, sliding))).max(axis=(0, 2))
        assert np.all(delta[1:W] > 1e-6)              # i - 0 < W: seen by both kinds
        if sliding:
            assert np.all(delta[W:] == 0), np.nonzero(delta[W:])[0][:4]
        else:
            assert np.all(delta[W:] > 1e-7)


def test_rope_moves_a_sliding_layer_and_not_a_full_one(tiny):
    cfg, args, params, _ = tiny
    x = jax.random.normal(jax.random.PRNGKey(2), (1, S, cfg["hidden_size"]), jnp.float32)
    at = jnp.arange(S, dtype=jnp.int32)
    for sliding in (True, False):
        base = np.asarray(_attention_out(params, args, x, sliding, at))
        shifted = np.asarray(_attention_out(params, args, x, sliding, at + 5))
        stretched = np.asarray(_attention_out(params, args, x, sliding, at * 3))
        np.testing.assert_allclose(shifted, base, atol=2e-5)   # the rotation is relative
        if sliding:
            assert np.abs(stretched - base).max() > 1e-3
        else:
            np.testing.assert_array_equal(stretched, base)     # no position enters a full layer


def test_the_output_gate_and_the_head_norms_are_in_the_layer(tiny):
    """Zero gate weights halve the heads' output (sigmoid(0)); a head norm's
    gain scales q, so the scores, so the output."""
    cfg, args, params, _ = tiny
    att = params["layers"][0]["attention"]
    x = jax.random.normal(jax.random.PRNGKey(4), (1, S, cfg["hidden_size"]), jnp.float32)
    at = jnp.arange(S, dtype=jnp.int32)
    out = lambda p, gated=True: afmoe.gated_attention(p, x, args, at, True)
    ungated = dict(att, wg={"weight": jnp.zeros_like(att["wg"]["weight"])})
    assert float(jnp.abs(out(ungated) - out(att)).max()) > 1e-4
    # with the gate at one half, wo sees half of the plain GQA output under the same mask
    from mlx_cuda_distributed_pretraining_tpu.ops import masks
    from mlx_cuda_distributed_pretraining_tpu.ops.attention import reference_attention
    from mlx_cuda_distributed_pretraining_tpu.models.llama import apply_rope, rms_norm, rope_cos_sin
    H, G, D = args.num_heads, args.num_kv_heads, args.head_dim
    q = rms_norm((x @ att["wq"]["weight"]).reshape(1, S, H, D), att["q_norm"]["weight"], 1e-5)
    k = rms_norm((x @ att["wk"]["weight"]).reshape(1, S, G, D), att["k_norm"]["weight"], 1e-5)
    cos, sin = rope_cos_sin(at, D, args.rope_theta)
    plain = reference_attention(apply_rope(q, cos, sin), apply_rope(k, cos, sin),
                                (x @ att["wv"]["weight"]).reshape(1, S, G, D),
                                mask_mod=masks.sliding_window(args.sliding_window))
    np.testing.assert_allclose(np.asarray(out(ungated)),
                               np.asarray(0.5 * plain.reshape(1, S, H * D) @ att["wo"]["weight"]),
                               atol=2e-6)
    louder = dict(att, q_norm={"weight": 2.0 * att["q_norm"]["weight"]})
    assert float(jnp.abs(out(louder) - out(att)).max()) > 1e-4


def test_the_embedding_is_scaled_by_the_root_of_the_width(tiny):
    cfg, args, params, batch = tiny
    plain = dataclasses.replace(args, mup_enabled=False)
    scaled = {**params, "tok_embeddings": {"weight": params["tok_embeddings"]["weight"]
                                           * cfg["hidden_size"] ** 0.5}}
    a, _ = afmoe.forward(params, batch["inputs"], args)
    b, _ = afmoe.forward(scaled, batch["inputs"], plain)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
    assert float(jnp.abs(afmoe.forward(params, batch["inputs"], plain)[0] - a).max()) > 1e-3


def test_the_eight_shares_add_up_to_the_uncut_layer(tiny):
    """One routed layer cut into shares of ``count`` experts each: every share
    computes shared expert + its held experts with weights normalised over all
    chosen. Their sum, the shared expert counted once, is the layer that holds
    every expert, in the reference; and the program's share is the
    reference's share."""
    cfg, args, _, _ = tiny
    E, count = 16, 2                                   # eight shares of two
    cfg = dict(cfg, num_experts=E)
    whole_cfg = dict(cfg, experts_held={"first": 0, "count": E})
    ff = ref.make_params(jnp.uint32(5), dict(whole_cfg, num_hidden_layers=2,
                                             layer_types=[SLIDING, FULL_ATT]))["layers"][0]["feed_forward"]
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, cfg["hidden_size"]), jnp.float32)
    whole = ref.routed_layer(ff, x, whole_cfg, "float32")
    shared = ref._swiglu(ff["shared"], x, "float32")
    cut = lambda first: {**ff, "experts": jax.tree_util.tree_map(
        lambda w: w[first:first + count], ff["experts"])}
    total = jnp.zeros_like(whole)
    for first in range(0, E, count):
        share = ref.routed_layer(cut(first), x, dict(cfg, experts_held={"first": first, "count": count}),
                                 "float32")
        mine, stats = afmoe.routed_ffn(cut(first), x, dataclasses.replace(
            args, n_routed_experts=E, experts_held=(first, count)))
        np.testing.assert_allclose(np.asarray(mine), np.asarray(share), atol=3e-6)
        assert float(stats["moe_load"].sum()) == B * S * cfg["num_experts_per_tok"]
        total = total + (share - shared)
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(whole), atol=5e-6)
    assert float(jnp.abs(whole - shared).max()) > 1e-3   # the shares held something


@pytest.mark.parametrize("crowded", [False, True], ids=["as_routed", "every_choice_held"])
@pytest.mark.parametrize("chunk_rows", [10 ** 9, 128, 32], ids=["whole", "4_chunks", "16_chunks"])
def test_a_held_share_drops_nothing(tiny, monkeypatch, chunk_rows, crowded):
    """The held share has no capacity: its buffer has a row for every
    selection, so the layer equals the reference's (which drops nothing) even
    when the router sends every token's every choice to the held experts, at
    every chunk size this model may name (``held_chunk_rows``). And the
    layer around it, whose post-norm runs inside the chunk loop: the bits of
    the norm after the loop, every chunk size's output, the reference's output,
    and the reference's gradient to every weight and to ``x``."""
    cfg, args, params, _ = tiny
    args = dataclasses.replace(args, held_chunk_rows=chunk_rows)
    ff = jax.tree_util.tree_map(jnp.asarray, params["layers"][0]["feed_forward"])
    first, count = args.experts_held
    K = args.num_experts_per_tok
    assert count == K                                   # a token can choose all of the held
    assert moe_lib.held_chunks(B * S, K, count, args.n_routed_experts, chunk_rows) == \
        (max(1, B * S * K // min(chunk_rows, B * S * K)),) * 2   # a quarter held: one size, one count
    if crowded:
        ff = {**ff, "router": {**ff["router"], "bias": ff["router"]["bias"].at[first:first + count].set(10.0)}}
    x = jax.random.normal(jax.random.PRNGKey(2), (B, S, cfg["hidden_size"]), jnp.float32)
    got, stats = afmoe.routed_ffn(ff, x, args)
    held = float(stats["moe_load"][first:first + count].sum())
    assert held == B * S * count if crowded else 0 < held < B * S
    assert float(stats["moe_dropped"]) == 0
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref.routed_layer(ff, x, cfg, "float32")),
                               atol=3e-6)

    layer = {**jax.tree_util.tree_map(jnp.asarray, params["layers"][0]), "feed_forward": ff}
    positions = jnp.arange(S, dtype=jnp.int32)
    block = lambda a: lambda p, x: afmoe.block(p, x, positions, a, True, True)[0]
    want = lambda p, x: ref._layer(p, x, cfg, "float32", True, SLIDING)
    out = block(args)(layer, x)
    np.testing.assert_allclose(   # the experts' own sums differ by 1e-9 with the chunk's rows
        np.asarray(out), np.asarray(block(dataclasses.replace(args, held_chunk_rows=10 ** 9))(layer, x)),
        atol=3e-6)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want(layer, x)), atol=1e-5)
    grad = lambda f: jax.grad(lambda p, x: jnp.sum(jnp.sin(f(p, x))), (0, 1))(layer, x)
    gaps = _leaf_gaps(grad(block(args)), grad(want))
    assert len(gaps) == len(jax.tree_util.tree_leaves(layer)) + 1
    assert max(gaps.values()) < 5e-4, sorted(gaps.items(), key=lambda kv: -kv[1])[:3]
    compiled = lambda: jax.jit(lambda p, x: block(args)(p, x))(layer, x)   # a fresh trace a call
    out = compiled()
    monkeypatch.setattr(moe_lib, "sigmoid_routed_ffn", tail_after_loop(moe_lib.sigmoid_routed_ffn))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(compiled()))


def test_selection_bias_moves_the_choice_and_not_the_weights_at_the_published_scale():
    scale = FULL["route_scale"]
    assert scale == 2.826
    x = jax.random.normal(jax.random.PRNGKey(3), (4, 32, 16), jnp.float32)
    router = {"weight": jax.random.normal(jax.random.PRNGKey(4), (16, 128)) * 0.3,
              "bias": jnp.zeros((128,))}
    idx0, w0, scores = moe_lib.sigmoid_route(x, router, 8, scale)
    np.testing.assert_allclose(np.asarray(w0.sum(-1)), scale, rtol=1e-6)   # normalised, then scaled
    pushed = dict(router, bias=jnp.zeros((128,)).at[77].set(10.0))
    idx1, w1, scores1 = moe_lib.sigmoid_route(x, pushed, 8, scale)
    assert np.all(np.any(np.asarray(idx1) == 77, axis=-1)) \
        and not np.all(np.any(np.asarray(idx0) == 77, axis=-1))
    np.testing.assert_array_equal(np.asarray(scores), np.asarray(scores1))
    chosen = np.take_along_axis(np.asarray(scores1), np.asarray(idx1), axis=-1)
    np.testing.assert_allclose(np.asarray(w1), scale * chosen / chosen.sum(-1, keepdims=True), rtol=1e-6)
    # the reference's router is the same function of the same numbers
    cfg = dict(num_experts_per_tok=8, route_scale=scale)
    ridx, rw = ref.route(pushed, x, cfg, "float32")
    np.testing.assert_array_equal(np.sort(np.asarray(ridx)), np.sort(np.asarray(idx1)))
    np.testing.assert_allclose(np.sort(np.asarray(rw)), np.sort(np.asarray(w1)), rtol=1e-5)


def test_configuration_file_keeps_every_published_number():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        pytest.skip("no catalog on this machine")
    with open(catalog) as f:
        entry = next(json.loads(l) for l in f if '"Trinity-Mini"' in l)
    pub = entry["config"]
    assert FULL["source"] == entry["source_url"] and FULL["architecture"] == pub["model_type"] == "afmoe"
    changed = {k for k, v in pub.items() if FULL.get(k) != v}
    assert changed == {"num_hidden_layers", "num_dense_layers", "vocab_size", "layer_types"}
    assert set(FULL["reduced"]) == changed | {"num_experts"}
    assert FULL["published"] == {k: pub[k] for k in FULL["reduced"]}
    # every width as published
    for k in ("hidden_size", "intermediate_size", "moe_intermediate_size", "head_dim",
              "num_attention_heads", "num_key_value_heads", "num_experts", "num_experts_per_tok",
              "num_shared_experts", "sliding_window", "route_scale", "rms_norm_eps", "rope_theta"):
        assert FULL[k] == pub[k], k
    # the cut: one dense layer then the published order, both kinds present, the floors kept
    L, Ld = FULL["num_hidden_layers"], FULL["num_dense_layers"]
    assert Ld == 1 and L - Ld >= 4
    assert FULL["layer_types"] == pub["layer_types"][1:1 + L]
    assert {SLIDING, FULL_ATT} == set(FULL["layer_types"][Ld:]) and FULL["layer_types"][0] == SLIDING
    assert FULL["experts_held"] == {"first": 0, "count": 16} and 8 * 16 == pub["num_experts"]
    assert FULL["vocab_size"] * 8 == pub["vocab_size"]
    entry_b = next(c for c in _load("BENCHMARK.json")["configs"] if c["name"] == FULL["name"])
    assert set(entry_b["reduced"]) == set(FULL["reduced"]) and entry_b["source"] == FULL["source"]
    assert {"output_gate", "qk_norm", "full_attention_positions", "post_norms", "router_bias",
            "load_balance_coeff", "rope_convention", "weights", "source_checked"} <= set(FULL["assumed"])


def test_parameter_and_flop_arithmetic():
    # ISSUE 33 reads 134,488,576 a routed layer and 65,020,416 the dense one: it counted the two
    # head norms as 512 numbers (they are 2 x 128) and left the selection bias (128) out
    assert flops.routed_layer_params(FULL) == 134_488_448 == 134_488_576 - 512 + 256 + 128
    assert flops.dense_layer_params(FULL) == 65_020_160
    for n, total in ((4, 705_474_304), (6, 974_451_200), (7, 1_108_939_648)):
        cfg = dict(FULL, num_hidden_layers=1 + n, layer_types=FULL["published"]["layer_types"][1:2 + n])
        assert flops.total_params(cfg) == total == 167_520_512 + n * 134_488_448
    tiny_cfg = harness.merge_into(FULL, TINY["config"])
    n = sum(int(x.size) for x in jax.tree_util.tree_leaves(
        jax.eval_shape(lambda: ref.make_params(jnp.uint32(0), tiny_cfg))))
    assert n == flops.total_params(tiny_cfg)
    assert flops.uniform_held_experts_per_token(FULL) == 1.0
    # the band: W (W + 1) / 2 + (S - W) W pairs a head, the whole triangle where W >= S
    assert flash_window.band_positions(16384, 2048) == 2048 * 2049 // 2 + (16384 - 2048) * 2048
    assert flash_window.band_positions(128, 2048) == 128 * 129 // 2
    assert stack.band_positions(16384, 2048) == flash_window.band_positions(16384, 2048)
    share = flash_window.band_positions(16384, 2048) / (16384 * 16385 // 2)
    assert 0.23 < share < 0.24                         # what a window call should cost of a causal one
    assert flash_window.fwd(1, 32, 16384, 128, 2048) == 4 * 32 * 128 * flash_window.band_positions(16384, 2048)
    assert flash_window.bwd_dq(1, 32, 16384, 128, 2048) / flash_window.fwd(1, 32, 16384, 128, 2048) == 1.5
    assert flash_window.bwd_dkv(1, 32, 16384, 128, 2048) / flash_window.fwd(1, 32, 16384, 128, 2048) == 2.0
    # a window as long as the sequence executes the triangle, diagonal included
    assert flash_window.fwd(1, 32, 4096, 128, 4096) == pytest.approx(
        flash_flops.fwd(1, 32, 4096, 128) * (4097 / 4096))
    # more rows on the held experts, more required work; attention by each layer's own mask
    base = flops.train_flops_per_token(FULL, 16384, 0.0)
    assert flops.train_flops_per_token(FULL, 16384, 1.0) - base == pytest.approx(
        6 * 3 * 2048 * 1024 * flops.routed_layers(FULL))
    assert flops.train_flops_per_token(FULL, 16384) == flops.train_flops_per_token(FULL, 16384, 1.0)
    all_full = dict(FULL, layer_types=[FULL_ATT] * FULL["num_hidden_layers"])
    assert flops.train_flops_per_token(all_full, 16384) > flops.train_flops_per_token(FULL, 16384)
    # the program's own count (its mfu= line) is the same model's
    assert afmoe.flops_per_token(_args(FULL, "flash"), 16384) == pytest.approx(
        flops.train_flops_per_token(FULL, 16384))


# -- the trace readers --------------------------------------------------------------
def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(no, value):
    if isinstance(value, int):
        return _varint(no << 3) + _varint(value)
    value = value.encode() if isinstance(value, str) else value
    return _varint((no << 3) | 2) + _varint(len(value)) + value


def _xplane(ops, steps):
    """A device plane as the TPU profiler writes one (tsl xplane.proto), from
    ``ops`` [(name stack, instruction text, start_us, duration_us)] and
    ``steps`` [(start_us, duration_us)]."""
    metas, op_events = b"", b""
    for i, (stack, text, start, dur) in enumerate(ops, start=1):
        stat = _field(1, 1) + _field(5, stack)                        # stat 1 = tf_op
        metas += _field(4, _field(1, i) + _field(2, _field(1, i) + _field(2, text) + _field(5, stat)))
        op_events += _field(4, _field(1, i) + _field(2, start * 10 ** 6) + _field(3, dur * 10 ** 6))
    step_id = len(ops) + 1
    metas += _field(4, _field(1, step_id) + _field(2, _field(1, step_id) + _field(2, "step")))
    step_events = b"".join(_field(4, _field(1, step_id) + _field(2, s * 10 ** 6) + _field(3, d * 10 ** 6))
                           for s, d in steps)
    plane = (_field(2, "/device:TPU:0") + _field(3, _field(2, "XLA Ops") + op_events)
             + _field(3, _field(2, "Steps") + step_events) + metas
             + _field(5, _field(1, 1) + _field(2, _field(1, 1) + _field(2, "tf_op"))))
    return _field(1, plane)


def _read_metric(name, sources):
    readers = os.path.join(REPO, "benchmark", "layer_metrics")
    if readers not in sys.path:
        sys.path.insert(0, readers)
    spec = importlib.util.spec_from_file_location("m_" + name.replace(".", "_"),
                                                  os.path.join(readers, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(sources)


NEW_READERS = ("step_device_ms.attn_window", "step_device_ms.attn_global", "step_device_ms.attn_gate",
               "kernel_peak_pct.window_flash_fwd", "kernel_peak_pct.window_flash_bwd",
               "kernel_peak_pct.global_flash_fwd", "kernel_peak_pct.global_flash_bwd")


def _trace_dir(tmp_path, name, data):
    where = tmp_path / name / "plugins" / "profile" / "run"    # as the profiler lays it out
    where.mkdir(parents=True)
    (where / "t.xplane.pb").write_bytes(data)
    return str(tmp_path / name)


@pytest.mark.parametrize("stored", ["train_1chip_v5e", "train_1chip_v5e_scoped"])
def test_new_readers_find_nothing_in_a_trace_without_their_scopes(tmp_path, stored):
    """Run on the parent, or in a cell of another architecture, whose program
    has no such scope, each new reader returns None and raises nothing: the
    two stored traces of the benchmark (before the program had scopes, and
    with the vocabulary's)."""
    with gzip.open(os.path.join(REPO, "benchmark/tests/data", stored + ".xplane.pb.gz")) as src:
        work = _trace_dir(tmp_path, stored, src.read())
    sources = {"trace_dir": work, "peaks": {"bf16_flops": 197e12}, "sliding_window": 2048}
    assert {n: _read_metric(n, sources) for n in NEW_READERS} == dict.fromkeys(NEW_READERS)
    assert {n: _read_metric(n, {}) for n in NEW_READERS} == dict.fromkeys(NEW_READERS)


def test_new_readers_read_a_trace_with_the_scopes(tmp_path):
    """One step of 1,000 us: a window layer's forward call of 100 us and a full
    layer's of 400 at ``[1, 32, 16384, 128]``, each kind's two backward calls,
    a layout copy under each kind, the gate's two fusions."""
    pre = "jit(train_step)/jvp(jit(loss))/while/body/checkpoint/layer/"
    bwd = "jit(train_step)/transpose(jvp(jit(loss)))/while/body/checkpoint/layer/"
    call = lambda k: f"%{k}.1 = (bf16[1,32,16384,128]{{3,2,1,0}}, f32[1,32,1,16384]{{3,2,1,0}}) custom-call()"
    ops = [
        (pre + "cond/branch_1_fun/attn_window/attn_core/flash_fwd/pallas_call:", call("flash_fwd"), 0, 100),
        (pre + "cond/branch_0_fun/attn_global/attn_core/flash_fwd/pallas_call:", call("flash_fwd"), 100, 400),
        (bwd + "cond/branch_1_fun/attn_window/attn_core/flash_bwd_dq/pallas_call:", call("flash_bwd_dq"), 500, 60),
        (bwd + "cond/branch_1_fun/attn_window/attn_core/flash_bwd_dkv/pallas_call:", call("flash_bwd_dkv"), 560, 80),
        (bwd + "cond/branch_0_fun/attn_global/attn_core/flash_bwd_dq/pallas_call:", call("flash_bwd_dq"), 640, 100),
        (bwd + "cond/branch_0_fun/attn_global/attn_core/flash_bwd_dkv/pallas_call:", call("flash_bwd_dkv"), 740, 150),
        (pre + "cond/branch_1_fun/attn_window/attn_core/transpose", "%copy.1 = bf16[1,32,16384,128]{3,2,1,0} copy()", 890, 10),
        (pre + "cond/branch_0_fun/attn_global/attn_core/transpose", "%copy.2 = bf16[1,32,16384,128]{3,2,1,0} copy()", 900, 20),
        (pre + "attn_qkv/attn_gate/dot_general", "%fusion.1 = bf16[16384,4096]{1,0} fusion()", 920, 30),
        (pre + "attn_out/attn_gate/mul", "%fusion.2 = bf16[16384,4096]{1,0} fusion()", 950, 5),
        (pre + "ffn/dot_general", "%fusion.3 = bf16[16384,1024]{1,0} fusion()", 955, 45),
    ]
    sources = {"trace_dir": _trace_dir(tmp_path, "both", _xplane(ops, [(0, 1000)])),
               "peaks": {"bf16_flops": 197e12}, "sliding_window": 2048}
    got = {n: _read_metric(n, sources) for n in NEW_READERS}
    assert got["step_device_ms.attn_window"] == pytest.approx(0.250)
    assert got["step_device_ms.attn_global"] == pytest.approx(0.670)
    assert got["step_device_ms.attn_gate"] == pytest.approx(0.035)
    band, tri = flash_window.band_positions(16384, 2048), 16384 ** 2 / 2
    per_pair = 2 * 32 * 128                            # operations a (query, key) pair a matmul
    assert got["kernel_peak_pct.window_flash_fwd"] == pytest.approx(
        100 * 2 * per_pair * band / 100e-6 / 197e12)
    assert got["kernel_peak_pct.window_flash_bwd"] == pytest.approx(
        100 * 7 * per_pair * band / 140e-6 / 197e12)
    assert got["kernel_peak_pct.global_flash_fwd"] == pytest.approx(
        100 * 2 * per_pair * tri / 400e-6 / 197e12)
    assert got["kernel_peak_pct.global_flash_bwd"] == pytest.approx(
        100 * 7 * per_pair * tri / 250e-6 / 197e12)
    # without the configuration's window a band cannot be counted; the triangle can
    no_window = {k: v for k, v in sources.items() if k != "sliding_window"}
    assert _read_metric("kernel_peak_pct.window_flash_fwd", no_window) is None
    assert _read_metric("kernel_peak_pct.global_flash_fwd", no_window) is not None
    # the shared rows read this trace too: the kinds' kernels are attn_core's
    assert _read_metric("step_device_ms.attn_core", sources) == pytest.approx(0.920)


def test_cells_one_and_two_import_nothing_of_the_new_modules():
    """A llama or xing run pays nothing for this architecture: the registry
    imports models/afmoe.py only when a config names it, and the other kinds
    never its reference, count or kind."""
    code = ("import sys; sys.path.insert(0, %r)\n"
            "from benchmark.traffic_kinds import train_job, train_job_arch\n"
            "from mlx_cuda_distributed_pretraining_tpu.train.trainer import Trainer\n"
            "from mlx_cuda_distributed_pretraining_tpu.models.registry import resolve_architecture\n"
            "resolve_architecture('llama'); resolve_architecture('xing_mla_moe')\n"
            "assert 'afmoe' not in train_job_arch.MODEL_SECTIONS\n"
            "new = [m for m in sys.modules if m.endswith(('afmoe', 'flash_window', '_attn_kinds'))]\n"
            "assert not new, new\n"
            "assert resolve_architecture('afmoe').plans\n"
            "assert any(m.endswith('models.afmoe') for m in sys.modules)\n" % REPO)
    subprocess.run([sys.executable, "-c", code], check=True, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    with pytest.raises(ValueError, match="afmoe"):
        resolve_architecture("no_such_model")


@pytest.mark.parametrize("held_count", [2, 1], ids=["a_quarter_held", "an_eighth_held"])
def test_the_cell_rehearses_through_its_traffic_kind(held_count, tmp_path, monkeypatch):
    """``run.py --rehearse`` looks a kind up in rehearse.json, which is closed;
    this is the new cell's rehearsal: a Context at tiny widths, the kind's own
    ``run``: Trainer.train() on architecture afmoe from a dict config, the
    window, the events' counters, the reference's three steps, the comparison.
    With an eighth of the experts held, as in the cell, the chunk loops run at
    a small buffer or the whole one (tests/test_xing.py)."""
    # the window counts steps, not this machine's seconds (tests/test_xing.py has the reason)
    ticks = itertools.count()
    monkeypatch.setattr(kind.arch.base, "time", types.SimpleNamespace(
        perf_counter=lambda: 0.008 * next(ticks)))
    bench, cell, config, mix = harness.load_cell(CELL)
    assert mix["kind"] == "train_job_afmoe" and cell["chips"] == 1
    base_mix = _load("benchmark/traffic/pack4k-b4.json")
    differs = ("kind", "seq_len", "batch_size", "shape_seed", "documents")
    assert {k: v for k, v in mix.items() if k not in differs} == \
        {k: v for k, v in base_mix.items() if k not in differs}
    assert (mix["seq_len"], mix["batch_size"], mix["shape_seed"]) == (16384, 1, 20260928)
    assert mix["documents"] == {"median": 2400, "sigma": 1.2, "min": 16, "max": 16384,
                                "zipf_exponent": 1.1}
    config = harness.merge_into(config, TINY["config"])
    config["experts_held"] = dict(config["experts_held"], count=held_count)
    mix = harness.merge_into(mix, TINY["traffic"])
    cell = dict(cell, limits={k: 0.05 for k in cell["limits"]})
    if held_count == 1:   # one expert's three banks of 32 columns: their step-1 profile reads
        # 0.0709 in bfloat16 beside float32, to the last digit what one buffer size read
        cell["limits"]["first_grad_profile_gap"] = 0.1
    ctx = harness.Context(cell, config, mix, seed=3_000_000_019, seconds=1.5, trace=False,
                          rehearse=True, workdir=str(tmp_path), quiet=True)
    res = kind.run(ctx)
    assert res["correct"], res["check_numbers"]
    assert len(res["sources"]["timed_steps"]) == 62 and res["sources"]["sliding_window"] == 32
    assert len(res["check_numbers"]) == 3 + 3           # one term a step, three steps
    assert max(v for k, v in res["check_numbers"].items() if k.startswith("loss_gap")) < 1e-3
    events = res["sources"]["step_window_events"]
    assert events and all({"moe_rows_held", "moe_chunks_whole", "moe_load_max_over_mean",
                           "moe_drop"} <= set(e) for e in events)
    assert all(e["moe_drop"] == 0 and e["moe_rows_held"] > 0 for e in events)
    # 4 chunks a layer, 3 routed layers: a step counts the chunks of the layers in which some
    # chunk's held rows did not fit its small buffer, and with one buffer size none
    assert all(e["moe_chunks_whole"] in ((0, 4, 8, 12) if held_count == 1 else (0,)) for e in events)
    # the run's first window says what was traced: both kinds of layer, and no kernel here
    run_dir, = (os.path.join(tmp_path, "runs", d) for d in os.listdir(os.path.join(tmp_path, "runs")))
    first = next(e for e in train_job._read_events(run_dir) if e.get("type") == "step_window")
    plan = first["attn_plan"]
    assert plan["window_layers"] >= 2 and plan["global_layers"] >= 1     # the dense layer; the scan
    assert plan["window_simple"] == plan["window_layers"] and "flash_plan" in first
    assert first["moe_plan"]["dispatch_gather"] == first["moe_plan"]["combine_gather"] >= 1
    assert first["moe_plan"]["chunk_loop_tail"] >= 1      # the scanned stack's loop took the post-norm
    assert first["moe_plan"]["chunk_two_sizes"] == (held_count == 1)   # 2 of 8 held: one buffer size
    # off the chip the expert layers run the blocked backend: no gmm or tgmm call was traced
    assert not any(first["gmm_plan"].values()) and "gmm_resident" in first["gmm_plan"]
    assert res["end_to_end"]["train_tokens_per_s_per_chip"] > 0 and res["end_to_end"]["setup_s"] > 0
    assert flops.train_flops_per_token(config, mix["seq_len"], 0.0) < res["sources"]["flops_per_token"]
    # every metric the cell is listed under has a reader file, and the shares of a causal
    # call's peak do not list it: they would count its band calls as triangles
    listed = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", ())}
    for name in listed:
        assert os.path.isfile(os.path.join(REPO, "benchmark/layer_metrics", name + ".py"))
    assert set(NEW_READERS) <= listed and "kernel_peak_pct.gmm" in listed
    assert not {"kernel_peak_pct.flash_fwd", "kernel_peak_pct.flash_bwd",
                "kernel_peak_pct.mla_flash_fwd", "step_device_ms.mtp"} & listed


def test_the_flash_paths_are_tallied_by_kind(tiny):
    """With the kernels, ``attn_plan`` says for each kind which path
    ``flash_plan`` gives the forward and the two backward kernels."""
    cfg, _, params, batch = tiny
    before = core_counts()
    jax.eval_shape(lambda p: afmoe.loss_fn(p, batch, _args(cfg, "flash"), scan_layers=True)[0], params)
    traced = {k: n - before.get(k, 0) for k, n in core_counts().items() if n - before.get(k, 0)}
    assert traced == {"window_layers": 2, "global_layers": 1,
                      **{f"window_{k}_resident": 2 for k in ("fwd", "bwd_dq", "bwd_dkv")},
                      **{f"global_{k}_resident": 1 for k in ("fwd", "bwd_dq", "bwd_dkv")}}


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
        if len(shape) >= 2:
            assert "fsdp" in specs[path], (path, shape, specs[path])
        else:
            assert specs[path] in (P(), P(None)), (path, specs[path])
    assert param_pspec("layers.0.attention.wg.weight", (64, 64), mesh) == P("fsdp", "tp")
    assert param_pspec("layers.0.attention.q_norm.weight", (16,), mesh) == P(None)
    assert param_pspec("dense_layers.0.post_attention_norm.weight", (64,), mesh) == P(None)
    assert param_pspec("layers.1.feed_forward.router.bias", (8,), mesh) == P(None)


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
             "model": kind.arch.MODEL_SECTIONS["afmoe"](cfg, {"attention_type": "simple"}),
             "training": {"hyperparameters": {"batch_size": 4, "learning_rate": 1e-2, "iters": 4},
                          "scheduler": {"type": "constant"}, "optimization": {"optimizer": "adafactor"}},
             "logging": {"steps": {"logging_interval": 1, "checkpoint_interval": 0,
                                   "validation_interval": 0}},
             "system": {"seed": 0, "scan_layers": True, "remat": "full", "mesh": mesh}}
        tr = Trainer(Config.from_dict(d), runs_root=str(tmp_path / "runs"), quiet=True)
        tr.train()
        with open(os.path.join(tr.run_dir, "events.jsonl")) as f:
            events = [json.loads(l) for l in f]
        return [(e["loss"], e["moe_rows_held"]) for e in events if e.get("type") == "step_window"]

    one, sharded = run("one", {}), run("fsdp", {"fsdp": 2})
    assert len(one) == len(sharded) == 4
    np.testing.assert_allclose([s[0] for s in sharded], [o[0] for o in one], rtol=2e-4)
    assert [s[1] for s in sharded] == [o[1] for o in one]
    assert one[-1][0] < one[0][0]


def test_the_sample_config_trains_through_the_cli(tmp_path):
    """``train.py --config configs/model-config-afmoe-sample.yaml`` on the CPU."""
    (tmp_path / "train.jsonl").write_text("".join(
        json.dumps({"text": "the quick brown fox jumps over the lazy dog " * 8}) + "\n"
        for _ in range(200)))
    shutil.copy(tmp_path / "train.jsonl", tmp_path / "val.jsonl")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "train.py"), "--config",
         os.path.join(REPO, "configs/model-config-afmoe-sample.yaml"), "--runs-root",
         str(tmp_path / "runs"), "--iters", "6", "--batch-size", "2",
         "--set", "logging.steps.logging_interval=2"],
        cwd=tmp_path, capture_output=True, text=True, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    log = out.stdout + out.stderr
    assert re.search(r"Step 6: loss=", log), log[-1500:]
    assert "attention layers (traced, by kind and kernel path): " in log
    assert re.search(r"window_layers=\d+, .*global_layers=1", log)


def test_the_train_step_carries_the_scopes_the_metrics_read(tiny):
    """``attn_window`` and ``attn_global`` enclose ``attn_core`` on every
    operation of a layer's core, forward, recomputed and backward, in the two
    branches of the scan's ``cond`` and in the dense layer outside it;
    ``attn_gate`` sits inside ``attn_qkv`` and ``attn_out``; no operation of a
    core is left outside its kind."""
    cfg, _, params, batch = tiny
    args = _args(cfg, "flash")
    step = jax.jit(jax.grad(lambda p: afmoe.loss_fn(p, batch, args, remat="full", scan_layers=True)[0]))
    hlo = step.lower(params).compile().as_text()
    names = set(re.findall(r'op_name="([^"]+)"', hlo))
    stack = lambda n: [t for t in re.split(r"[/()]", n) if t]
    core = [n for n in names if "attn_core" in stack(n)]
    assert core and all(("attn_window" in stack(n)) != ("attn_global" in stack(n)) for n in core)
    for kind_scope in ("attn_window", "attn_global"):
        mine = [n for n in core if kind_scope in stack(n)]
        assert all(stack(n).index(kind_scope) < stack(n).index("attn_core") for n in mine)
        for when in ("rematted_computation", "transpose", "jvp"):
            assert any(when in stack(n) for n in mine), (kind_scope, when)
        for k in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
            assert any(k in stack(n) for n in mine), (kind_scope, k)
    assert any("cond" in stack(n) for n in core) and any("cond" not in stack(n) for n in core)
    gate = [n for n in names if "attn_gate" in stack(n)]
    assert any("attn_qkv" in stack(n) for n in gate) and any("attn_out" in stack(n) for n in gate)
    assert all("attn_qkv" in stack(n) or "attn_out" in stack(n) for n in gate)
    for scope in ("embed", "layer", "norm", "ffn", "moe_router", "moe_experts", "final_norm",
                  "lm_head_ce"):
        assert any(scope in stack(n) for n in names), scope
    # RoPE is a sliding layer's: no rotation (cos/sin) under the full kind's branch
    rotary = [n for n in names if stack(n)[-1] in ("cos", "sin")]
    assert rotary and all("attn_qkv" in stack(n) for n in rotary)


def test_a_wrong_mask_on_one_layer_fails_the_checks_limits(tiny, reference_step):
    """The program with one layer's kind swapped (a sliding layer attending
    to everything, without its rotation) against the reference, through the
    kind's own comparison under the cell's limits: not correct; with the
    right masks, correct."""
    cfg, args, params, batch = tiny
    (want_loss,), want_grads = reference_step
    limits = _load(f"benchmark/workloads/{CELL}.json")["limits"]
    names = train_job._leaf_names(params)

    def numbers(loss, grads):
        leaves = [np.asarray(g, np.float64) for g in jax.tree_util.tree_leaves(grads)]
        return {"losses": [float(loss)], "names": names,
                "grad_norms": [float(np.linalg.norm(g)) for g in leaves],
                "grad_profiles": [np.square(g).reshape(g.shape[0], -1).sum(-1) for g in leaves],
                "changes": [1.0] * len(leaves)}

    want = numbers(want_loss, want_grads)
    (loss, _), grads = _program_step(params, batch, args, True)
    assert train_job.compare(numbers(loss, grads), want, limits, lambda _: None)["ok"]
    types_ = list(cfg["layer_types"])
    assert types_[1] == SLIDING
    types_[1] = FULL_ATT
    wrong = _args(dict(cfg, layer_types=types_))
    (loss, _), grads = _program_step(params, batch, wrong, True)
    said = []
    verdict = train_job.compare(numbers(loss, grads), want, limits, said.append)
    assert not verdict["ok"], verdict["numbers"]
    assert any("OUTSIDE" in line for line in said)
