"""Architecture ``sambay`` (models/sambay.py) against the benchmark's plain
reference (benchmark/reference/sambay.py, which imports nothing of the
program), at tiny widths on seeded random weights, and the pieces this
architecture brought: the selective scan's two paths, the two tensors that
cross layers, differential attention at head sizes 16/32 here (64/128
published), the tied head, the new scopes, tallies and readers, and the
benchmark's traffic kind for it.
"""

import gzip
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
from benchmark.flops import flash_diff, ssm_scan
from benchmark.flops import sambay as flops
from benchmark.reference import sambay as ref
from benchmark.traffic_kinds import train_job
from benchmark.traffic_kinds import train_job_sambay as kind
from mlx_cuda_distributed_pretraining_tpu.config import Config
from mlx_cuda_distributed_pretraining_tpu.models import sambay
from mlx_cuda_distributed_pretraining_tpu.models.registry import resolve_architecture
from mlx_cuda_distributed_pretraining_tpu.ops import flash_attention as fa
from mlx_cuda_distributed_pretraining_tpu.ops.attention import core_counts
from mlx_cuda_distributed_pretraining_tpu.ops import selective_scan as ss
from test_afmoe import _read_metric, _trace_dir, _xplane

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "phi4-mini-flash-l6.train-seq16k"
B, S = 2, 64


def _load(path):
    with open(os.path.join(REPO, path)) as f:
        return json.load(f)


FULL = _load("benchmark/configs/phi4-mini-flash-l6.json")
TINY = _load("benchmark/rehearse_sambay.json")
# the catalog's row for Phi-4-mini-flash-reasoning (model-configs guide), its `config` whole
CATALOG = {"embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560, "intermediate_size": 10240,
           "layer_norm_eps": 1e-05, "max_position_embeddings": 262144, "mb_per_layer": 2,
           "model_type": "phi4flash", "num_attention_heads": 40, "num_hidden_layers": 32,
           "num_key_value_heads": 20, "resid_pdrop": 0, "sliding_window": 512,
           "tie_word_embeddings": True, "mlp_bias": False, "lm_head_bias": False,
           "vocab_size": 200064}


def _args(cfg, attention_type="simple"):
    model = kind.arch.MODEL_SECTIONS["sambay"](cfg, {"attention_type": attention_type})
    return sambay.SambaYArgs.from_config(Config.from_dict({"name": "t", "model": model}).model,
                                         cfg["vocab_size"])


@pytest.fixture(scope="module")
def tiny():
    """(configuration at tiny widths, seeded weights, a batch)."""
    cfg = harness.merge_into(FULL, TINY["config"])
    params = ref.init_params(7, cfg)
    toks = np.random.default_rng(0).integers(3, cfg["vocab_size"], size=(B, S + 1)).astype(np.int32)
    batch = {"inputs": jnp.asarray(toks[:, :-1]), "targets": jnp.asarray(toks[:, 1:]),
             "mask": jnp.ones((B, S), jnp.float32)}
    return cfg, params, batch


def _reference_step(cfg, params, batch):
    return jax.jit(lambda p: ref.loss_and_grads(p, batch["inputs"], batch["targets"], cfg))(params)


@pytest.fixture(scope="module")
def reference_step(tiny):
    return _reference_step(*tiny)


def _program_step(cfg, params, batch, attention_type="simple"):
    args = _args(cfg, attention_type)
    return jax.jit(jax.value_and_grad(lambda p: sambay.loss_fn(p, batch, args, remat="full"),
                                      has_aux=True))(params)


def _leaf_gaps(got, want):
    return {jax.tree_util.keystr(path): float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-12))
            for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                                    jax.tree_util.tree_leaves(want))}


# -- the model against the reference ------------------------------------------------
@pytest.mark.parametrize("attention_type,backend", [("simple", "xla"), ("flash", "kernel")])
def test_program_matches_reference_loss_and_every_gradient(tiny, reference_step, monkeypatch,
                                                           attention_type, backend):
    """Kinds ``M S M F G C`` under full remat: through the XLA scan and the simple
    attention, and through the scan's kernels and the flash kernels (16/32-wide
    heads here), both interpreted."""
    monkeypatch.setenv("SSM_BACKEND", backend)
    cfg, params, batch = tiny
    (want_loss,), want = reference_step
    (loss, count), got = _program_step(cfg, params, batch, attention_type)
    assert float(count) == B * S
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=2e-6)
    gaps = _leaf_gaps(got, want)
    assert len(gaps) == len(jax.tree_util.tree_leaves(params))
    assert max(gaps.values()) < 5e-4, sorted(gaps.items(), key=lambda kv: -kv[1])[:3]


def test_the_trees_are_one_tree(tiny):
    """The program's initialiser and the reference's make the same tree, leaf
    for leaf; the benchmark puts the reference's weights in the program's place."""
    cfg, params, _ = tiny
    own = sambay.init_params(jax.random.PRNGKey(0), _args(cfg))
    assert jax.tree_util.tree_structure(own) == jax.tree_util.tree_structure(params)
    assert [a.shape for a in jax.tree_util.tree_leaves(own)] == \
        [a.shape for a in jax.tree_util.tree_leaves(params)]
    ssm = own["layers"][0]["ssm"]
    np.testing.assert_allclose(np.exp(ssm["A_log"][3]), np.arange(1, cfg["ssm"]["d_state"] + 1), rtol=1e-6)
    step = jax.nn.softplus(ssm["dt_proj"]["bias"])
    assert 1e-3 * 0.999 <= float(step.min()) and float(step.max()) <= 1e-1 * 1.001
    assert float(ssm["D"].min()) == 1.0 and "output" not in own


def test_forward_gives_the_references_logits_and_has_no_cache(tiny):
    cfg, params, batch = tiny
    got, _ = sambay.forward(params, batch["inputs"], _args(cfg))
    want = ref.logits_at(params, batch["inputs"], cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    with pytest.raises(NotImplementedError, match="recurrent-state cache"):
        sambay.forward(params, batch["inputs"], _args(cfg), cache={})


def _zeroed(params, layer, path):
    """``params`` with every leaf under ``layers[layer][path...]`` zero."""
    out = jax.tree_util.tree_map(lambda a: a, params)
    node = out["layers"][layer]
    for key in path[:-1]:
        node = node[key]
    node[path[-1]] = jax.tree_util.tree_map(jnp.zeros_like, node[path[-1]])
    return out


def test_a_gmu_layers_gradient_reaches_the_mamba_layer_that_made_the_memory(tiny):
    """With layer 2's ``out_proj`` zero its mixer adds nothing to the residual:
    what its other parameters get comes through the memory alone, from the G
    layer, and equals the reference's."""
    cfg, params, batch = tiny
    cut = _zeroed(params, 2, ("ssm", "out_proj"))
    _, got = _program_step(cfg, cut, batch)
    _, want = _reference_step(cfg, cut, batch)
    mine, theirs = got["layers"][2]["ssm"], want["layers"][2]["ssm"]
    for name in ("in_proj", "conv", "x_proj", "dt_proj", "A_log", "D"):
        for a, b in zip(jax.tree_util.tree_leaves(mine[name]), jax.tree_util.tree_leaves(theirs[name])):
            assert float(jnp.linalg.norm(b)) > 0, name
            assert float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b)) < 5e-4, name
    # and with the G layer cut off from it, nothing: the memory is the only way
    no_reader = _zeroed(cut, 4, ("gmu", "w2"))
    _, none = _program_step(cfg, no_reader, batch)
    assert float(jnp.linalg.norm(none["layers"][2]["ssm"]["x_proj"]["weight"])) == 0.0
    # the first Mamba layer's scan output is no memory: layer 2's is
    assert _args(cfg).memory_layer == 2 and _args(cfg).kv_layer == 3


def test_a_cross_layers_gradient_reaches_the_full_layers_keys_and_values(tiny):
    """With the F layer's ``wo`` zero its own attention adds nothing: the key and
    value columns of its ``wqkv`` are reached through the C layer alone, the query
    columns not at all; both as in the reference."""
    cfg, params, batch = tiny
    cut = _zeroed(params, 3, ("attention", "wo"))
    _, got = _program_step(cfg, cut, batch)
    _, want = _reference_step(cfg, cut, batch)
    q_cols = cfg["num_attention_heads"] * cfg["head_dim"]
    for leaf in ("weight", "bias"):
        a, b = got["layers"][3]["attention"]["wqkv"][leaf], want["layers"][3]["attention"]["wqkv"][leaf]
        assert float(jnp.linalg.norm(a[..., :q_cols])) == 0.0 == float(jnp.linalg.norm(b[..., :q_cols]))
        assert float(jnp.linalg.norm(b[..., q_cols:])) > 0
        assert float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b)) < 5e-4
    assert "wqkv" not in params["layers"][5]["attention"] and "wq" in params["layers"][5]["attention"]


@pytest.mark.parametrize("kind_,flash", [("S", False), ("F", False), ("F", True)])
def test_differential_attention_is_two_softmax_maps_and_their_normed_difference(tiny, kind_, flash):
    """``attention_mixer`` (one call over the stacked heads) against the explicit
    form: per query pair two softmax maps over the pair group's 2D-wide values."""
    cfg, params, _ = tiny
    layer = {"S": 1, "F": 3}[kind_]
    p = params["layers"][layer]["attention"]
    H, G, D, W = (cfg[k] for k in ("num_attention_heads", "num_key_value_heads", "head_dim",
                                   "sliding_window"))
    u = jax.random.normal(jax.random.PRNGKey(3), (B, S, cfg["hidden_size"]))
    got, _ = sambay.attention_mixer(p, u, _args(cfg, "flash" if flash else "simple"), kind_, layer)
    qkv = np.asarray(u @ p["wqkv"]["weight"] + p["wqkv"]["bias"], np.float64)
    q, k, v = np.split(qkv, [H * D, (H + G) * D], axis=-1)
    q, k = q.reshape(B, S, H // 2, 2, D), k.reshape(B, S, G // 2, 2, D)
    vbar = v.reshape(B, S, G // 2, 2 * D)
    t, s = np.arange(S)[:, None], np.arange(S)[None, :]
    seen = (s <= t) & ((t - s < W) if kind_ == "S" else True)
    lam_init = 0.8 - 0.6 * np.exp(-0.3 * layer)
    f = lambda n: np.asarray(p[n], np.float64)
    lam = np.exp(f("lambda_q1") @ f("lambda_k1")) - np.exp(f("lambda_q2") @ f("lambda_k2")) + lam_init
    out = np.zeros((B, S, H // 2, 2 * D))
    for j in range(H // 2):
        maps = []
        for i in (0, 1):
            sc = np.einsum("btd,bsd->bts", q[:, :, j, i], k[:, :, j // (H // G), i]) / np.sqrt(D)
            sc = np.where(seen, sc, -np.inf)
            pr = np.exp(sc - sc.max(-1, keepdims=True))
            maps.append(np.einsum("bts,bsd->btd", pr / pr.sum(-1, keepdims=True), vbar[:, :, j // (H // G)]))
        d = maps[0] - lam * maps[1]
        out[:, :, j] = d / np.sqrt((d * d).mean(-1, keepdims=True) + 1e-5) \
            * np.asarray(p["subln"]["weight"], np.float64) * (1 - lam_init)
    want = out.reshape(B, S, H * D) @ np.asarray(p["wo"]["weight"], np.float64) + np.asarray(p["wo"]["bias"])
    np.testing.assert_allclose(np.asarray(got), want, atol=3e-5)
    assert sambay.lambda_init(0) == pytest.approx(0.2) and 0.55 < sambay.lambda_init(3) < 0.56


# -- the selective scan ---------------------------------------------------------------
def _sequential_scan(c, delta, A, Bm, Cm, D):
    def step(h, xs):
        x, dt, b, cc = xs
        h = jnp.exp(dt[..., None] * A) * h + (dt * x)[..., None] * b[:, None, :]
        return h, jnp.einsum("bdn,bn->bd", h, cc) + D * x

    h0 = jnp.zeros((c.shape[0], c.shape[2], A.shape[1]))
    _, ys = jax.lax.scan(step, h0, tuple(a.swapaxes(0, 1) for a in (c, delta, Bm, Cm)))
    return ys.swapaxes(0, 1)


@pytest.fixture(scope="module")
def scan_case():
    Bt, T, Di, N = 2, 64, 160, 4          # 160 channels: padded to a block of 1,024
    ks = jax.random.split(jax.random.PRNGKey(0), 7)
    ops = (jax.random.normal(ks[0], (Bt, T, Di)),
           jax.nn.softplus(jax.random.normal(ks[1], (Bt, T, Di)) - 2),
           -jnp.exp(jax.random.normal(ks[2], (Di, N)) * 0.5),
           jax.random.normal(ks[3], (Bt, T, N)), jax.random.normal(ks[4], (Bt, T, N)),
           jax.random.normal(ks[5], (Di,)))
    w = jax.random.normal(ks[6], (Bt, T, Di))
    want = jax.value_and_grad(lambda *a: jnp.sum(_sequential_scan(*a) * w), argnums=range(6))(*ops)
    return ops, w, want


@pytest.mark.parametrize("backend", ["kernel", "xla"])
def test_selective_scan_matches_a_sequential_scan_value_and_all_six_gradients(scan_case, backend):
    """Four chunks of 16 steps: the kernels (interpreted) carry the state and the
    state's cotangent across chunks, the XLA form scans checkpointed chunks."""
    ops, w, (want_value, want_grads) = scan_case
    before = ss.plan_counts()
    value, grads = jax.value_and_grad(
        lambda *a: jnp.sum(ss.selective_scan(*a, backend=backend, chunk=16) * w), argnums=range(6))(*ops)
    np.testing.assert_allclose(float(value), float(want_value), rtol=2e-6)
    for name, g, wg in zip(("c", "delta", "A", "B", "C", "D"), grads, want_grads):
        assert g.shape == wg.shape and g.dtype == wg.dtype
        assert float(jnp.max(jnp.abs(g - wg)) / jnp.max(jnp.abs(wg))) < 2e-5, name
    traced = {k: n - before.get(k, 0) for k, n in ss.plan_counts().items() if n - before.get(k, 0)}
    assert traced == ({"fwd_kernel": 1, "bwd_kernel": 1, "fwd_kernel_chunk16": 1, "bwd_kernel_chunk16": 1}
                      if backend == "kernel" else {"xla": 1, "xla_chunk16": 1})


def test_the_scan_plans_from_shapes_and_backend(monkeypatch):
    assert ss.ssm_plan(16384, "kernel") == ss.SsmPlan("kernel", 128)
    assert ss.ssm_plan(16384, "xla") == ss.SsmPlan("xla", 256)
    assert ss.ssm_plan(96, "kernel") == ss.SsmPlan("kernel", 32)        # halved until it divides
    assert ss.ssm_plan(100, "kernel") == ss.SsmPlan("xla", 4)           # no chunk of 8 divides 100
    assert ss.default_backend() == "xla"                                # off the TPU
    monkeypatch.setenv("SSM_BACKEND", "kernel")
    assert ss.default_backend() == "kernel"
    with pytest.raises(ValueError, match="kernel | xla"):
        ss.ssm_plan(64, "cuda")


def test_the_scan_takes_the_xla_form_under_a_mesh(scan_case):
    """GSPMD cannot partition a Mosaic kernel and the kernels have no shard_map."""
    from jax.sharding import Mesh

    from mlx_cuda_distributed_pretraining_tpu.parallel.context import use_mesh

    ops, _, _ = scan_case
    before = ss.plan_counts()
    with use_mesh(Mesh(np.array(jax.devices()[:2]), ("fsdp",))):
        jax.eval_shape(lambda *a: ss.selective_scan(*a, backend="kernel"), *ops)
    assert ss.plan_counts()["xla"] == before["xla"] + 1
    assert ss.plan_counts()["fwd_kernel"] == before["fwd_kernel"]


# -- counts, configuration, benchmark entries -------------------------------------------
def test_total_params_is_the_tree_and_the_published_count(tiny):
    cfg, params, _ = tiny
    assert flops.total_params(cfg) == sum(int(a.size) for a in jax.tree_util.tree_leaves(params))
    assert flops.total_params(FULL) == 1_145_237_632
    assert flops.total_params(FULL, published=True) == 3_852_562_944
    assert flops.layer_params(FULL) == {"M": 119_895_040, "S": 98_322_304, "F": 98_322_304,
                                        "G": 104_867_840, "C": 91_766_144}
    shapes = jax.eval_shape(lambda: ref.make_params(jnp.uint32(0), FULL))
    assert sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes)) == 1_145_237_632
    table = FULL["hidden_size"] * FULL["vocab_size"]
    assert table == 512_163_840 and 0.446 < table / flops.matmul_params(FULL) < 0.448


def test_flop_counts(tiny):
    """6 a multiplied weight with the table once, and both softmax maps of the
    one band layer and the two triangle layers; the program's own count (its
    ``mfu=`` line) is the same model's; the scan is counted apart."""
    S16 = 16384
    tri, band = S16 * (S16 + 1) // 2, 512 * 513 // 2 + (S16 - 512) * 512
    assert flops.attention_pairs(FULL, S16) == 2 * tri + band
    assert flops.train_flops_per_token(FULL, S16) == pytest.approx(
        6 * flops.matmul_params(FULL) + 3 * 40 * 2 * (64 + 128) * (2 * tri + band) / S16)
    assert sambay.flops_per_token(_args(FULL, "flash"), S16) == pytest.approx(
        flops.train_flops_per_token(FULL, S16))
    assert sambay.matmul_params_per_token(_args(FULL)) == flops.matmul_params(FULL)
    # a 64/128 causal call: 2 (64 + 128) a pair forward, the backward's two kernels 2.5 and 2 times it
    assert flash_diff.fwd(1, 80, S16, 128) == 80 * tri * 2 * 192
    assert flash_diff.bwd_dq(1, 80, S16, 64) + flash_diff.bwd_dkv(1, 80, S16, 64) == 80 * tri * 2 * 640
    with pytest.raises(ValueError, match="64/128"):
        flash_diff.fwd(1, 80, S16, 64)
    # the scan: 0.50 GB a layer forward at two bytes an element (ISSUE 43), 1.0 GB at the kernels' four
    assert ssm_scan.fwd_bytes(1, S16, 5120, 16, itemsize=2) == pytest.approx(0.5e9, rel=0.02)
    assert ssm_scan.fwd_bytes(1, S16, 5120, 16) == 4 * (3 * S16 * 5120 + 2 * S16 * 16 + 5120 * 17)
    assert ssm_scan.bwd_bytes(1, S16, 5120, 16) > ssm_scan.fwd_bytes(1, S16, 5120, 16)
    assert ssm_scan.state_updates(1, S16, 5120, 16) == pytest.approx(1.34e9, rel=0.01)


def test_the_configuration_file_says_what_the_issue_says():
    bench = _load("BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == "phi4-mini-flash-l6")
    # the fourth configuration and cell; later ones are appended behind them
    assert entry == bench["configs"][3] and entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == FULL["source"] and entry["file"] == "benchmark/configs/phi4-mini-flash-l6.json"
    # every number of the catalog's row under the same key; only the depth differs
    assert {k: FULL[k] for k in CATALOG if k != "num_hidden_layers"} == \
        {k: v for k, v in CATALOG.items() if k != "num_hidden_layers"}
    assert FULL["num_hidden_layers"] == 6 and FULL["published"]["num_hidden_layers"] == 32
    assert list(FULL["reduced"]) == ["num_hidden_layers"] and FULL["architecture"] == "sambay"
    assert FULL["layer_kinds"] == ["M", "S", "M", "F", "G", "C"]
    assert FULL["ssm"] == {"d_state": 16, "d_conv": 4, "expand": 2, "dt_rank": 160}
    pub = FULL["published"]["layer_kinds"]
    assert "".join(pub) == "MS" * 8 + "MF" + "GC" * 7 and pub[16] == "M" and pub[17] == "F"
    assert {"mamba_sizes", "layout", "differential_attention", "biases", "memory", "positions",
            "window_edge", "packing", "weights"} <= set(FULL["assumed"])
    assert {"layer_ratio", "head_share"} <= set(FULL["distorts"])
    assert FULL["precision"]["control"] == "fp8" and FULL["head_dim"] == 64
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell == bench["workloads"][3] and cell["chips"] == 1
    assert (cell["config"], cell["traffic"]) == ("phi4-mini-flash-l6", "pack16k-sambay")
    mix, other = _load("benchmark/traffic/pack16k-sambay.json"), _load("benchmark/traffic/pack16k-afmoe.json")
    differs = ("kind", "shape_seed")
    assert {k: v for k, v in mix.items() if k not in differs} == \
        {k: v for k, v in other.items() if k not in differs} and list(mix) == list(other)
    assert (mix["kind"], mix["shape_seed"]) == ("train_job_sambay", 20260930)


NEW_READERS = ("step_device_ms.ssm", "step_device_ms.ssm_scan", "step_device_ms.gmu",
               "step_device_ms.attn_diff", "kernel_hbm_pct.ssm_scan_fwd", "kernel_hbm_pct.ssm_scan_bwd",
               "kernel_peak_pct.diff_flash_fwd", "kernel_peak_pct.diff_flash_bwd")


def test_the_cell_is_declared_for_the_metrics_it_can_report():
    bench = _load("BENCHMARK.json")
    listed = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", ())}
    for name in listed:
        assert os.path.isfile(os.path.join(REPO, "benchmark/layer_metrics", name + ".py"))
    assert set(NEW_READERS) <= listed
    names = [m["name"] for m in bench["per_layer"]]
    mine = bench["per_layer"][names.index(NEW_READERS[0]):][:len(NEW_READERS)]   # one run of entries
    assert [m["name"] for m in mine] == list(NEW_READERS)
    assert all(m["workloads"] == [CELL] and m["moves"] == "train_tokens_per_s_per_chip"
               for m in mine)
    assert {"train_mfu_pct", "step_hbm_gib", "step_device_ms.attn_window",
            "step_device_ms.attn_global", "step_device_ms.lm_head_ce"} <= listed
    # the shares that count D off a 128-wide output would over-count a 64/128 call
    assert not {n for n in listed if n.startswith("kernel_peak_pct.") and "diff_flash" not in n}
    assert not {"step_device_ms.moe", "step_device_ms.attn_gate", "moe_rows_held_per_step"} & listed
    e2e = next(m for m in bench["end_to_end"] if m["name"] == "train_tokens_per_s_per_chip")
    assert e2e["workloads"][3] == CELL and e2e["bound"] == 0.01


# -- the trace readers --------------------------------------------------------------------
@pytest.mark.parametrize("stored", ["train_1chip_v5e", "train_1chip_v5e_scoped"])
def test_new_readers_find_nothing_in_a_trace_without_their_scopes(tmp_path, stored):
    """Run on the parent, or in a cell of another architecture, each new reader
    returns None and raises nothing."""
    with gzip.open(os.path.join(REPO, "benchmark/tests/data", stored + ".xplane.pb.gz")) as src:
        work = _trace_dir(tmp_path, stored, src.read())
    sources = {"trace_dir": work, "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}}
    assert {n: _read_metric(n, sources) for n in NEW_READERS} == dict.fromkeys(NEW_READERS)
    assert {n: _read_metric(n, {}) for n in NEW_READERS} == dict.fromkeys(NEW_READERS)


def _trace_ops():
    pre = "jit(train_step)/jvp(jit(loss))/checkpoint/layer/"
    bwd = "jit(train_step)/transpose(jvp(jit(loss)))/checkpoint/layer/"
    y5, hb = "f32[1,16384,5,8,128]{4,3,2,1,0}", "f32[1,128,5,16,8,128]{5,4,3,2,1,0}"
    flat, da, dd = "f32[1,5,128,1,2048]{4,3,2,1,0}", "f32[1,5,16,8,128]{4,3,2,1,0}", "f32[1,5,8,128]{3,2,1,0}"
    flash = lambda k, d: (f"%{k}.1 = (bf16[1,80,16384,{d}]{{3,2,1,0}}, f32[1,80,1,16384]{{3,2,1,0}}) "
                          "custom-call()")
    return [
        (pre + "ssm/ssm_scan_fwd/pallas_call:", f"%ssm_scan_fwd.1 = ({y5}, {hb}) custom-call()", 0, 70),
        (bwd + "ssm/ssm_scan_bwd/pallas_call:",
         f"%ssm_scan_bwd.1 = ({y5}, {y5}, {flat}, {flat}, {da}, {dd}) custom-call()", 70, 170),
        (pre + "ssm/ssm_proj/dot_general", "%fusion.1 = bf16[16384,10240]{1,0} fusion()", 240, 60),
        (pre + "ssm/ssm_conv/mul", "%fusion.2 = f32[16384,5120]{1,0} fusion()", 300, 10),
        (pre + "gmu/dot_general", "%fusion.3 = bf16[16384,5120]{1,0} fusion()", 310, 25),
        (pre + "attn_global/attn_core/flash_fwd/pallas_call:", flash("flash_fwd", 128), 335, 200),
        (bwd + "attn_global/attn_core/flash_bwd_dq/pallas_call:", flash("flash_bwd_dq", 64), 535, 150),
        (bwd + "attn_global/attn_core/flash_bwd_dkv/pallas_call:", flash("flash_bwd_dkv", 64), 685, 250),
        (pre + "attn_diff/sub", "%fusion.4 = bf16[16384,2560]{1,0} fusion()", 935, 15),
        (pre + "ffn/dot_general", "%fusion.5 = bf16[16384,10240]{1,0} fusion()", 950, 50),
    ]


def test_new_readers_read_a_trace_with_the_scopes(tmp_path):
    """One step of 1,000 us: a scan's forward call of 70 us and its backward of
    170 at ``[1, 16384, 5120]`` x 16, the mixer's other work, a GMU's matmul, a
    64/128 causal forward of 200 us with its two backward kernels, the difference."""
    sources = {"trace_dir": _trace_dir(tmp_path, "t", _xplane(_trace_ops(), [(0, 1000)])),
               "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}}
    got = {n: _read_metric(n, sources) for n in NEW_READERS}
    assert got["step_device_ms.ssm"] == pytest.approx(0.310)
    assert got["step_device_ms.ssm_scan"] == pytest.approx(0.240)
    assert got["step_device_ms.gmu"] == pytest.approx(0.025)
    assert got["step_device_ms.attn_diff"] == pytest.approx(0.015)
    dims = (1, 16384, 5120, 16)
    assert got["kernel_hbm_pct.ssm_scan_fwd"] == pytest.approx(
        100 * ssm_scan.fwd_bytes(*dims) / 70e-6 / 819e9)
    assert got["kernel_hbm_pct.ssm_scan_bwd"] == pytest.approx(
        100 * ssm_scan.bwd_bytes(*dims) / 170e-6 / 819e9)
    tri = 16384 * 16385 // 2
    assert got["kernel_peak_pct.diff_flash_fwd"] == pytest.approx(
        100 * 80 * tri * 2 * 192 / 200e-6 / 197e12)
    assert got["kernel_peak_pct.diff_flash_bwd"] == pytest.approx(
        100 * 80 * tri * 2 * 640 / 400e-6 / 197e12)
    # the accepted rows read this trace too
    assert _read_metric("step_device_ms.attn_global", sources) == pytest.approx(0.600)
    assert _read_metric("step_device_ms.attn_core", sources) == pytest.approx(0.600)


def test_the_diff_readers_refuse_calls_of_another_width(tmp_path):
    """A 128/128 call under ``attn_global`` (cell 3's full layers) is not a 64/128 one."""
    pre = "jit(train_step)/jvp(jit(loss))/layer/attn_global/attn_core/"
    call = "%flash_fwd.1 = (bf16[1,32,16384,128]{3,2,1,0}, f32[1,32,1,16384]{3,2,1,0}) custom-call()"
    bwd = "%flash_bwd_dq.1 = bf16[1,32,16384,128]{3,2,1,0} custom-call()"
    ops = [(pre + "flash_fwd/pallas_call:", call, 0, 100), (pre + "flash_bwd_dq/pallas_call:", bwd, 100, 100),
           (pre + "flash_bwd_dkv/pallas_call:", bwd.replace("dq", "dkv"), 200, 100)]
    sources = {"trace_dir": _trace_dir(tmp_path, "t", _xplane(ops, [(0, 1000)])),
               "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}}
    assert _read_metric("kernel_peak_pct.diff_flash_bwd", sources) is None
    assert _read_metric("kernel_peak_pct.global_flash_fwd", sources) is not None


# -- scopes, tallies, rules ------------------------------------------------------------------
def test_the_train_step_carries_the_scopes_the_metrics_read(tiny, monkeypatch):
    """``ssm`` encloses ``ssm_proj``, ``ssm_conv`` and the two kernels' names;
    ``gmu`` a G mixer; ``attn_window`` / ``attn_global`` enclose ``attn_core`` and
    the flash kernels; ``attn_diff`` the difference; forward, recomputed and
    backward."""
    monkeypatch.setenv("SSM_BACKEND", "kernel")
    cfg, params, batch = tiny
    args = _args(cfg, "flash")
    step = jax.jit(jax.grad(lambda p: sambay.loss_fn(p, batch, args, remat="full")[0]))
    names = set(re.findall(r'op_name="([^"]+)"', step.lower(params).compile().as_text()))
    stack = lambda n: [t for t in re.split(r"[/()]", n) if t]
    under = lambda scope: [n for n in names if scope in stack(n)]
    for inner in ("ssm_proj", "ssm_conv", "ssm_scan_fwd", "ssm_scan_bwd"):
        assert under(inner) and all("ssm" in stack(n) for n in under(inner)), inner
    for scope in ("ssm", "gmu", "attn_diff", "attn_window", "attn_global"):
        for when in ("rematted_computation", "transpose", "jvp"):
            assert any(when in stack(n) for n in under(scope)), (scope, when)
    core = under("attn_core")
    assert core and all(("attn_window" in stack(n)) != ("attn_global" in stack(n)) for n in core)
    for kind_scope in ("attn_window", "attn_global"):
        for k in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
            assert any(k in stack(n) for n in under(kind_scope)), (kind_scope, k)
    for scope in ("embed", "layer", "norm", "attn_qkv", "attn_out", "ffn", "final_norm", "lm_head_ce"):
        assert under(scope), scope
    assert not [n for n in under("ssm_scan_fwd") if "gmu" in stack(n) or "attn_core" in stack(n)]


def test_the_tallies_say_what_a_step_traced(tiny, monkeypatch):
    """``ssm_plan``: layers by kind and the scans by path and chunk; ``attn_plan``:
    each attention kind with its kernels' paths; cells 1-3's ``flash_plan`` keys
    are as they were (the 64/128 calls are counted under the same keys)."""
    monkeypatch.setenv("SSM_BACKEND", "kernel")
    cfg, params, batch = tiny
    before = (sambay.ssm_plan_counts(), core_counts(), fa.plan_counts())
    jax.eval_shape(jax.grad(lambda p: sambay.loss_fn(p, batch, _args(cfg, "flash"))[0]), params)
    after = (sambay.ssm_plan_counts(), core_counts(), fa.plan_counts())
    ssm_, attn, flash = ({k: n - b.get(k, 0) for k, n in a.items() if n - b.get(k, 0)}
                         for a, b in zip(after, before))
    assert ssm_ == {"mamba_layers": 2, "gmu_layers": 1, "scan_fwd_kernel": 2, "scan_bwd_kernel": 2,
                    "scan_fwd_kernel_chunk64": 2, "scan_bwd_kernel_chunk64": 2}
    assert attn == {f"{k}_{w}": 1 for k in ("window", "global", "cross")
                    for w in ("layers", "fwd_resident", "bwd_dq_resident", "bwd_dkv_resident")}
    assert flash == {"resident": 3, "bwd_dq_resident": 3, "bwd_dkv_resident": 3}
    assert set(fa.plan_counts()) == set(fa._PLAN_KEYS)
    arch = resolve_architecture("sambay")
    assert set(arch.plans) == {"attn_plan", "ssm_plan"} and arch.flops_per_token is sambay.flops_per_token


def test_flash_plan_takes_heads_of_64_beside_values_of_128():
    """By shapes only: at 16,384 positions all three kernels hold their operands
    resident at 64/128, as at 128/128 (cell 3) and 192/128 (cell 2)."""
    for k in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert fa.flash_plan(16384, 16384, 64, jnp.bfloat16, kernel=k, Dv=128).path == "resident"
        assert fa.flash_plan(16384, 16384, 128, jnp.bfloat16, kernel=k).path == "resident"
        assert fa.flash_plan(4096, 4096, 192, jnp.bfloat16, kernel=k, Dv=128).path == "resident"


def test_sharding_rules_cover_the_new_leaves(tiny):
    """Every leaf of the architecture is matched by a rule of its own (none falls
    to the replicated default), and under fsdp every matrix of a matmul is split
    along a dimension the axis divides."""
    from jax.sharding import Mesh, PartitionSpec as P

    from mlx_cuda_distributed_pretraining_tpu.parallel import sharding_rules
    from mlx_cuda_distributed_pretraining_tpu.utils.tree import flatten_dict

    _, params, _ = tiny
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("fsdp", "tp"))
    specs = flatten_dict(sharding_rules.tree_pspecs(params, mesh))
    shapes = {k: v.shape for k, v in flatten_dict(params).items()}
    assert set(specs) == set(shapes)
    for path, shape in shapes.items():
        assert any(re.search(pat, path) for pat, _ in sharding_rules._RULES), path
        replicated = len(shape) < 2 or path.endswith(("conv.weight", "A_log"))
        assert ("fsdp" in specs[path]) != replicated, (path, shape, specs[path])
    pspec = lambda path, shape: sharding_rules.param_pspec(path, shape, mesh)
    assert pspec("layers.1.attention.wqkv.weight", (64, 128)) == P("fsdp", "tp")
    assert pspec("layers.0.ssm.in_proj.weight", (64, 256)) == P("fsdp", None)
    assert pspec("layers.0.ssm.out_proj.weight", (128, 64)) == P(None, "fsdp")
    assert pspec("layers.4.gmu.w1.weight", (64, 128)) == P("fsdp", "tp")
    assert pspec("layers.4.gmu.w2.weight", (128, 64)) == P("tp", "fsdp")
    assert pspec("layers.1.attention.lambda_q1", (16,)) == P(None)
    assert pspec("layers.1.attention.subln.weight", (32,)) == P(None)


def test_cells_one_to_three_import_nothing_of_the_new_modules():
    """A llama, xing or afmoe run pays nothing for this architecture."""
    code = ("import sys; sys.path.insert(0, %r)\n"
            "from benchmark.traffic_kinds import train_job, train_job_arch, train_job_afmoe\n"
            "from mlx_cuda_distributed_pretraining_tpu.train.trainer import Trainer\n"
            "from mlx_cuda_distributed_pretraining_tpu.models.registry import resolve_architecture\n"
            "resolve_architecture('llama'); resolve_architecture('afmoe')\n"
            "assert 'sambay' not in train_job_arch.MODEL_SECTIONS\n"
            "new = [m for m in sys.modules if m.endswith(('sambay', 'selective_scan', 'ssm_scan', "
            "'flash_diff'))]\n"
            "assert not new, new\n"
            "assert set(resolve_architecture('sambay').plans) == {'attn_plan', 'ssm_plan'}\n"
            "assert any(m.endswith('ops.selective_scan') for m in sys.modules)\n" % REPO)
    subprocess.run([sys.executable, "-c", code], check=True, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    with pytest.raises(ValueError, match="sambay"):
        resolve_architecture("no_such_model")


def test_from_config_refuses_a_stack_whose_readers_come_before_their_producer():
    model = kind.arch.MODEL_SECTIONS["sambay"](harness.merge_into(FULL, TINY["config"]),
                                               {"attention_type": "simple"})

    def build(kinds, **over):
        m = json.loads(json.dumps(model))
        m["dimensions"].update(layer_kinds=kinds, num_layers=len(kinds))
        m["misc"].update(over)
        return sambay.SambaYArgs.from_config(Config.from_dict({"name": "t", "model": m}).model, 512)

    assert build(["M", "S"]).memory_layer is None and build(["M", "F", "G"]).memory_layer == 0
    for kinds, match in ((["M", "C", "F"], "C layer"), (["G", "M", "F"], "G layer"),
                         (["S", "F", "G"], "G layer"), (["M", "F", "F"], "one F layer"),
                         (["M", "X"], "layer_kinds")):
        with pytest.raises(ValueError, match=match):
            build(kinds)
    with pytest.raises(ValueError, match="ties its head"):
        build(["M", "S"], tie_word_embeddings=False)


# -- through the trainer and the benchmark's kind -----------------------------------------------
def test_the_cell_rehearses_through_its_traffic_kind(tmp_path, monkeypatch):
    """``run.py --rehearse`` looks a kind up in rehearse.json, which is closed;
    this is the new cell's rehearsal: a Context at tiny widths, the kind's own
    ``run``: Trainer.train() on architecture sambay from a dict config, the
    window, the reference's three steps, the comparison."""
    ticks = itertools.count()   # the window counts steps, not this machine's seconds
    monkeypatch.setattr(kind.arch.base, "time", types.SimpleNamespace(
        perf_counter=lambda: 0.008 * next(ticks)))
    bench, cell, config, mix = harness.load_cell(CELL)
    assert mix["kind"] == "train_job_sambay" and cell["chips"] == 1
    config = harness.merge_into(config, TINY["config"])
    mix = harness.merge_into(mix, TINY["traffic"])
    cell = dict(cell, limits={k: 0.05 for k in cell["limits"]})
    ctx = harness.Context(cell, config, mix, seed=3_000_000_019, seconds=0.25, trace=False,
                          rehearse=True, workdir=str(tmp_path), quiet=True)
    res = kind.run(ctx)
    assert res["correct"], res["check_numbers"]
    assert len(res["sources"]["timed_steps"]) >= 5 and res["sources"]["sliding_window"] == 16
    assert len(res["check_numbers"]) == 3 + 3           # one term a step, three steps
    assert max(v for k, v in res["check_numbers"].items() if k.startswith("loss_gap")) < 1e-3
    run_dir, = (os.path.join(tmp_path, "runs", d) for d in os.listdir(os.path.join(tmp_path, "runs")))
    first = next(e for e in train_job._read_events(run_dir) if e.get("type") == "step_window")
    assert first["ssm_plan"]["mamba_layers"] == 2 and first["ssm_plan"]["gmu_layers"] == 1
    assert first["ssm_plan"]["scan_xla"] >= 2 and not first["ssm_plan"]["scan_fwd_kernel"]   # off the chip
    assert first["attn_plan"]["window_simple"] == first["attn_plan"]["window_layers"] >= 1
    assert first["attn_plan"]["cross_layers"] >= 1 and first["fused_ce_plan"]["grad_in_forward"] >= 1
    assert res["end_to_end"]["train_tokens_per_s_per_chip"] > 0 and res["end_to_end"]["setup_s"] > 0
    assert res["sources"]["flops_per_token"] == flops.train_flops_per_token(config, mix["seq_len"])


def test_trains_under_fsdp_as_on_one_device(tmp_path):
    """Trainer.train() on the architecture from a dict config, with and without
    an fsdp mesh: the same losses, so no new leaf breaks the sharded step."""
    from mlx_cuda_distributed_pretraining_tpu.train.trainer import Trainer

    cfg = harness.merge_into(FULL, TINY["config"])
    corpus = tmp_path / "train.jsonl"
    corpus.write_text("".join(json.dumps({"text": "the quick brown fox jumps over the lazy dog " * 6})
                              + "\n" for _ in range(120)))

    def run(name, mesh):
        d = {"name": name, "overwrite": True,
             "data": {"input_file": str(corpus), "validation_file": str(corpus),
                      "preprocessing": {"max_context_size": 64}, "tokenizer": {"normal_vocab_size": 256}},
             "model": kind.arch.MODEL_SECTIONS["sambay"](cfg, {"attention_type": "simple"}),
             "training": {"hyperparameters": {"batch_size": 4, "learning_rate": 1e-2, "iters": 4},
                          "scheduler": {"type": "constant"}, "optimization": {"optimizer": "adafactor"}},
             "logging": {"steps": {"logging_interval": 1, "checkpoint_interval": 0,
                                   "validation_interval": 0}},
             "system": {"seed": 0, "remat": "full", "mesh": mesh}}
        tr = Trainer(Config.from_dict(d), runs_root=str(tmp_path / "runs"), quiet=True)
        tr.train()
        with open(os.path.join(tr.run_dir, "events.jsonl")) as f:
            events = [json.loads(l) for l in f]
        return [e["loss"] for e in events if e.get("type") == "step_window"]

    from mlx_cuda_distributed_pretraining_tpu.parallel.context import set_mesh

    try:
        one, sharded = run("one", {}), run("fsdp", {"fsdp": 2})
    finally:
        set_mesh(None)   # the Trainer's mesh outlives it: the worker's next file must not find it
    assert len(one) == len(sharded) == 4
    np.testing.assert_allclose(sharded, one, rtol=2e-4)
    assert one[-1] < one[0]


def test_the_sample_config_trains_through_the_cli(tmp_path):
    """``train.py --config configs/model-config-sambay-sample.yaml`` on the CPU."""
    (tmp_path / "train.jsonl").write_text("".join(
        json.dumps({"text": "the quick brown fox jumps over the lazy dog " * 8}) + "\n"
        for _ in range(200)))
    shutil.copy(tmp_path / "train.jsonl", tmp_path / "val.jsonl")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "train.py"), "--config",
         os.path.join(REPO, "configs/model-config-sambay-sample.yaml"), "--runs-root",
         str(tmp_path / "runs"), "--iters", "6", "--batch-size", "2",
         "--set", "logging.steps.logging_interval=2"],
        cwd=tmp_path, capture_output=True, text=True, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    log = out.stdout + out.stderr
    assert re.search(r"Step 6: loss=", log), log[-1500:]
    assert "state-space layers (traced; scans by path and chunk): " in log
    assert re.search(r"mamba_layers=\d+, gmu_layers=\d+, .*scan_xla=\d+", log)
