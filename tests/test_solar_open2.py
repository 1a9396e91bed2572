"""Architecture ``solar_open2`` (models/solar_open2.py) against the benchmark's
plain reference (benchmark/reference/solar_open2.py, which imports nothing of
the program), at tiny widths on seeded random weights, and what this
architecture forced: the chunk's triangular solve held to float64 where the
write strength reaches 2 (ops/kda.py), the share of the experts one rank of
forty holds, the third mixer kind's scopes, the tally, the two new readers, a
token-side tile for rows 4,096 wide, and the benchmark's traffic kind for it.
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
from benchmark.flops import kda_chunk
from benchmark.flops import solar_open2 as flops
from benchmark.reference import solar_open2 as ref
from benchmark.traffic_kinds import train_job
from benchmark.traffic_kinds import train_job_solar2 as kind
from mlx_cuda_distributed_pretraining_tpu.config import Config
from mlx_cuda_distributed_pretraining_tpu.models import kimi_linear as kl
from mlx_cuda_distributed_pretraining_tpu.models import solar_open2 as so
from mlx_cuda_distributed_pretraining_tpu.models.registry import resolve_architecture
from mlx_cuda_distributed_pretraining_tpu.ops import kda as kda_ops
from mlx_cuda_distributed_pretraining_tpu.ops import token_sum as ts
from test_afmoe import _read_metric, _trace_dir, _xplane

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL, CELL6 = "solar-open2-250b-ep40.train-seq8k", "kimi-linear-48b-a3b-ep16.train-seq8k"
B, S = 2, 64
NEW_READERS = ("step_device_ms.kda_proj", "kda_neg_eig_cores_per_step")
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
AS_CUT, PUBLISHED = 1_295_087_424, 250_287_810_304


def _load(path):
    with open(os.path.join(REPO, path)) as f:
        return json.load(f)


FULL = _load("benchmark/configs/solar-open2-250b-ep40.json")
TINY = _load("benchmark/rehearse_solar2.json")


def _catalog():
    """The catalog's row for Solar-Open2-250B (model-configs guide), its `config` whole."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(path) as f:
        return next(json.loads(line) for line in f if '"Solar-Open2-250B"' in line)


def _model(cfg, attention_type="simple"):
    return kind.arch.MODEL_SECTIONS["solar_open2"](cfg, {"attention_type": attention_type})


def _args(cfg, attention_type="simple"):
    return so.SolarOpen2Args.from_config(
        Config.from_dict({"name": "t", "model": _model(cfg, attention_type)}).model, cfg["vocab_size"])


@pytest.fixture(scope="module")
def tiny():
    """(configuration at tiny widths, seeded weights, a batch)."""
    cfg = harness.merge_into(FULL, TINY["config"])
    params = ref.init_params(7, cfg)
    toks = np.random.default_rng(0).integers(3, cfg["vocab_size"], size=(B, S + 1)).astype(np.int32)
    batch = {"inputs": jnp.asarray(toks[:, :-1]), "targets": jnp.asarray(toks[:, 1:]),
             "mask": jnp.ones((B, S), jnp.float32)}
    return cfg, params, batch


def _wide(cfg):
    """The tiny configuration with one delta-rule head the width of a register: what the kernels take."""
    return dict(cfg, linear_attn_config=dict(cfg["linear_attn_config"], head_dim=128, num_heads=1))


def _leaf_gaps(got, want):
    return {jax.tree_util.keystr(path): float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-12))
            for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                                    jax.tree_util.tree_leaves(want))}


# -- the model against the reference ------------------------------------------------
@pytest.mark.parametrize("attention_type,backend", [("simple", "xla"), ("flash", "xla"), ("simple", "kernel")])
def test_program_matches_reference_loss_and_every_gradient(tiny, monkeypatch, no_mesh_left_behind,
                                                          attention_type, backend):
    """The third case runs the mixer's four kernels (interpret mode) at a head the
    width of a register. Every core's write strength is doubled, and says so."""
    cfg, params, batch = tiny
    monkeypatch.setenv("KDA_BACKEND", backend)
    if backend == "kernel":
        cfg = _wide(cfg)
        params = ref.init_params(7, cfg)
    (want,), want_grads = jax.jit(lambda p: ref.loss_and_grads(p, batch["inputs"], batch["targets"], cfg))(params)
    args = _args(cfg, attention_type)
    before = so.plan_counts()
    (loss, count), grads = jax.jit(jax.value_and_grad(
        lambda p: so.loss_fn(p, batch, args, remat="full"), has_aux=True))(params)
    traced = {k: n - before.get(k, 0) for k, n in so.plan_counts().items()}
    assert traced[backend] >= 3 and traced["conv_" + backend] == 3 * traced[backend]
    assert traced["xla" if backend == "kernel" else "kernel"] == 0
    assert traced["neg_eig_cores"] == traced[backend] == traced["solve_halving"]
    assert traced["gqa_layers"] >= 1 and traced["kda_layers"] >= 3 and "latent_layers" not in traced
    assert float(count) == B * S
    assert float(loss) == pytest.approx(float(want), rel=2e-5)
    gaps = _leaf_gaps(grads, want_grads)
    assert max(gaps.values()) < 2e-4, sorted(gaps.items(), key=lambda kv: -kv[1])[:5]
    # the selection bias is a buffer: no gradient on either side
    assert all(float(jnp.abs(l["feed_forward"]["router"]["bias"]).max()) == 0.0 for l in grads["layers"])


def test_the_write_strength_is_what_the_setting_says(tiny):
    """Without ``kda_allow_neg_eigval`` the same weights are another model, on both sides alike."""
    cfg, params, batch = tiny
    plain = dict(cfg, kda_allow_neg_eigval=False)
    assert (_args(cfg).kda_beta_scale, _args(plain).kda_beta_scale) == (2.0, 1.0)
    logits = lambda c: jax.jit(lambda p, t: so.forward(p, t, _args(c))[0])(params, batch["inputs"])
    want = jax.jit(lambda p, t: ref.logits_at(p, t, plain))(params, batch["inputs"])
    assert float(jnp.abs(logits(plain) - want).max()) < 2e-5
    assert float(jnp.abs(logits(cfg) - want).max()) > 1e-4
    before = so.plan_counts()["neg_eig_cores"]
    jax.eval_shape(lambda p: so.forward(p, batch["inputs"], _args(plain))[0], params)
    assert so.plan_counts()["neg_eig_cores"] == before      # a run that lost the setting counts none


def test_the_trees_are_one_tree_and_the_counts_are_the_issues(tiny):
    cfg, params, _ = tiny
    own = jax.eval_shape(lambda: so.init_params(jax.random.PRNGKey(0), _args(cfg)))
    assert jax.tree_util.tree_structure(own) == jax.tree_util.tree_structure(params)
    assert [a.shape for a in jax.tree_util.tree_leaves(own)] == \
        [a.shape for a in jax.tree_util.tree_leaves(params)]
    assert sum(a.size for a in jax.tree_util.tree_leaves(params)) == flops.total_params(cfg)
    # at the cell's widths, without allocating: the tree the program trains, and the published whole
    shapes = jax.tree_util.tree_leaves(ref.param_shapes(FULL), is_leaf=ref._is_spec)
    assert sum(int(np.prod(s)) for s, _ in shapes) == flops.total_params(FULL) == AS_CUT
    assert flops.total_params(FULL, published=True) == PUBLISHED == FULL["published"]["total_params"]
    z = flops._sizes(FULL)
    assert (flops.kda_mixer_params(z), flops.gqa_mixer_params(z), flops.expert_params(z)) == \
        (137_732_288, 109_051_904, 15_728_640)
    assert z["kinds"] == list("GKKK") and flops._sizes(FULL, published=True)["kinds"] == list("GKKK") * 12
    assert so.matmul_params_per_token(_args(FULL)) == flops.matmul_params(FULL)
    assert so.flops_per_token(_args(FULL), 8192) == pytest.approx(
        flops.train_flops_per_token(FULL, 8192), rel=1e-3)   # S against S + 1 keys a query
    # by the weights a token meets the three delta-rule mixers are most of the cell
    assert 3 * flops.kda_matmul_params(z) / flops.matmul_params(FULL) == pytest.approx(0.587, abs=0.002)


def test_forward_gives_the_references_logits_and_has_no_cache(tiny):
    cfg, params, batch = tiny
    logits, cache = jax.jit(lambda p, t: so.forward(p, t, _args(cfg)))(params, batch["inputs"])
    want = jax.jit(lambda p, t: ref.logits_at(p, t, cfg))(params, batch["inputs"])
    assert cache is None and float(jnp.abs(logits - want).max()) < 2e-5
    with pytest.raises(NotImplementedError, match="recurrent-state cache"):
        so.forward(params, batch["inputs"], _args(cfg), cache=object())


def test_the_reference_summed_over_head_groups_is_the_reference(tiny, monkeypatch):
    """At the published 64 heads the reference sums a KDA mixer over groups of
    ``CORE_HEADS`` heads (memory alone); at these widths one group holds all four, so
    two groups of two are forced here: the same loss and the same gradients."""
    cfg, params, batch = tiny
    one = jax.jit(lambda p: ref.loss_and_grads(p, batch["inputs"], batch["targets"], cfg))(params)
    monkeypatch.setattr(ref, "CORE_HEADS", 2)
    two = jax.jit(lambda p: ref.loss_and_grads(p, batch["inputs"], batch["targets"], cfg))(params)
    assert float(one[0][0]) == pytest.approx(float(two[0][0]), rel=1e-6)
    assert max(_leaf_gaps(two[1], one[1]).values()) < 5e-6


def test_a_changed_token_moves_no_output_before_it(tiny):
    """The convolutions, the delta rule's state and the gated softmax layer are
    causal: another token at ``t`` leaves every logit before ``t`` as it was."""
    cfg, params, batch = tiny
    t = 37
    args = _args(cfg)
    logits = jax.jit(lambda tokens: so.forward(params, tokens, args)[0])
    base = logits(batch["inputs"])
    moved = logits(batch["inputs"].at[:, t].set((batch["inputs"][:, t] + 1) % cfg["vocab_size"]))
    assert float(jnp.abs(moved[:, :t] - base[:, :t]).max()) == 0.0
    assert float(jnp.abs(moved[:, t:] - base[:, t:]).max()) > 1e-4


def test_the_forty_shares_add_up():
    """80 experts over 40 ranks of 2: the forty held shares of the program's routed
    layer, the shared expert (which every rank computes alike) counted once, sum
    to what the uncut reference gives for the whole layer."""
    cfg = harness.merge_into(FULL, TINY["config"])
    cfg = dict(cfg, n_routed_experts=80, num_experts_per_tok=8, experts_held={"first": 0, "count": 80})
    whole = ref.param_shapes(cfg)["layers"][1]["feed_forward"]
    leaves, treedef = jax.tree_util.tree_flatten(whole, is_leaf=ref._is_spec)
    keys = jax.random.split(jax.random.PRNGKey(3), len(leaves))
    p = jax.tree_util.tree_unflatten(treedef, [jax.random.normal(k, s, jnp.float32) * 0.2
                                               for k, (s, _) in zip(keys, leaves)])
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 32, cfg["hidden_size"]), jnp.float32)
    want = ref.routed_layer(p, x, cfg, "float32")
    shared = ref.routed_layer(p, x, cfg, "float32", first=0, count=0)
    total = shared
    for rank in range(40):
        held = dict(cfg, experts_held={"first": 2 * rank, "count": 2})
        share = dict(p, experts=jax.tree_util.tree_map(lambda a: a[2 * rank:2 * rank + 2], p["experts"]))
        y, stats = so.routed_ffn(share, x, _args(held))
        total = total + (y - shared)
        assert float(stats["moe_load"].sum()) == x.shape[0] * x.shape[1] * 8   # the router's whole width
    assert float(jnp.abs(total - want).max()) < 1e-4 * float(jnp.abs(want).max())
    assert float(jnp.abs(y - want).max()) > 0.1 * float(jnp.abs(want).max())   # a share alone is not the layer


# -- the solve's conditioning (ISSUE 56, step 0) ---------------------------------------------
def _shared_keys(rng, shape, shared):
    """Unit keys that are ``shared`` of one common direction and the rest noise, as
    neighbouring tokens of a trained model are; independent where ``shared`` is 0."""
    d = shape[-1]
    common = rng.standard_normal(d)
    noise = rng.standard_normal(shape)
    k = shared * common / np.linalg.norm(common) + (1 - shared) * noise / np.linalg.norm(noise, axis=-1, keepdims=True)
    return k / np.linalg.norm(k, axis=-1, keepdims=True)


def _recurrence64(q, k, v, g, beta):
    """The recurrence a step at a time in float64, on ``[S, d]`` of one head."""
    St, out = np.zeros((q.shape[1], v.shape[1])), np.zeros(v.shape)
    for t in range(q.shape[0]):
        St = St * np.exp(g[t])[:, None]
        St = St + beta[t] * np.outer(k[t], v[t] - k[t] @ St)
        out[t] = q[t] @ St
    return out


# (keys' shared part, beta's range): the float32 error of `_solve` against a float64 inverse (largest
# entry's error over the largest entry) and of `kda` against the float64 recurrence (relative), as the
# parent's product form (I - D)(I + D^2)(I + D^4)(I + D^8) over 16-step blocks read them, then this tree's
# halving (PERF.md section 6, PR 56; four and two seeds), then the limit: at least 100 times under the
# parent's reading where the parent lost two digits (0.2 and 5e-2 at a write strength near 2), and no
# worse than the parent anywhere it was good (with twice the room, for another machine's rounding).
SOLVE_READINGS = {
    (0.9, (0.8, 1.0)): ((2.1e-4, 2.8e-4), (2.6e-7, 4.0e-7), 2e-6),
    (0.9, (1.8, 2.0)): ((1.5e-1, 2.3e-1), (2.9e-6, 3.8e-6), 2e-5),
    (0.0, (0.8, 1.0)): ((1.1e-7, 1.7e-7), (6.6e-8, 8.0e-8), 2e-7),
    (0.0, (1.8, 2.0)): ((5.1e-7, 8.4e-7), (1.7e-7, 3.0e-7), 8e-7),
}
CORE_READINGS = {
    (0.0, (0.0, 1.0)): ((3.0e-7, 3.0e-7), (3.0e-7, 3.0e-7), 6e-7),
    (0.9, (0.9, 1.0)): ((5.9e-5, 7.9e-5), (1.5e-6, 1.6e-6), 5e-6),
    (0.9, (1.8, 2.0)): ((3.8e-2, 6.0e-2), (6.1e-6, 6.1e-6), 5e-5),
}


def _pinned(parent, limit) -> bool:
    return limit <= 2 * parent[1] and (parent[0] < 1e-2 or limit <= parent[0] / 100)


@pytest.mark.parametrize("shared,betas", sorted(SOLVE_READINGS))
def test_the_solve_holds_float64_where_the_write_strength_reaches_two(shared, betas):
    parent, _, limit = SOLVE_READINGS[shared, betas]
    assert _pinned(parent, limit)
    for seed in range(4):
        rng = np.random.default_rng(seed)
        k = _shared_keys(rng, (128, 128), shared)
        A = np.tril(rng.uniform(*betas, 128)[:, None] * (k @ k.T), -1)
        want = np.linalg.inv(np.eye(128) + A)
        got = np.asarray(kda_ops._solve(jnp.asarray(A, jnp.float32)), np.float64)
        assert np.abs(got - want).max() / np.abs(want).max() < limit, seed
    assert kda_ops.SOLVE_FORM == "halving"


@pytest.mark.parametrize("backend", ["xla", "kernel"])
@pytest.mark.parametrize("shared,betas", sorted(CORE_READINGS))
def test_the_core_holds_the_float64_recurrence_where_the_write_strength_reaches_two(shared, betas, backend):
    """Float32 operands, 256 steps (two chunks of the kernels' 128), two heads of
    128 each with its own shared direction."""
    parent, _, limit = CORE_READINGS[shared, betas]
    assert _pinned(parent, limit)
    rng = np.random.default_rng(0)
    Sq, H, d = 256, 2, 128
    k = np.stack([_shared_keys(rng, (Sq, d), shared) for _ in range(H)], axis=1)
    q = _shared_keys(rng, (Sq, H, d), 0.0) * d ** -0.5
    v = rng.standard_normal((Sq, H, d))
    g = -np.log1p(np.exp(rng.standard_normal((Sq, H, d)))) * 0.02
    beta = rng.uniform(*betas, (Sq, H))
    f32 = lambda a: jnp.asarray(a, jnp.float32)[None]
    got = np.asarray(kda_ops.kda(f32(q), f32(k), f32(v), f32(g), f32(beta), backend=backend)[0], np.float64)
    r = lambda a: np.asarray(jnp.asarray(a, jnp.float32), np.float64)     # the operands as the core got them
    want = np.stack([_recurrence64(r(q)[:, h], r(k)[:, h], r(v)[:, h], r(g)[:, h], r(beta)[:, h])
                     for h in range(H)], axis=1)
    assert np.linalg.norm(got - want) / np.linalg.norm(want) < limit


def test_the_core_differentiates_at_a_doubled_write_strength():
    """Value and all five gradients against the float32 recurrence at beta in (1, 2), kernels and XLA form."""
    from test_kimi_linear import _core_case, _sequential

    q, k, v, g, beta, w = _core_case(1, 128, 2, 128, 0.2, seed=5)
    beta = 1.0 + beta
    rel = lambda a, b: float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))
    wants = jax.grad(lambda *a: jnp.sum(_sequential(*a) * w), argnums=(0, 1, 2, 3, 4))(q, k, v, g, beta)
    for backend in ("kernel", "xla"):
        core = lambda *a: kda_ops.kda(*a, backend=backend)
        assert rel(core(q, k, v, g, beta), _sequential(q, k, v, g, beta)) < 5e-6
        grads = jax.grad(lambda *a: jnp.sum(core(*a) * w), argnums=(0, 1, 2, 3, 4))(q, k, v, g, beta)
        gaps = {n: rel(a, b) for n, a, b in zip(("q", "k", "v", "g", "beta"), grads, wants)}
        assert max(gaps.values()) < 2e-5, (backend, gaps)


# -- shapes no cell had ------------------------------------------------------------------------
def test_the_token_side_tile_fits_rows_of_the_cells_width():
    """A held share's combine over rows 4,096 wide: two staging slots of 32 pieces
    leave a tile of 128 tokens no room, and a tile of 64 (what the plan then gave)
    is no whole lane register, which Mosaic refuses; the plan keeps the tile and
    halves the round. The other cells' widths keep 32 (their programs do not move)."""
    bf16 = jnp.dtype(jnp.bfloat16).itemsize
    assert ts.token_sum_plan(16384, 8, 4096, 5120, jnp.bfloat16, "pallas") == 128
    assert ts._round_pieces(128, 4096, bf16) == 16
    assert [ts._round_pieces(128, D, bf16) for D in (2048, 2304, 3584)] == [32, 32, 32]
    assert ts.token_sum_plan(2048, 4, 3584, 5120, jnp.float32, "pallas") == 0       # cell 2's float32 rows: as before
    assert ts.token_sum_plan(16384, 8, 4096, 5120, jnp.float32, "pallas") == 0
    assert ts.token_sum_plan(48, 2, 512, 64, jnp.float32, "pallas") == 48            # few tokens: one tile


def test_the_token_side_sums_at_a_round_of_sixteen_pieces_match_the_gather(monkeypatch):
    """Two tiles of 128 tokens whose 512 held rows each are two rounds of 16 pieces
    (rows 4,096 wide, bfloat16; the kernels interpreted): the combine, its two
    gradients and the dispatch's backward against XLA's gather of every selection."""
    from mlx_cuda_distributed_pretraining_tpu.models import moe

    monkeypatch.setenv("GMM_BACKEND", "pallas")
    rng = np.random.default_rng(5)
    T, K, E, D = 256, 4, 8, 4096
    idx = np.stack([rng.permutation(E)[:K] for _ in range(T)]).astype(np.int32)
    plan = moe.dispatch_plan(jnp.asarray(idx), E, 16, 0, None)
    y_buf = jnp.asarray(rng.normal(size=(plan.row_sel.shape[0], D)), jnp.bfloat16)
    x = jnp.asarray(rng.normal(size=(T, D)), jnp.bfloat16)
    gate_w = jnp.asarray(rng.uniform(0.1, 1.0, size=(T, K)), jnp.float32)
    probe = jnp.asarray(rng.normal(size=y_buf.shape), y_buf.dtype)
    assert ts.token_sum_plan(T, K, D, y_buf.shape[0], y_buf.dtype) == 128
    assert ts._round_pieces(128, D, 2) == 16 < 128 * K // ts._PIECE
    sin_sum = lambda out: jnp.sum(jnp.sin(out.astype(jnp.float32)))
    gathered = lambda y, w: moe._sum_held(moe._take_rows(y, plan.sel_row), jnp.where(plan.sel_held, w, 0), y.dtype)
    seen = moe.plan_counts()
    got = [moe.combine_rows(y_buf, gate_w, plan),
           *jax.grad(lambda y, w: sin_sum(moe.combine_rows(y, w, plan)), (0, 1))(y_buf, gate_w),
           jax.grad(lambda x: sin_sum(moe.dispatch_rows(x, plan) * probe))(x)]
    traced = {k: n - seen[k] for k, n in moe.plan_counts().items()}
    assert traced["token_sum_kernel"] == 4 and traced["token_sum_xla"] == 0
    want = [gathered(y_buf, gate_w),
            *jax.grad(lambda y, w: sin_sum(gathered(y, w)), (0, 1))(y_buf, gate_w),
            jax.grad(lambda x: sin_sum(moe._dispatch_rows(x, plan) * probe))(x)]
    for name, a, b in zip(("out", "dy_buf", "dgate_w", "dx"), got, want):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        # dgate_w is a float32 sum of 4,096 exact products in another order: 2e-4 of the largest read
        tol = 1e-3 if name == "dgate_w" else 2.0 ** -7
        np.testing.assert_allclose(a, b, atol=tol * np.abs(b).max(), rtol=tol, err_msg=name)


# -- configuration, declarations ---------------------------------------------------------------
def test_the_configuration_file_says_what_the_issue_says():
    catalog = _catalog()
    assert FULL["source"] == catalog["source_url"] and FULL["architecture"] == "solar_open2"
    reduced = set(FULL["reduced"])
    assert reduced == {"num_hidden_layers", "n_routed_experts", "vocab_size", "gqa_layers"}
    for key, value in catalog["config"].items():
        if key not in reduced or key == "n_routed_experts":     # the router keeps its published width
            assert FULL[key] == value, key
    assert FULL["linear_attn_config"] == {"short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 64,
                                          "num_kv_heads": None}
    assert (FULL["num_hidden_layers"], FULL["gqa_layers"], FULL["first_k_dense_replace"]) == (4, [0], 0)
    assert FULL["experts_held"] == {"first": 0, "count": 8} and FULL["vocab_size"] == 196608 // 8 == 192 * 128
    assert FULL["published"] == {
        "num_hidden_layers": 48, "n_routed_experts": 320, "vocab_size": 196608,
        "gqa_layers": list(range(0, 48, 4)), "total_params": PUBLISHED}
    assert (FULL["hidden_size"], FULL["moe_intermediate_size"], FULL["num_attention_heads"],
            FULL["num_key_value_heads"], FULL["head_dim"]) == (4096, 1280, 64, 8, 128)
    assert (FULL["num_experts_per_tok"], FULL["routed_scaling_factor"], FULL["n_shared_experts"]) == (8, 1, 1)
    assert (FULL["use_rope"], FULL["use_gqa_gate"], FULL["kda_allow_neg_eigval"], FULL["kda_use_full_proj"]) == \
        (False, True, True, False)
    assert FULL["precision"]["control"] == "fp8" and "40 chips" in FULL["deployment"]
    for said in ("solve", "state", "decay"):
        assert said in FULL["precision"]["train"]
    assert {"gqa_gate", "gqa_plain", "router", "kda", "neg_eigval", "dense_ffn", "packing", "weights",
            "source_checked"} <= set(FULL["assumed"])
    mix, base = _load("benchmark/traffic/pack8k-solar2.json"), _load("benchmark/traffic/pack8k-b2-kda.json")
    assert {k: v for k, v in mix.items() if k != "kind"} == {k: v for k, v in base.items() if k != "kind"}
    assert (mix["kind"], mix["seq_len"], mix["batch_size"]) == ("train_job_solar2", 8192, 2)
    bench = _load("BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == FULL["name"])
    assert set(entry["reduced"]) == reduced and entry["source"] == FULL["source"]
    assert entry["file"] == "benchmark/configs/solar-open2-250b-ep40.json" and bench["configs"][-1] == entry
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "pack8k-solar2" and bench["workloads"][6] == cell
    assert all(len(w["why"]) <= 200 for w in bench["workloads"] + bench["configs"])
    record = _load(f"benchmark/workloads/{CELL}.json")
    assert set(record["limits_why"]) >= set(record["limits"]) and "size" in record and "who" in record


def test_the_cell_is_declared_for_the_metrics_it_reports_and_no_other():
    bench = _load("BENCHMARK.json")
    of = lambda cell: {m["name"] for m in bench["per_layer"] if cell in m.get("workloads", ())}
    listed = of(CELL)
    for name in listed:
        assert os.path.isfile(os.path.join(REPO, "benchmark/layer_metrics", name + ".py"))
    # what cell 6 reports but its two latent-attention shares, and: the gate, the 64/8 causal pair, the tally
    assert listed == (of(CELL6) - {"kernel_peak_pct.mla_flash_fwd", "kernel_peak_pct.mla_flash_bwd"}) | {
        "step_device_ms.attn_gate", "kernel_peak_pct.flash_fwd", "kernel_peak_pct.flash_bwd",
        "kda_neg_eig_cores_per_step"}
    mine = bench["per_layer"][-len(NEW_READERS):]
    assert [m["name"] for m in mine] == list(NEW_READERS)
    assert [m["workloads"] for m in mine] == [[CELL6, CELL], [CELL]]
    assert [(m["unit"], m["better"], m["source"], m["layer"], m["moves"]) for m in mine] == [
        ("ms", "lower", "device_trace", "train step", "train_tokens_per_s_per_chip"),
        ("count", "higher", "program_counter", "train step", "train_tokens_per_s_per_chip")]
    assert all(m["workloads"][-1] == CELL for m in bench["per_layer"] if CELL in m.get("workloads", ()))
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert e2e["train_tokens_per_s_per_chip"]["workloads"][-1] == CELL and "workloads" not in e2e["setup_s"]
    assert (e2e["train_tokens_per_s_per_chip"]["bound"], e2e["setup_s"]["bound"], bench["run_seconds"]) == \
        (0.01, 0.1, 40)


# -- the trace readers --------------------------------------------------------------------
@pytest.mark.parametrize("stored", ["train_1chip_v5e", "train_1chip_v5e_scoped"])
def test_new_readers_find_nothing_in_a_run_without_what_they_read(tmp_path, stored):
    """Run on the parent, or in a cell of another architecture, each new reader
    returns None and raises nothing."""
    with gzip.open(os.path.join(REPO, "benchmark/tests/data", stored + ".xplane.pb.gz")) as src:
        work = _trace_dir(tmp_path, stored, src.read())
    sources = {"trace_dir": work, "peaks": PEAKS, "kda_heads": 64, "kda_head_dim": 128,
               "step_window_events": [{"type": "step_window", "steps": 1}],
               "kda_plan": {"kernel": 6, "xla": 0, "conv_kernel": 18, "conv_xla": 0}}     # the parent's tally
    assert {n: _read_metric(n, sources) for n in NEW_READERS} == dict.fromkeys(NEW_READERS)
    assert {n: _read_metric(n, {}) for n in NEW_READERS} == dict.fromkeys(NEW_READERS)


def test_the_readers_read_a_trace_of_the_cells_shapes(tmp_path):
    """One step of 3,000 us at ``[2, 8192, 64 x 128]``: a KDA layer's projections
    and prologue, its core forward, recomputed and backward, the gated layer's
    projections with the gate inside them, and its 64/8 causal core."""
    pre = "jit(train_step)/jvp(jit(loss))/checkpoint/layer/"
    again = "jit(train_step)/transpose(jvp(jit(loss)))/checkpoint/rematted_computation/layer/"
    bwd = "jit(train_step)/transpose(jvp(jit(loss)))/checkpoint/layer/"
    o, st = "bf16[2,8192,8192]{2,1,0}", "f32[2,64,64,128,128]{4,3,2,1,0}"
    dg, db = "f32[2,8192,8192]{2,1,0}", "f32[2,64,64,128,1]{4,3,2,1,0}"
    ops = [
        (pre + "kda/kda_proj/dot_general", "%fusion.1 = bf16[16384,8192]{1,0} fusion()", 0, 300),
        (pre + "kda/kda_proj/short_conv_fwd/pallas_call:", f"%short_conv_fwd.1 = {o} custom-call()", 300, 50),
        (bwd + "kda/kda_proj/short_conv_bwd/pallas_call:", f"%short_conv_bwd.1 = ({o}, f32[8192,4]{{1,0}}) custom-call()", 350, 50),
        (pre + "kda/kda_core/kda_fwd/pallas_call:", f"%kda_fwd.1 = {o} custom-call()", 400, 200),
        (again + "kda/kda_core/kda_fwd/pallas_call:", f"%kda_fwd.2 = ({o}, {st}) custom-call()", 600, 200),
        (bwd + "kda/kda_core/kda_bwd/pallas_call:",
         f"%kda_bwd.1 = ({o}, {o}, {o}, {dg}, {db}, {db}) custom-call()", 800, 500),
        (pre + "kda/kda_out/dot_general", "%fusion.2 = bf16[16384,4096]{1,0} fusion()", 1300, 100),
        (pre + "attn_qkv/dot_general", "%fusion.3 = bf16[16384,8192]{1,0} fusion()", 1400, 200),
        (pre + "attn_qkv/attn_gate/dot_general", "%fusion.4 = bf16[16384,8192]{1,0} fusion()", 1600, 150),
        (pre + "attn_out/attn_gate/mul", "%fusion.5 = bf16[16384,8192]{1,0} fusion()", 1750, 50),
        (pre + "attn_core/flash_fwd/pallas_call:",
         "%flash_fwd.1 = (bf16[2,64,8192,128]{3,2,1,0}, f32[2,64,1,8192]{3,2,1,0}) custom-call()", 1800, 700),
        (pre + "ffn/dot_general", "%fusion.6 = bf16[16384,1280]{1,0} fusion()", 2500, 500),
    ]
    plan = {"gqa_layers": 1, "kda_layers": 3, "kernel": 3, "xla": 0, "kernel_chunk128": 3, "levels7": 3,
            "pair_passes0": 3, "conv_kernel": 9, "conv_xla": 0, "neg_eig_cores": 3, "solve_halving": 3}
    sources = {"trace_dir": _trace_dir(tmp_path, "t", _xplane(ops, [(0, 3000)])), "peaks": PEAKS,
               "kda_heads": 64, "kda_head_dim": 128, "step_window_events": [{"type": "step_window", "steps": 1}],
               "kda_plan": plan}
    assert _read_metric("step_device_ms.kda_proj", sources) == pytest.approx(0.400)
    assert _read_metric("step_device_ms.kda_core", sources) == pytest.approx(0.900)
    assert _read_metric("step_device_ms.kda", sources) == pytest.approx(1.400)
    assert _read_metric("step_device_ms.attn_gate", sources) == pytest.approx(0.200)
    assert _read_metric("step_device_ms.attn_proj", sources) == pytest.approx(0.400)
    assert _read_metric("kda_neg_eig_cores_per_step", sources) == 3.0
    assert _read_metric("kda_neg_eig_cores_per_step", dict(sources, kda_plan=dict(plan, neg_eig_cores=0))) == 0.0
    assert _read_metric("kda_xla_calls_per_step", sources) == 0.0 == _read_metric("kda_conv_xla_calls_per_step", sources)
    # the new shapes' roofline shares: 64 heads from the kind's sources, the HBM roof binding both passes
    at = (2, 8192, 64, 128)
    roof = lambda f, b: kda_chunk.roof_seconds(f(*at), b(*at), PEAKS)
    assert kda_chunk.fwd_bytes(*at) / PEAKS["hbm_bytes_per_s"] > kda_chunk.fwd_flops(*at) / PEAKS["bf16_flops"]
    assert _read_metric("kernel_roof_pct.kda_fwd", sources) == pytest.approx(
        100 * 2 * roof(kda_chunk.fwd_flops, kda_chunk.fwd_bytes) / 400e-6)
    assert _read_metric("kernel_roof_pct.kda_bwd", sources) == pytest.approx(
        100 * roof(kda_chunk.bwd_flops, kda_chunk.bwd_bytes) / 500e-6)
    assert _read_metric("kernel_roof_pct.kda_fwd", dict(sources, kda_heads=32)) is None
    # the 64/8 causal core under the accepted pair's forward reader: 2 B H S^2 D over its time and the peak
    assert _read_metric("kernel_peak_pct.flash_fwd", sources) == pytest.approx(
        100 * 2.0 * 2 * 64 * 8192 * 8192 * 128 / 700e-6 / PEAKS["bf16_flops"])


# -- scopes, rules, imports -------------------------------------------------------------------
def test_the_train_step_carries_the_scopes_the_metrics_read(tiny, monkeypatch):
    """``kda`` encloses ``kda_proj``, ``kda_core`` (with the kernels' names) and
    ``kda_out``; the gated layer has ``attn_qkv``, ``attn_core``, ``attn_out`` with
    ``attn_gate`` inside the first and the last, under no kind's scope."""
    cfg, params, batch = tiny
    monkeypatch.setenv("KDA_BACKEND", "kernel")
    args = _args(_wide(cfg))
    shapes = jax.eval_shape(lambda: so.init_params(jax.random.PRNGKey(0), args))
    step = jax.jit(jax.grad(lambda p: so.loss_fn(p, batch, args, remat="full")[0]))
    names = set(re.findall(r'op_name="([^"]+)"', step.lower(shapes).compile().as_text()))
    stacks = [re.split(r"[/()]", n) for n in names]
    under = lambda scope: [s for s in stacks if scope in s]
    assert under("kda_proj") and under("kda_out") and under("attn_qkv") and under("attn_out") and under("attn_core")
    assert all("kda" in s for s in under("kda_proj") + under("kda_core") + under("kda_out"))
    assert any("kda_fwd" in s for s in under("kda_core")) and any("kda_bwd" in s for s in under("kda_core"))
    assert any("short_conv_fwd" in s for s in under("kda_proj"))
    assert under("attn_gate") and all("attn_qkv" in s or "attn_out" in s for s in under("attn_gate"))
    assert not under("attn_global") and not under("attn_window")
    assert under("moe_experts") and under("moe_router") and under("ffn") and under("lm_head_ce")


def test_sharding_rules_cover_the_new_leaves(tiny):
    from jax.sharding import Mesh, PartitionSpec as P

    from mlx_cuda_distributed_pretraining_tpu.parallel import sharding_rules
    from mlx_cuda_distributed_pretraining_tpu.utils.tree import flatten_dict

    cfg, params, _ = tiny
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("fsdp", "tp"))
    specs = flatten_dict(sharding_rules.tree_pspecs(params, mesh))
    for path in specs:
        assert any(re.search(pat, path) for pat, _ in sharding_rules._RULES), path
    pspec = lambda path, shape: sharding_rules.param_pspec(path, shape, mesh)
    assert pspec("layers.0.attention.wq.weight", (4096, 8192)) == P("fsdp", "tp") == \
        pspec("layers.0.attention.wg.weight", (4096, 8192))
    assert pspec("layers.0.attention.wk.weight", (4096, 1024)) == P("fsdp", "tp")
    assert pspec("layers.0.attention.wo.weight", (8192, 4096)) == P("tp", "fsdp")
    assert pspec("layers.1.kda.wq.weight", (4096, 8192)) == P("fsdp", "tp")
    assert pspec("layers.1.kda.wo.weight", (8192, 4096)) == P("tp", "fsdp")
    assert pspec("layers.1.kda.wb.weight", (4096, 64)) == P("fsdp", None)
    assert pspec("layers.1.kda.g_up.weight", (128, 8192)) == P(None, "tp")
    assert pspec("layers.1.feed_forward.router.weight", (4096, 320)) == P("fsdp", None)


def test_the_core_takes_the_xla_form_under_a_mesh_at_a_doubled_write_strength(tiny, monkeypatch):
    from jax.sharding import Mesh

    from mlx_cuda_distributed_pretraining_tpu.parallel.context import use_mesh

    cfg, params, batch = tiny
    args = _args(_wide(cfg))
    p = so.init_params(jax.random.PRNGKey(2), args)["layers"][1]["kda"]
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, cfg["hidden_size"]), jnp.float32)
    alone = kl.kda_mixer(p, x, args, 2.0)
    before = kda_ops.plan_counts()
    monkeypatch.setenv("KDA_BACKEND", "kernel")      # asked for, and not taken under a mesh
    with use_mesh(Mesh(np.array(jax.devices()[:2]), ("fsdp",))):
        meshed = kl.kda_mixer(p, x, args, 2.0)
    after = kda_ops.plan_counts()
    assert after["xla"] == before["xla"] + 1 and after["kernel"] == before["kernel"]
    assert float(jnp.abs(meshed - alone).max()) < 1e-5 * float(jnp.abs(alone).max()) + 1e-6


def test_cells_one_to_six_import_nothing_of_the_new_module():
    """A llama, xing, afmoe, sambay, sdar or kimi_linear run pays nothing for this architecture."""
    code = ("import sys; sys.path.insert(0, %r)\n"
            "from benchmark.traffic_kinds import (train_job, train_job_arch, train_job_afmoe,\n"
            "                                     train_job_sambay, train_job_sdar, train_job_kda)\n"
            "from mlx_cuda_distributed_pretraining_tpu.train.trainer import Trainer\n"
            "from mlx_cuda_distributed_pretraining_tpu.models.registry import resolve_architecture\n"
            "for name in ('llama', 'xing_mla_moe', 'afmoe', 'sambay', 'sdar_moe', 'kimi_linear'):\n"
            "    resolve_architecture(name)\n"
            "assert 'solar_open2' not in train_job_arch.MODEL_SECTIONS\n"
            "new = [m for m in sys.modules if m.endswith(('solar_open2', 'train_job_solar2'))]\n"
            "assert not new, new\n"
            "assert set(resolve_architecture('solar_open2').plans) == {'kda_plan'}\n"
            "assert any(m.endswith('models.solar_open2') for m in sys.modules)\n" % REPO)
    subprocess.run([sys.executable, "-c", code], check=True, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    with pytest.raises(ValueError, match="solar_open2"):
        resolve_architecture("no_such_model")


def test_from_config_reads_the_published_keys_and_refuses_what_the_model_cannot_run():
    args = _args(FULL, "flash")
    assert args.layer_kinds == ("G", "K", "K", "K") and args.gqa_layers == (0,)
    assert (args.kda_heads, args.kda_head_dim, args.conv_size, args.kda_beta_scale) == (64, 128, 4, 2.0)
    assert (args.num_heads, args.num_kv_heads, args.head_dim, args.hidden_size) == (64, 8, 128, 4096)
    assert (args.n_routed_experts, args.num_experts_per_tok, args.experts_held) == (320, 8, (0, 8))
    assert (args.moe_intermediate_size, args.n_shared_experts, args.routed_scaling_factor) == (1280, 1, 1.0)
    assert args.attention_type == "flash" and args.is_moe and args.held_chunk_rows == FULL["held_chunk_rows"]
    model = _model(harness.merge_into(FULL, TINY["config"]))

    def build(**over):
        m = json.loads(json.dumps(model))
        for section, values in over.items():
            m[section].update(values)
        return so.SolarOpen2Args.from_config(Config.from_dict({"name": "t", "model": m}).model, 512)

    assert build().layer_kinds == ("G", "K", "K", "K")
    assert build(attention={"gqa_layers": [1, 3]}).layer_kinds == ("K", "G", "K", "G")
    for over, match in (
            ({"attention": {"use_rope": True}}, "use_rope"),
            ({"attention": {"use_gqa_gate": False}}, "use_gqa_gate"),
            ({"linear_attn": {"kda_use_full_proj": True}}, "kda_use_full_proj"),
            ({"attention": {"gqa_layers": [0, 4]}}, "gqa_layers"),          # the published list's next entry
            ({"attention": {"gqa_layers": [0, 0]}}, "gqa_layers"),
            ({"attention": {"gqa_layers": []}}, "gqa_layers"),
            ({"dimensions": {"num_layers": 8}, "attention": {"gqa_layers": [0, 8]}}, "gqa_layers"),
            ({"moe": {"first_k_dense_replace": 1}}, "first_k_dense_replace"),
            ({"moe": {"norm_topk_prob": False}}, "norm_topk_prob"),
            ({"moe": {"experts_held": [6, 4]}}, "experts_held"),
            ({"linear_attn": {"num_kv_heads": 2}}, "num_kv_heads")):
        with pytest.raises(ValueError, match=match):
            build(**over)


# -- through the trainer and the benchmark's kind -----------------------------------------------
def test_the_cell_rehearses_through_its_traffic_kind(tmp_path, monkeypatch):
    """``run.py --rehearse`` looks a kind up in rehearse.json, which is closed;
    this is the new cell's rehearsal: a Context at tiny widths, the kind's own
    ``run``: Trainer.train() on architecture solar_open2 from a dict config, the
    window, the events' tallies, the reference's three steps, the comparison."""
    ticks = itertools.count()   # the window counts steps, not this machine's seconds
    monkeypatch.setattr(kind.arch.base, "time", types.SimpleNamespace(
        perf_counter=lambda: 0.008 * next(ticks)))
    bench, cell, config, mix = harness.load_cell(CELL)
    assert mix["kind"] == "train_job_solar2" and cell["chips"] == 1
    config = harness.merge_into(config, TINY["config"])
    mix = harness.merge_into(mix, TINY["traffic"])
    # every number held to 0.05 but the first gradient's profile: at these widths a held expert sees a
    # few dozen rows, and a token bfloat16 routes elsewhere turns its bank's gradient
    cell = dict(cell, limits={k: 0.3 if k == "first_grad_profile_gap" else 0.05 for k in cell["limits"]})
    ctx = harness.Context(cell, config, mix, seed=3_000_000_019, seconds=1.5, trace=False,
                          rehearse=True, workdir=str(tmp_path), quiet=True)
    res = kind.run(ctx)
    assert res["correct"], res["check_numbers"]
    # three losses, the harness's three, and the kind's two over the leaves no router feeds
    assert len(res["sources"]["timed_steps"]) >= 5 and len(res["check_numbers"]) == 3 + 3 + 2
    assert {"unrouted_grad_norm_gap", "unrouted_grad_profile_gap"} <= set(res["check_numbers"]) & set(cell["limits"])
    assert kind.arch.base.compare is not kind.compare                    # handed back after the run
    assert (res["sources"]["kda_heads"], res["sources"]["kda_head_dim"]) == (4, 32)
    assert max(v for k, v in res["check_numbers"].items() if k.startswith("loss_gap")) < 1e-3
    events = res["sources"]["step_window_events"]
    plan = res["sources"]["kda_plan"]
    assert plan["xla"] >= 3 and plan["kernel"] == 0 and plan["kda_layers"] >= 3 and plan["gqa_layers"] >= 1
    assert plan["neg_eig_cores"] == plan["xla"] == plan["solve_halving"] and plan["conv_xla"] == 3 * plan["xla"]
    assert events and all(e["moe_drop"] == 0 and e["moe_rows_held"] > 0 and "kda_plan" not in e for e in events)
    assert _read_metric("kda_neg_eig_cores_per_step", res["sources"]) == plan["neg_eig_cores"]
    assert _read_metric("kda_xla_calls_per_step", res["sources"]) >= 3       # no kernel off the chip
    run_dir, = (os.path.join(tmp_path, "runs", d) for d in os.listdir(os.path.join(tmp_path, "runs")))
    first = next(e for e in train_job._read_events(run_dir) if e.get("type") == "step_window")
    assert first["kda_plan"] == plan and first["fused_ce_plan"]["grad_in_forward"] >= 1
    assert res["end_to_end"]["train_tokens_per_s_per_chip"] > 0 and res["end_to_end"]["setup_s"] > 0
    held = np.mean([e["moe_rows_held"] for e in events]) / 256 / flops.routed_layers(config)
    assert res["sources"]["flops_per_token"] == pytest.approx(
        flops.train_flops_per_token(config, mix["seq_len"], held))
    # control_solar2.py --sound reads the same numbers on that seed without the window
    from benchmark import control_solar2
    lines = []
    assert control_solar2.main(["--workload", CELL, "--seeds", "", "--sound", str(ctx.seed),
                                "--rehearse", "rehearse_solar2.json"], say=lines.append) in (0, 1)
    sound, = (json.loads(l) for l in lines if l.startswith("{"))
    assert sound["sound"] == "program" and sound["seed"] == ctx.seed
    assert sound["numbers"] == pytest.approx(res["check_numbers"], rel=1e-6)
    assert harness.load_cell(CELL)[3]["kind"] == "train_job_solar2"      # the name it borrowed is handed back
    with pytest.raises(SystemExit, match="train_job_kda"):
        control_solar2.main(["--workload", CELL6, "--seeds", "1"], say=lines.append)


def test_the_control_runs_the_reference_in_the_place_of_the_program():
    """The control's comparison at tiny widths: the float32 reference against
    itself with every matmul operand through float8 (the diagnosis's arithmetic is
    ``reference/kimi_linear.py``'s, which ``tests/test_kimi_linear.py`` runs): every number moves."""
    from benchmark import control_arch

    bench, cell, config, mix = harness.load_cell(CELL)
    config = harness.merge_into(config, TINY["config"])
    mix = harness.merge_into(mix, dict(TINY["traffic"], checked_steps=1))
    verdict = control_arch.train_control(dict(cell, limits=dict.fromkeys(cell["limits"], 1e-9)), config, mix, 11,
                                         config["precision"]["control"], rehearse=True, say=lambda _: None)
    assert not verdict["ok"] and all(v > 0 for v in verdict["numbers"].values()), verdict["numbers"]
    assert ref.BF16_KDA in ref.PRECISIONS


def test_the_sample_config_trains_through_the_cli(tmp_path):
    """``train.py --config configs/model-config-solar-open2-sample.yaml`` on the
    CPU: a tokenised corpus, training and validation, the tally on the lines."""
    (tmp_path / "train.jsonl").write_text("".join(
        json.dumps({"text": "the quick brown fox jumps over the lazy dog " * 8}) + "\n"
        for _ in range(200)))
    shutil.copy(tmp_path / "train.jsonl", tmp_path / "val.jsonl")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "train.py"), "--config",
         os.path.join(REPO, "configs/model-config-solar-open2-sample.yaml"), "--runs-root",
         str(tmp_path / "runs"), "--iters", "6", "--batch-size", "2",
         "--set", "logging.steps.logging_interval=2", "--set", "logging.steps.validation_interval=3"],
        cwd=tmp_path, capture_output=True, text=True, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    log = out.stdout + out.stderr
    assert re.search(r"Step 6: loss=", log), log[-1500:]
    assert re.search(r"Step 3 validation: val_loss=", log), log[-1500:]
    assert re.search(r"delta-rule layers \(traced; cores by form and chunk\): gqa_layers=\d+, kda_layers=\d+, "
                     r"kernel=0, xla=\d+.*neg_eig_cores=[1-9]\d*, solve_halving=[1-9]", log), log[-1500:]
