"""MoE block + expert parallelism.

The reference only declares MoE config fields (models/llama.py:40-41);
our models/moe.py implements the real block. These tests check routing
math, gradient flow to every expert, and that the ep-sharded train step
on a virtual mesh matches the single-device loss.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import activation_scatters, tail_after_loop
from mlx_cuda_distributed_pretraining_tpu.models import llama, moe
from mlx_cuda_distributed_pretraining_tpu.optim import build_optimizer
from mlx_cuda_distributed_pretraining_tpu.config import SystemConfig, TrainingConfig
from mlx_cuda_distributed_pretraining_tpu.parallel import build_mesh
from mlx_cuda_distributed_pretraining_tpu.train.train_step import (
    init_train_state,
    make_train_step,
)

MOE_ARGS = llama.LlamaArgs(
    vocab_size=128, hidden_size=32, intermediate_size=64, num_layers=2,
    num_heads=2, num_kv_heads=2, head_dim=16, max_position_embeddings=64,
    num_local_experts=4, num_experts_per_tok=2,
)


def _batch(bs=4, seq=16, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.integers(1, 120, size=(bs, seq + 1)).astype(np.int32)
    return {
        "inputs": jnp.asarray(x[:, :-1]),
        "targets": jnp.asarray(x[:, 1:]),
        "mask": jnp.ones((bs, seq), jnp.float32),
    }


def test_dispatch_combine_shapes_and_conservation():
    # A perfectly balanced router keeps every token: combine sums to 1.
    B, S, E, K, C = 2, 8, 4, 2, 8
    probs = jnp.full((B, S, E), 1.0 / E)
    dispatch, combine = moe._dispatch_combine(probs, K, C)
    assert dispatch.shape == (B, S, E, C)
    # every token dispatched to exactly K slots
    np.testing.assert_allclose(np.asarray(dispatch.sum(axis=(2, 3))), K)
    # combine weights renormalized over the K picks
    np.testing.assert_allclose(np.asarray(combine.sum(axis=(2, 3))), 1.0, atol=1e-5)


def test_capacity_drops_overflow_tokens():
    # All tokens want expert 0 with capacity 2: only 2 survive per row.
    B, S, E, K, C = 1, 6, 4, 1, 2
    logits = jnp.zeros((B, S, E)).at[..., 0].set(10.0)
    probs = jax.nn.softmax(logits, axis=-1)
    dispatch, combine = moe._dispatch_combine(probs, K, C)
    assert float(dispatch[..., 0, :].sum()) == pytest.approx(2.0)
    # dropped tokens have zero combine weight (residual carries them)
    per_token = np.asarray(combine.sum(axis=(2, 3)))[0]
    assert (per_token[:2] > 0.9).all() and (per_token[2:] < 1e-6).all()


def test_balanced_router_aux_loss_is_one():
    # Uniform probs + uniform assignment -> Switch aux loss == 1.
    probs = jnp.full((2, 8, 4), 0.25)
    idx = jnp.tile(jnp.arange(4), 4).reshape(2, 8)
    assert float(moe.load_balancing_loss(probs, idx, 4)) == pytest.approx(1.0)


@pytest.mark.slow
def test_moe_forward_and_all_experts_get_gradients():
    params = llama.init_params(jax.random.PRNGKey(0), MOE_ARGS)
    batch = _batch()
    loss, grads = jax.value_and_grad(
        lambda p: llama.loss_fn(p, batch, MOE_ARGS)[0]
    )(params)
    assert np.isfinite(float(loss))
    g = grads["layers"][0]["feed_forward"]["experts"]["w_gate"]["weight"]
    per_expert = np.asarray(jnp.abs(g).sum(axis=(1, 2)))
    assert (per_expert > 0).all(), f"dead experts: {per_expert}"
    # router learns too
    rg = grads["layers"][0]["feed_forward"]["router"]["weight"]
    assert float(jnp.abs(rg).sum()) > 0


def test_moe_aux_loss_increases_total_loss():
    params = llama.init_params(jax.random.PRNGKey(0), MOE_ARGS)
    batch = _batch()
    import dataclasses

    no_aux = dataclasses.replace(MOE_ARGS, moe_aux_weight=0.0)
    l_with, _ = llama.loss_fn(params, batch, MOE_ARGS)
    l_without, _ = llama.loss_fn(params, batch, no_aux)
    assert float(l_with) > float(l_without)


def test_router_z_loss_applies_without_aux_weight():
    # z-loss must survive moe_aux_weight=0 (it is scaled independently).
    import dataclasses

    params = llama.init_params(jax.random.PRNGKey(0), MOE_ARGS)
    batch = _batch()
    base = dataclasses.replace(MOE_ARGS, moe_aux_weight=0.0, router_z_weight=0.0)
    with_z = dataclasses.replace(MOE_ARGS, moe_aux_weight=0.0, router_z_weight=1.0)
    l0, _ = llama.loss_fn(params, batch, base)
    lz, _ = llama.loss_fn(params, batch, with_z)
    assert float(lz) > float(l0)


def test_moe_nondivisible_seq_is_padded_not_regrouped():
    # S=20 with group 8 pads to 24 (3 groups) instead of reverting to one
    # O(S) capacity group; output stays finite and correctly shaped.
    import dataclasses

    args = dataclasses.replace(MOE_ARGS, moe_group_size=8)
    params = llama.init_params(jax.random.PRNGKey(0), args)
    batch = _batch(bs=2, seq=20)
    loss, _ = llama.loss_fn(params, batch, args)
    assert np.isfinite(float(loss))
    logits, _ = llama.forward(params, batch["inputs"], args)
    assert logits.shape == (2, 20, MOE_ARGS.vocab_size)


def test_eval_loss_excludes_router_aux():
    params = llama.init_params(jax.random.PRNGKey(0), MOE_ARGS)
    batch = _batch()
    l_train, _ = llama.loss_fn(params, batch, MOE_ARGS, include_aux=True)
    l_eval, _ = llama.loss_fn(params, batch, MOE_ARGS, include_aux=False)
    assert float(l_train) > float(l_eval)


def test_mlp_bias_with_moe_rejected():
    import dataclasses

    bad = dataclasses.replace(MOE_ARGS, mlp_bias=True)
    with pytest.raises(ValueError, match="mlp_bias"):
        llama.init_params(jax.random.PRNGKey(0), bad)


def test_moe_token_grouping_keeps_capacity_bounded():
    # group_size fixes capacity independent of S: dispatch memory is O(S).
    import dataclasses

    args = dataclasses.replace(MOE_ARGS, moe_group_size=8)
    params = llama.init_params(jax.random.PRNGKey(0), args)
    batch = _batch(bs=2, seq=32)  # 4 groups of 8 per row
    loss, _ = llama.loss_fn(params, batch, args)
    assert np.isfinite(float(loss))
    # per-group capacity stays fixed while whole-sequence capacity grows
    assert moe.expert_capacity(8, 4, 2, 1.25) < moe.expert_capacity(32, 4, 2, 1.25)


@pytest.mark.slow
def test_moe_decode_cache_matches_full_forward():
    params = llama.init_params(jax.random.PRNGKey(0), MOE_ARGS)
    tokens = jnp.asarray(np.random.default_rng(1).integers(1, 120, (1, 8)), jnp.int32)
    full, _ = llama.forward(params, tokens, MOE_ARGS)
    cache = llama.init_cache(MOE_ARGS, 1, 16)
    logits, cache = llama.forward(params, tokens[:, :4], MOE_ARGS, cache=cache, start_pos=0)
    outs = [logits[:, -1]]
    for i in range(4, 8):
        logits, cache = llama.forward(
            params, tokens[:, i : i + 1], MOE_ARGS, cache=cache, start_pos=i
        )
        outs.append(logits[:, -1])
    # decode sees the whole prefix; capacity is per-call so early-token
    # routing can differ slightly from the full pass — compare loosely.
    np.testing.assert_allclose(
        np.asarray(outs[-1]), np.asarray(full[:, -1]), atol=2e-2, rtol=2e-2
    )


@pytest.mark.slow
def test_moe_train_step_on_ep_mesh_matches_single_device():
    if jax.device_count() < 4:
        pytest.skip("needs 4 virtual devices")
    sys_cfg = SystemConfig(seed=0, device="cpu", mesh={"ep": 2, "dp": 2})
    mesh = build_mesh(sys_cfg, devices=jax.devices()[:4])
    params = llama.init_params(jax.random.PRNGKey(0), MOE_ARGS)
    tr = TrainingConfig(
        hyperparameters={"learning_rate": 1e-3},
        scheduler={"type": "cosine"},
        optimization={"optimizer": "adamw"},
    )
    opt = build_optimizer(tr, 10)

    def loss_fn(p, b):
        return llama.loss_fn(p, b, MOE_ARGS)

    batch = _batch(bs=8)
    # single-device reference first: the sharded step donates its buffers
    sstep, _ = make_train_step(loss_fn, opt)
    sstate = init_train_state(jax.tree_util.tree_map(jnp.copy, params), opt)
    _, smetrics = sstep(sstate, batch)

    step, shardings = make_train_step(loss_fn, opt, mesh=mesh, params_like=params)
    state = jax.device_put(init_train_state(params, opt), shardings)
    new_state, metrics = step(state, batch)
    sharded_loss = float(metrics["loss"])
    assert sharded_loss == pytest.approx(float(smetrics["loss"]), rel=1e-4)
    # expert weights actually sharded over ep
    w = new_state["params"]["layers"][0]["feed_forward"]["experts"]["w_gate"]["weight"]
    spec = w.sharding.spec
    assert spec and spec[0] == "ep", f"expert dim not ep-sharded: {spec}"


# -- grouped (dropless, sort-based) dispatch ---------------------------------

def test_grouped_matches_einsum_loss_and_grads():
    # At ample capacity (CF = E/K) the einsum oracle drops nothing, so both
    # impls compute the same math modulo fp32 summation order.
    import dataclasses

    args_g = dataclasses.replace(MOE_ARGS, moe_impl="grouped", moe_group_size=16)
    args_e = dataclasses.replace(
        MOE_ARGS, moe_impl="einsum", moe_group_size=16,
        moe_capacity_factor=float(MOE_ARGS.num_local_experts)
        / MOE_ARGS.num_experts_per_tok)
    params = llama.init_params(jax.random.PRNGKey(0), MOE_ARGS)
    batch = _batch()
    lg, gg = jax.value_and_grad(lambda p: llama.loss_fn(p, batch, args_g)[0])(params)
    le, ge = jax.value_and_grad(lambda p: llama.loss_fn(p, batch, args_e)[0])(params)
    assert float(lg) == pytest.approx(float(le), abs=1e-6)
    flat_g = jax.tree_util.tree_leaves_with_path(gg)
    flat_e = jax.tree_util.tree_leaves_with_path(ge)
    for (kg, vg), (ke, ve) in zip(flat_g, flat_e):
        assert kg == ke
        np.testing.assert_allclose(
            np.asarray(vg), np.asarray(ve), atol=1e-6, rtol=1e-4,
            err_msg=f"grad mismatch at {jax.tree_util.keystr(kg)}")


def test_grouped_is_dropless_keeps_overflow_tokens():
    # Starved capacity: the einsum impl drops selections (counted in its
    # routing stats), the sorted grouped path keeps every one.
    import dataclasses

    args_e = dataclasses.replace(
        MOE_ARGS, moe_impl="einsum", moe_group_size=16, moe_capacity_factor=0.25)
    args_g = dataclasses.replace(args_e, moe_impl="grouped")
    params = llama.init_params(jax.random.PRNGKey(0), MOE_ARGS)
    batch = _batch()

    def run(args):
        loss, (_, stats) = llama.loss_fn(params, batch, args, with_moe_stats=True)
        return float(loss), float(stats["moe_dropped"])

    loss_e, dropped_e = run(args_e)
    loss_g, dropped_g = run(args_g)
    assert dropped_e > 0, "starved einsum capacity must drop selections"
    assert dropped_g == 0, "grouped dispatch must be dropless"
    assert np.isfinite(loss_e) and np.isfinite(loss_g)
    # the kept overflow tokens actually change the computed loss
    assert loss_g != pytest.approx(loss_e, abs=1e-7)


# name: K, N, block_t, group sizes, T (rows past the groups are a dead tail),
# dtype, the VMEM budget as the column block gmm should just fit with (None:
# the module's own), and the column block gmm's plan then takes (tgmm holds
# float32 sums besides, so under a tight budget its block may be narrower).
_GMM_CASES = {
    "bn_is_N-f32-bt64": (32, 48, 64, [64, 0, 128, 64], 256, jnp.float32, None, 48),
    "bn_is_N-bf16-bt8-dead_tail": (32, 256, 8, [16, 0, 24, 8], 80, jnp.bfloat16, None, 256),
    "two_column_blocks-f32-bt64-dead_tail": (32, 512, 64, [64, 0, 192, 64], 512, jnp.float32, 256, 256),
    "two_column_blocks-bf16-bt8": (32, 512, 8, [8, 0, 32, 8], 48, jnp.bfloat16, 256, 256),
    "narrowest_128-f32-bt8-dead_tail": (32, 256, 8, [8, 0, 24, 16], 72, jnp.float32, 128, 128),
    "narrowest_128-bf16-bt64": (32, 256, 64, [64, 0, 128, 64], 256, jnp.bfloat16, 128, 128),
}


@pytest.mark.parametrize("backend,case", [("blocked", "bn_is_N-f32-bt64")]
                         + [("pallas", case) for case in _GMM_CASES])
def test_gmm_backends_match_ragged_fwd_and_bwd(backend, case, monkeypatch):
    """blocked and (interpret-mode) pallas against the XLA-native ragged_dot
    reference: forward values and both gradients, at every width the kernels'
    plan takes (the weights' whole width, several column blocks, the
    narrowest block of 128, which tgmm takes over its budget), with an empty
    group, an expert of several tiles and a dead tail of several tiles."""
    from mlx_cuda_distributed_pretraining_tpu.ops import grouped_matmul as gm

    K, N, bt, sizes, T, dtype, admit, want = _GMM_CASES[case]
    if admit is not None:
        monkeypatch.setattr(gm, "_RESIDENT_VMEM_BUDGET",
                            gm._vmem_bytes("gmm", K, admit, bt, jnp.dtype(dtype).itemsize))
    assert gm.gmm_plan(K, N, bt, dtype) == want
    dw_bn = gm.gmm_plan(K, N, bt, dtype, "tgmm")
    assert dw_bn <= want
    rng = np.random.default_rng(0)
    live = (np.arange(T) < sum(sizes))[:, None]  # the dispatcher's buffer: zero rows past the groups
    x = jnp.asarray(rng.normal(size=(T, K)) * live, dtype)
    w = jnp.asarray(rng.normal(size=(len(sizes), K, N)), dtype)
    sizes = jnp.asarray(sizes, jnp.int32)

    def loss(x, w, backend):
        y = gm.gmm(x, w, sizes, block_t=bt, backend=backend).astype(jnp.float32)
        return (y * y).sum(), y

    (ref_l, ref_y), (ref_dx, ref_dw) = jax.value_and_grad(
        loss, argnums=(0, 1), has_aux=True)(x, w, "ragged")
    seen = gm.plan_counts()
    (l, y), (dx, dw) = jax.value_and_grad(
        loss, argnums=(0, 1), has_aux=True)(x, w, backend)
    traced = {k: n - seen.get(k, 0) for k, n in gm.plan_counts().items() if n - seen.get(k, 0)}
    if backend == "pallas":  # the forward and dX (over the weights' other axis); dW
        assert traced["gmm_resident"] == 2 and traced["tgmm_resident"] == 1
        assert traced[f"gmm_bn{want}"] >= 1 and traced[f"tgmm_bn{dw_bn}"] == 1
    else:
        assert not traced
    f32 = dtype == jnp.float32
    for name, got, ref, atol in (("y", y, ref_y, 1e-5), ("dx", dx, ref_dx, 1e-3), ("dw", dw, ref_dw, 1e-3)):
        ref = np.asarray(ref, np.float32)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), ref, err_msg=f"{backend} {name}",
            atol=atol if f32 else 0.02 * np.abs(ref).max(), rtol=1e-4 if f32 else 0.02)


# cell 2's and cell 3's expert matrices, both orientations (the forward's and
# dX's), at the cells' row tile in bfloat16: gmm holds the whole width of the
# weights, tgmm half of it or more
@pytest.mark.parametrize("K,N", [(3584, 1024), (1024, 3584), (2048, 1024), (1024, 2048)])
@pytest.mark.parametrize("kernel", ["gmm", "tgmm"])
def test_gmm_plan_keeps_the_cells_matrices_resident(kernel, K, N):
    from mlx_cuda_distributed_pretraining_tpu.ops import grouped_matmul as gm

    bn = gm.gmm_plan(K, N, 128, jnp.bfloat16, kernel)
    assert N % bn == 0 and bn % 128 == 0
    assert bn >= 512 and (kernel == "tgmm" or bn == N)
    assert gm._vmem_bytes(kernel, K, bn, 128, 2) <= gm._RESIDENT_VMEM_BUDGET
    assert bn == gm.gmm_plan(K, N, 128, jnp.bfloat16, kernel)   # a pure function


@pytest.mark.parametrize("K,N,block_t,dtype,kernel,want", [
    (10**6, 1024, 128, jnp.bfloat16, "gmm", 128),   # a K no budget holds: the narrowest block
    (10**6, 64, 8, jnp.float32, "gmm", 64),
    (14336, 4096, 128, jnp.bfloat16, "gmm", 256),   # wider than 128 and under N
    (14336, 4096, 128, jnp.bfloat16, "tgmm", 128),  # 128 inside the budget
    (2048, 64, 8, jnp.bfloat16, "gmm", 64),         # a decode-sized dispatch, N under 128
    (256, 200, 16, jnp.float32, "tgmm", 200),       # no multiple of 128 divides N
    (2048, 128, 8, jnp.float32, "gmm", 128),
])
def test_gmm_plan_by_shape(K, N, block_t, dtype, kernel, want):
    from mlx_cuda_distributed_pretraining_tpu.ops import grouped_matmul as gm

    assert gm.gmm_plan(K, N, block_t, dtype, kernel) == want
    if kernel == "gmm":
        assert gm.gmm_plan(K, N, block_t, dtype) == want


def _scatter_ffn(experts, x_flat, gate_idx, gate_w, num_experts, block_t, first=0):
    """``moe.grouped_ffn`` as it was before its dispatch and combine became
    gathers: a scatter of the sorted rows into the buffer and a scatter-add of
    the weighted rows back, differentiated by XLA. The reference of the pair."""
    from mlx_cuda_distributed_pretraining_tpu.ops import grouped_matmul as gm

    T, D = x_flat.shape
    K = gate_idx.shape[-1]
    TK = T * K
    local = gate_idx.reshape(TK) - first
    ids = jnp.where((local >= 0) & (local < num_experts), local, num_experts)
    tok = jnp.arange(TK, dtype=jnp.int32) // K
    counts = jnp.bincount(ids, length=num_experts + 1)[:num_experts]
    padded = ((counts + block_t - 1) // block_t) * block_t
    p_off = jnp.concatenate([jnp.zeros((1,), padded.dtype), jnp.cumsum(padded)])
    raw_off = jnp.cumsum(counts) - counts
    order = jnp.argsort(ids, stable=True)
    ids_s = ids[order]
    real = ids_s < num_experts
    ids_c = jnp.minimum(ids_s, num_experts - 1)
    rank = jnp.arange(TK, dtype=jnp.int32) - raw_off[ids_c].astype(jnp.int32)
    T_buf = gm.round_up(TK + num_experts * (block_t - 1), block_t)
    dest = jnp.where(real, (p_off[ids_c] + rank).astype(jnp.int32), T_buf)  # OOB = no row
    x_buf = jnp.zeros((T_buf, D), x_flat.dtype).at[dest].set(x_flat[tok[order]])
    w = lambda name: experts[name]["weight"]
    h = jax.nn.silu(gm.gmm(x_buf, w("w_gate"), padded, block_t=block_t)) * gm.gmm(
        x_buf, w("w_up"), padded, block_t=block_t)
    y_buf = gm.gmm(h, w("w_down"), padded, block_t=block_t)
    w_s = jnp.where(real, gate_w.reshape(TK)[order], 0).astype(y_buf.dtype)
    return jnp.zeros((T, D), x_flat.dtype).at[tok[order]].add(
        y_buf[jnp.minimum(dest, T_buf - 1)] * w_s[:, None])


def _dispatch_case(case, dtype, D=24, width=16):
    """(experts, x, gate_idx, gate_w, num_experts, block_t, first) of one case."""
    rng = np.random.default_rng(11)
    T, K, E, first, bt, router = 32, 2, 4, 0, 8, 4
    if case == "held_share":          # most selections belong to experts held elsewhere
        first, router = 4, 16
    elif case == "ragged_rows":       # T * K no multiple of block_t
        T, K = 13, 3
    idx = np.stack([rng.permutation(router)[:K] for _ in range(T)]).astype(np.int32)
    if case == "empty_expert":        # expert 2 gets no row
        idx = np.where(idx == 2, 3, idx)
    elif case == "one_expert":        # every selection on expert 1
        idx = np.ones_like(idx)
    bank = lambda *shape: jnp.asarray(rng.normal(size=shape) * 0.3, dtype)
    experts = {"w_gate": {"weight": bank(E, D, width)}, "w_up": {"weight": bank(E, D, width)},
               "w_down": {"weight": bank(E, width, D)}}
    x = jnp.asarray(rng.normal(size=(T, D)), dtype)
    gate_w = jnp.asarray(rng.uniform(0.1, 1.0, size=(T, K)), dtype)
    return experts, x, jnp.asarray(idx), gate_w, E, bt, first


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", ["every_expert_held", "held_share", "empty_expert",
                                  "one_expert", "ragged_rows"])
def test_gather_dispatch_and_combine_match_the_scatter_form(case, dtype):
    """Output and the gradients of ``x``, ``gate_w`` and the three banks of the
    gathers both ways (custom backward) against the scatter formulation that
    XLA differentiates: in float32 exact to 1e-6 of a leaf's largest value (the
    sums run in another order)."""
    experts, x, idx, gate_w, E, bt, first = _dispatch_case(case, jnp.dtype(dtype))
    held = int(((np.asarray(idx) >= first) & (np.asarray(idx) < first + E)).sum())
    assert 0 < held < idx.size // 2 if case == "held_share" else held == idx.size
    if case == "ragged_rows":
        assert idx.size % bt

    def run(ffn, cast=None):
        leaves = jax.tree_util.tree_map(lambda a: a.astype(cast or a.dtype), (experts, x, gate_w))

        def loss(experts, x, gate_w):
            out = ffn(experts, x, idx, gate_w, E, bt, first=first)
            return jnp.sum(jnp.sin(out.astype(jnp.float32))), out
        (_, out), grads = jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)(*leaves)
        return [out] + jax.tree_util.tree_leaves(grads)

    got, want = run(moe.grouped_ffn), run(_scatter_ffn)
    assert len(got) == 1 + 3 + 2
    # bfloat16: both forms against the scatter form in float32 on the same rounded inputs
    truth = want if dtype == "float32" else run(_scatter_ffn, jnp.float32)
    for name, a, b, t in zip(("out", "w_down", "w_gate", "w_up", "x", "gate_w"), got, want, truth):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        a, b, t = (np.asarray(v, np.float32) for v in (a, b, t))
        scale = np.abs(t).max()
        assert scale > 0, name
        if dtype == "float32":
            np.testing.assert_allclose(a, b, atol=1e-6 * scale, rtol=1e-6, err_msg=name)
        else:  # the file's 2e-2 of the leaf's largest value, or what the scatter form is off by
            off, was = np.abs(a - t).max() / scale, np.abs(b - t).max() / scale
            assert off <= max(2e-2, 1.25 * was), (name, off, was)


def _token_sum_case(case, dtype, D=128):
    """``(plan, y_buf, x, gate_w)`` of one case of the token-side sums: the plan of
    32 tokens' selections at tiles of 16 rows, a buffer of its rows, the tokens'
    rows and their gate weights."""
    rng = np.random.default_rng(5)
    T, K, E, first, router, rows = 32, 2, 4, 0, 4, None
    if case in ("held_share", "token_holds_nothing", "short_buffer"):
        first, router = 4, 16            # most selections belong to experts held elsewhere
    idx = np.stack([rng.permutation(router)[:K] for _ in range(T)]).astype(np.int32)
    if case == "empty_expert":           # expert 2 gets no row
        idx = np.where(idx == 2, 3, idx)
    elif case == "token_holds_nothing":  # nor do three tokens in a row
        idx[5:8] = [12, 13]
    elif case == "short_buffer":         # the caller has seen that the held rows fit fewer
        rows = 64
        assert int(moe.held_rows(jnp.asarray(idx), E, 16, first)) <= rows < moe.buffer_rows(T * K, E, 16)
    plan = moe.dispatch_plan(jnp.asarray(idx), E, 16, first, rows)
    y_buf = jnp.asarray(rng.normal(size=(plan.row_sel.shape[0], D)), dtype)
    x = jnp.asarray(rng.normal(size=(T, D)), dtype)
    gate_w = jnp.asarray(rng.uniform(0.1, 1.0, size=(T, K)), jnp.float32)
    return plan, y_buf, x, gate_w


@pytest.mark.parametrize("form", ["kernel", "xla"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", ["every_expert_held", "held_share", "empty_expert",
                                  "token_holds_nothing", "short_buffer"])
def test_token_side_sums_match_the_gather_of_every_selection(case, dtype, form, monkeypatch):
    """The combine (value, ``dy_buf``, ``dgate_w``) and the dispatch's backward
    (``dx``) against XLA's own derivative of the two lines they were,
    ``_sum_held(_take_rows(buf, sel_row), scale)``: with the kernel forced
    (interpret mode) and in XLA's form, where ``dgate_w`` is taken on the
    buffer's side. The sums run in float32 in another order (the kernel's by
    expert), so a float32 leaf agrees to 1e-6 of its largest value and a
    bfloat16 one to a rounding of the cast."""
    if form == "kernel":
        monkeypatch.setenv("GMM_BACKEND", "pallas")
    plan, y_buf, x, gate_w = _token_sum_case(case, jnp.dtype(dtype))
    sin_sum = lambda out: jnp.sum(jnp.sin(out.astype(jnp.float32)))
    probe = jnp.asarray(np.random.default_rng(6).normal(size=y_buf.shape), y_buf.dtype)

    def gathered(y_buf, gate_w):
        return moe._sum_held(moe._take_rows(y_buf, plan.sel_row),
                             jnp.where(plan.sel_held, gate_w, 0), y_buf.dtype)
    seen = moe.plan_counts()
    got = [moe.combine_rows(y_buf, gate_w, plan),
           *jax.grad(lambda y, w: sin_sum(moe.combine_rows(y, w, plan)), (0, 1))(y_buf, gate_w),
           jax.grad(lambda x: sin_sum(moe.dispatch_rows(x, plan) * probe))(x)]
    traced = {k: n - seen[k] for k, n in moe.plan_counts().items()}
    # the combine alone, the differentiated combine and its ``dgate_w``, the dispatch's backward
    assert traced["token_sum_kernel" if form == "kernel" else "token_sum_xla"] == 4
    assert traced["token_sum_xla" if form == "kernel" else "token_sum_kernel"] == 0
    want = [gathered(y_buf, gate_w),
            *jax.grad(lambda y, w: sin_sum(gathered(y, w)), (0, 1))(y_buf, gate_w),
            jax.grad(lambda x: sin_sum(moe._dispatch_rows(x, plan) * probe))(x)]
    held_tokens = np.asarray(plan.sel_held).any(-1)
    assert held_tokens.all() == (case in ("every_expert_held", "empty_expert"))
    for name, a, b in zip(("out", "dy_buf", "dgate_w", "dx"), got, want):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.abs(b).max() > 0, name
        tol = 1e-6 if b.dtype == a.dtype and dtype == "float32" or name == "dgate_w" else 2.0 ** -7
        np.testing.assert_allclose(a, b, atol=tol * np.abs(b).max(), rtol=tol, err_msg=name)
    assert not np.asarray(got[0], np.float32)[~held_tokens].any()   # a token that holds nothing sums nothing


@pytest.mark.parametrize("arch", ["afmoe", "xing_mla_moe"])
def test_a_routed_layers_gradient_builds_no_row_for_every_selection(arch, monkeypatch):
    """One routed layer that holds a share, value and gradient, with the kernels
    forced: no array ``[tokens, top-k, width]`` anywhere in the jaxpr (a
    chunk's ``[T, K, D]``, which XLA's form gathers four times a layer), and
    every token-side sum tallied as the kernel's; off the chip, unforced, the
    same layer gathers it and tallies XLA's form."""
    def traced(backend):
        monkeypatch.setenv("GMM_BACKEND", backend)
        block, layer, x, args = _tiny_block(arch, monkeypatch)
        loss = lambda p, x: jnp.sum(jnp.sin(jax.checkpoint(lambda p, x: block(p, x))(p, x)))
        seen = moe.plan_counts()
        jaxpr = _live(jax.make_jaxpr(jax.value_and_grad(loss, (0, 1)))(layer, x))
        chunk_tokens = 2 * 128 // moe.held_chunks(2 * 128, args.num_experts_per_tok, 2, 8, 128)[1]
        shape = (chunk_tokens, args.num_experts_per_tok, args.hidden_size)
        wide = _equations(jaxpr, lambda e: any(
            getattr(v.aval, "shape", None) == shape for v in e.outvars))
        return wide, {k: n - seen[k] for k, n in moe.plan_counts().items()}

    wide, counts = traced("pallas")
    assert not wide, [str(e.primitive) for e in wide]
    assert counts["token_sum_kernel"] == 4 and counts["token_sum_xla"] == 0
    wide, counts = traced("ragged")
    assert wide and counts["token_sum_xla"] == 4 and counts["token_sum_kernel"] == 0


def test_grouped_block_gradient_has_no_scatter_of_activation_rows(monkeypatch):
    """``moe_block`` with ``moe_impl: grouped`` on one device: no scatter or
    scatter-add of rows as wide as the activations in the lowered gradient
    (``ragged`` forced: the ``blocked`` backend's own dW is a scatter-add
    through its weight gather), where the scatter form has both; and the
    dispatch and the combine are tallied as traced."""
    import dataclasses

    monkeypatch.setenv("GMM_BACKEND", "ragged")
    D = 40
    args = dataclasses.replace(MOE_ARGS, hidden_size=D, moe_impl="grouped")
    p = moe.init_moe_params(iter(jax.random.split(jax.random.PRNGKey(0), 4)), args)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, D), jnp.float32)
    seen = moe.plan_counts()
    grad = jax.jit(jax.grad(lambda p, x: jnp.sum(jnp.sin(moe.moe_block(p, x, args)[0])), (0, 1)))
    hlo = grad.lower(p, x).as_text(dialect="hlo")
    assert {k: n - seen[k] for k, n in moe.plan_counts().items()} == {
        "dispatch_gather": 1, "combine_gather": 1, "chunk_loop_tail": 0,   # no chunk loop here,
        "chunk_two_sizes": 0, "chunk_trips_small": 0, "chunk_trips_whole": 0,   # and one buffer size;
        # the combine, its backward's dgate_w and the dispatch's backward, XLA's form here
        "token_sum_kernel": 0, "token_sum_xla": 3}
    assert " gather(" in hlo and not activation_scatters(hlo, D)

    experts, xs, idx, gate_w, E, bt, first = _dispatch_case("held_share", jnp.float32, D=D)
    old = jax.jit(jax.grad(lambda x: jnp.sum(_scatter_ffn(experts, x, idx, gate_w, E, bt, first))))
    assert len(activation_scatters(old.lower(xs).as_text(dialect="hlo"), D)) >= 2


def _tiny_block(arch, monkeypatch, held_count=2):
    """``(block(p, x) -> x', one routed layer's weights, the layer's input, the
    model's args)`` of an architecture whose routed layers hold a share, at its
    rehearsal widths (2 x 128 tokens, top-2, 2 of 8 experts held: 4 chunks of
    128 rows at one buffer size; ``held_count`` 1 holds an eighth, and the
    tokens then go in 2 chunks through a small buffer of 256 rows (tiles of
    128) or in 4 through the whole one of 192 (tiles of 64))."""
    from benchmark import run as harness

    positions = jnp.arange(128, dtype=jnp.int32)
    held = {"experts_held": {"first": 2, "count": held_count}}
    if arch == "afmoe":
        import test_afmoe as t
        from mlx_cuda_distributed_pretraining_tpu.models import afmoe

        cfg = dict(harness.merge_into(t.FULL, t.TINY["config"]), **held)
        args = t._args(cfg)
        block = lambda p, x: afmoe.block(p, x, positions, args, True, True)[0]
        shape = (2, 128, cfg["hidden_size"])
    else:
        import test_xing as t
        from mlx_cuda_distributed_pretraining_tpu.config import Config
        from mlx_cuda_distributed_pretraining_tpu.models import xing

        cfg = dict(harness.merge_into(t.FULL, t.TINY["config"]), **held)
        section = t.kind.MODEL_SECTIONS["xing_mla_moe"](cfg, {"attention_type": "simple"})
        args = xing.XingArgs.from_config(
            Config.from_dict({"name": "t", "model": section}).model, cfg["vocab_size"])
        monkeypatch.setattr(xing, "HELD_CHUNK_ROWS", 128)
        block = lambda p, X: xing.block(p, X, positions, args, True)[0]
        shape = (args.hc_mult, 2, 128, cfg["hidden_size"])
    layer = jax.tree_util.tree_map(jnp.asarray, t.ref.init_params(7, cfg)["layers"][0])
    assert args.experts_held == (2, held_count) and args.n_routed_experts == 8
    assert moe.held_chunks(2 * 128, args.num_experts_per_tok, held_count, 8, 128) == (
        (4, 4) if held_count == 2 else (2, 4))
    return block, layer, jax.random.normal(jax.random.PRNGKey(3), shape, jnp.float32), args


def _equations(jaxpr, keep, path=None):
    """Equations that ``keep`` accepts of ``jaxpr`` and of every jaxpr inside
    it; with ``path``, of a ``cond`` only that branch (what one pass executes)."""
    found = [e for e in jaxpr.eqns if keep(e)]
    for e in jaxpr.eqns:
        inside = jax.core.jaxprs_in_params(e.params)
        if path is not None and e.primitive.name == "cond":
            inside = [e.params["branches"][path].jaxpr]
        for sub in inside:
            found += _equations(sub, keep, path)
    return found


def _live(closed):
    """``closed.jaxpr`` without the equations no output reads: what XLA's own
    dead-code elimination leaves of it."""
    from jax.interpreters import partial_eval as pe

    return pe.dce_jaxpr(closed.jaxpr, [True] * len(closed.jaxpr.outvars))[0]


@pytest.mark.parametrize("held_count", [2, 1])
@pytest.mark.parametrize("arch", ["afmoe", "xing_mla_moe"])
def test_a_rematerialised_layer_runs_its_held_experts_forward_twice_not_thrice(
        arch, held_count, monkeypatch):
    """A routed layer that holds a share, under ``jax.checkpoint`` as full
    remat has it, 4 chunks: with the layer's token-local tail inside the chunk
    loop nothing outside reads the loop's value, so the gradient holds the
    loop's backward (which recomputes each chunk) and no forward loop beside
    it; with the tail outside (the arrangement until PR 36: the tail reads the
    loop's value) it holds one loop over the chunks more and the three expert
    matmuls of that pass. Same output, bit for bit, and the same gradients.

    A quarter of the experts held (2 of 8) is one buffer size and no ``cond``;
    an eighth is two sizes, each loop the branch of a ``cond``, and the counts
    hold along either path: the small buffer's branch of every ``cond`` (1),
    whose loops take 2 trips, or the whole one's (0), whose loops take 4."""
    monkeypatch.setenv("GMM_BACKEND", "ragged")   # an expert matmul is one ``ragged_dot_general``
    block, layer, x, _ = _tiny_block(arch, monkeypatch, held_count)
    two_sizes = int(held_count == 1)
    def arrangement():   # fresh functions: a trace is cached by the function traced
        fwd = lambda p, x: block(p, x)
        loss = lambda p, x: jnp.sum(jnp.sin(jax.checkpoint(fwd)(p, x)))
        seen = moe.plan_counts()
        jaxpr = _live(jax.make_jaxpr(jax.grad(loss, (0, 1)))(layer, x))
        traced = {k: n - seen[k] for k, n in moe.plan_counts().items()}
        return jaxpr, traced, jax.jit(fwd)(layer, x), jax.jit(jax.grad(loss, (0, 1)))(layer, x)

    # a chunk function is traced once a size, whatever differentiates it afterwards; its
    # token-side passes once each where they run: the combine in the loop's forward and in the
    # backward's recomputation of a chunk, its dgate_w and the dispatch's backward beside it
    once = {"dispatch_gather": 1 + two_sizes, "combine_gather": 1 + two_sizes,
            "chunk_two_sizes": two_sizes, "chunk_trips_small": 2 * two_sizes, "chunk_trips_whole": 4,
            "token_sum_kernel": 0, "token_sum_xla": 4 * (1 + two_sizes)}
    new, traced, got, grads = arrangement()
    assert traced == dict(once, chunk_loop_tail=1)
    monkeypatch.setattr(moe, "sigmoid_routed_ffn", tail_after_loop(moe.sigmoid_routed_ffn))
    old, traced, was, grads_were = arrangement()
    assert traced == dict(once, chunk_loop_tail=0)

    matmul = lambda e: e.primitive.name == "ragged_dot_general"
    choice = lambda e: e.primitive.name == "cond"
    for path in ((1, 0) if two_sizes else (None,)):
        chunk_loop = lambda e: e.primitive.name == "scan" and e.params["length"] == (2 if path else 4)
        # three matmuls a pass: the forward, the loop's recomputation, dX, dW; and, with the
        # tail outside, the rematerialised layer's own pass over the loop
        assert len(_equations(old, matmul, path)) == 15 and len(_equations(new, matmul, path)) == 12
        assert len(_equations(old, chunk_loop, path)) == 3 and len(_equations(new, chunk_loop, path)) == 2
    # the forward's choice and the backward's, and the extra pass's where the tail is outside
    assert len(_equations(new, choice)) == 2 * two_sizes and len(_equations(old, choice)) == 3 * two_sizes
    np.testing.assert_array_equal(np.asarray(got), np.asarray(was))
    for a, b in zip(jax.tree_util.tree_leaves(grads), jax.tree_util.tree_leaves(grads_were)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-6)


def _held_share_case(arch, ids, n_chunks, monkeypatch):
    """``(experts, x, idx, gate_w, held, n_routed, chunk_rows)`` at ``arch``'s tiny
    widths with an eighth of the experts held (1 of 8), ``chunk_rows`` such that
    the whole buffer's loop takes ``n_chunks`` trips and the small one's half
    of them (one where ``n_chunks`` is one): ``ids`` ``fit`` draws every
    token's top-2 over the router's whole width, so a chunk's held rows fit
    its small buffer; ``all_held`` sends every selection to the held expert, so
    no chunk's do; ``doubled_chunk_overflows`` sends the held expert, from each
    half of the small loop's first chunk, as many selections as the small
    buffer of a chunk of that half's size has rows (``fit``'s elsewhere), so
    that each half would have fitted alone and the two together do not."""
    from mlx_cuda_distributed_pretraining_tpu.ops import grouped_matmul as gm

    _, layer, _, args = _tiny_block(arch, monkeypatch, held_count=1)
    first, n_routed, K = args.experts_held[0], args.n_routed_experts, args.num_experts_per_tok
    rng = np.random.default_rng(5)
    B, S, C = 2, 512, args.hidden_size   # 2,048 selections: a small buffer short of them
    idx = np.stack([rng.permutation(n_routed)[:K] for _ in range(B * S)]).astype(np.int32)
    if ids == "all_held":
        idx = np.full_like(idx, first)
    elif ids == "doubled_chunk_overflows":
        half = B * S // n_chunks                       # tokens of a chunk of the whole buffer's loop
        fits_a_half = moe.chunk_buffer_rows(half * K, 1, n_routed, gm.pick_block_t(half * K, 1))[0]
        assert fits_a_half % K == 0 and fits_a_half // K <= half
        for start in (0, half):
            idx[start:start + half] = (first + 1) % n_routed
            idx[start:start + fits_a_half // K] = first
    x = jnp.asarray(rng.normal(size=(B, S, C)), jnp.float32)
    gate_w = jnp.asarray(rng.uniform(0.1, 1.0, size=(B, S, K)), jnp.float32)
    return (layer["feed_forward"]["experts"], x, jnp.asarray(idx.reshape(B, S, K)), gate_w,
            args.experts_held, n_routed, B * S * K // n_chunks)


def _chunked_is_grouped_ffn_at_the_whole_buffer(arch, ids, with_tail, n_chunks, monkeypatch):
    """``held_share_ffn`` on :func:`_held_share_case` against ``grouped_ffn`` at a
    row for every selection with the tail applied afterwards: output and every
    gradient in float32 to 1e-6 of a leaf's largest value (a grouped matmul
    blocks by its buffer's size) → the trips counted at the whole buffer."""
    from mlx_cuda_distributed_pretraining_tpu.ops import grouped_matmul as gm

    experts, x, idx, gate_w, held, n_routed, chunk_rows = _held_share_case(
        arch, ids, n_chunks, monkeypatch)
    B, S, C = x.shape
    K, (first, count) = idx.shape[-1], held
    trips = moe.held_chunks(B * S, K, count, n_routed, chunk_rows)
    assert trips == (max(1, n_chunks // 2), n_chunks)
    # the small loop's buffer over its own, longer chunks; the whole loop's over its own
    (small, _), (_, whole) = (
        moe.chunk_buffer_rows(B * S * K // n, count, n_routed, gm.pick_block_t(B * S * K // n, count))
        for n in trips)
    block_t = gm.pick_block_t(B * S * K // trips[0], count)
    assert small < moe.chunk_buffer_rows(B * S * K // trips[0], count, n_routed, block_t)[1]
    if n_chunks >= 4:     # half the trips through a buffer of the same rows (tiles of 128 in both)
        assert small == whole
    rows = [sum(gm.round_up(int((chunk == first + e).sum()), block_t) for e in range(count))
            for chunk in np.asarray(idx).reshape(trips[0], -1)]
    if ids == "fit":
        assert max(rows) <= small
    elif ids == "all_held":
        assert min(rows) > small
    else:
        assert rows[0] > small and max(rows[1:], default=0) <= small
    rng = np.random.default_rng(6)
    gain = jnp.asarray(rng.uniform(0.5, 1.5, size=(C,)), jnp.float32)
    res = jnp.asarray(rng.normal(size=(B, S, C)), jnp.float32)
    mix = lambda routed, gain, res: jnp.tanh(routed * gain) + res   # token-local

    def chunked(experts, x, gate_w, gain, res):
        tail = dict(tail=lambda routed, res_c: mix(routed, gain, res_c),
                    operands=((0, res),)) if with_tail else {}
        return moe.held_share_ffn(experts, x, idx, gate_w, held, n_routed, chunk_rows, **tail)

    def at_whole_buffer(experts, x, gate_w, gain, res):
        out = moe.grouped_ffn(experts, x.reshape(B * S, C), idx.reshape(B * S, K),
                              gate_w.reshape(B * S, K), count,
                              gm.pick_block_t(B * S * K, count), first=first).reshape(B, S, C)
        return (mix(out, gain, res) if with_tail else out), None

    def run(ffn):
        def loss(*leaves):
            out, took_whole = ffn(*leaves)
            return jnp.sum(jnp.sin(out)), (out, took_whole)
        (_, (out, took_whole)), grads = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2, 3, 4), has_aux=True))(experts, x, gate_w, gain, res)
        return took_whole, [out] + jax.tree_util.tree_leaves(grads)

    seen = moe.plan_counts()
    took_whole, got = run(chunked)
    traced = {k: n - seen[k] for k, n in moe.plan_counts().items()}
    assert (traced["chunk_two_sizes"], traced["chunk_trips_small"], traced["chunk_trips_whole"]) == (
        1, *trips)
    _, want = run(at_whole_buffer)
    assert len(got) == 1 + 3 + 4
    for name, a, b in zip(("out", "w_down", "w_gate", "w_up", "x", "gate_w", "gain", "res"),
                          got, want):
        if not with_tail and name in ("gain", "res"):
            assert not np.asarray(a).any(), name
            continue
        a, b = np.asarray(a), np.asarray(b)
        scale = np.abs(b).max()
        assert a.shape == b.shape and scale > 0, name
        np.testing.assert_allclose(a, b, atol=1e-6 * scale, rtol=1e-6, err_msg=name)
    return float(took_whole)


@pytest.mark.parametrize("n_chunks", [1, 2, 4])
@pytest.mark.parametrize("with_tail", [False, True], ids=["no_tail", "tail"])
@pytest.mark.parametrize("ids", ["fit", "all_held"])
@pytest.mark.parametrize("arch", ["afmoe", "xing_mla_moe"])
def test_a_chunk_at_either_buffer_size_is_grouped_ffn_at_the_whole_buffer(
        arch, ids, with_tail, n_chunks, monkeypatch):
    """A layer that holds an eighth of the experts takes its tokens through the
    small buffer, in half the trips the whole buffer's loop takes (one for
    one), where every chunk's held rows fit it, and through the whole dropless
    one, in ``n_chunks`` trips, where some chunk's do not, and counts the
    trips that took the whole one: 0 and all ``n_chunks`` here. On either
    path the output and the gradients (banks, tokens, gate weights; with a
    tail its operand and the weight it closes over) are ``grouped_ffn``'s at a
    row for every selection with the tail applied afterwards."""
    took_whole = _chunked_is_grouped_ffn_at_the_whole_buffer(arch, ids, with_tail, n_chunks, monkeypatch)
    assert took_whole == (0 if ids == "fit" else n_chunks)


@pytest.mark.parametrize("n_chunks", [2, 4])
@pytest.mark.parametrize("with_tail", [False, True], ids=["no_tail", "tail"])
@pytest.mark.parametrize("arch", ["afmoe", "xing_mla_moe"])
def test_one_doubled_chunk_that_overflows_sends_the_layer_through_the_whole_buffer(
        arch, with_tail, n_chunks, monkeypatch):
    """``fit`` is asked of the small loop's own chunks, which are two of the
    whole loop's: where the held rows of one of them do not fit its small
    buffer, although each of its halves would have fitted the small buffer of
    a chunk of its own size, the layer runs the whole buffer's loop at that
    loop's ``n_chunks`` trips, ``moe_chunks_whole`` counts them, and output and
    gradients are the same values as ever."""
    took_whole = _chunked_is_grouped_ffn_at_the_whole_buffer(
        arch, "doubled_chunk_overflows", with_tail, n_chunks, monkeypatch)
    assert took_whole == n_chunks


@pytest.mark.parametrize("tokens, top_k, held, n_routed, chunk_rows, trips, rows", [
    (8192, 4, 8, 64, 4096, (4, 8), (5120, 5120)),           # xing4_0-29b-a4b-ep8: 8 of 64, top-4
    (16384, 8, 16, 128, 65536, (1, 2), (67584, 67584)),     # trinity-mini-ep8: 16 of 128, top-8
    (8192, 4, 4, 64, 4096, (2, 8), (4608, 4608)),           # a sixteenth held: a quarter of the trips
    (8192, 4, 16, 64, 4096, (8, 8), (6144, 6144)),          # a quarter held: one size, one count
    (8192, 4, 64, 64, 4096, (1, 1), (40960, 40960)),        # every expert held: no loop
    (24, 4, 1, 8, 4, (8, 8), (16, 24)),                     # 3 tokens halve no further: both stop
], ids=["xing4_0-29b-a4b-ep8", "trinity-mini-ep8", "a_sixteenth_held", "a_quarter_held",
        "every_expert_held", "tokens_that_halve_no_further"])
def test_held_chunks_gives_each_loop_the_trips_of_its_own_buffer(
        tokens, top_k, held, n_routed, chunk_rows, trips, rows):
    """``chunk_rows`` bounds a chunk's selections in the whole buffer's loop and a
    chunk's ``SMALL_BUFFER_LOADS`` balanced loads in the small one's → ``(small,
    whole)`` trips, and the rows of each loop's buffer over its own chunks. The
    benchmark's two expert cells take half the trips through a buffer of the
    rows the whole loop's has (``tests/test_tpu_compile.py::GMM_CELLS``), a
    layer that holds a quarter of the experts or more has one count, and one
    that holds them all has no loop."""
    from mlx_cuda_distributed_pretraining_tpu.ops import grouped_matmul as gm

    assert moe.held_chunks(tokens, top_k, held, n_routed, chunk_rows) == trips
    selections = [tokens * top_k // n for n in trips]
    got = tuple(moe.chunk_buffer_rows(s, held, n_routed, gm.pick_block_t(s, held))[size]
                for size, s in enumerate(selections))
    assert got == rows


@pytest.mark.parametrize("held", [(2, 2), (0, 8)], ids=["a_quarter_held", "every_expert_held"])
def test_a_layer_that_holds_a_quarter_of_the_experts_or_more_traces_no_choice(held, monkeypatch):
    """``SMALL_BUFFER_LOADS`` times the balanced share is every selection where
    a layer holds ``n_routed / SMALL_BUFFER_LOADS`` experts or more: one buffer
    size, so neither the layer nor its gradient holds a ``cond``, nothing is
    tallied as traced at two sizes, and the count of whole-buffer chunks is a
    constant 0."""
    from mlx_cuda_distributed_pretraining_tpu.ops import grouped_matmul as gm

    experts, x, idx, gate_w, _, n_routed, chunk_rows = _held_share_case(
        "afmoe", "fit", 4, monkeypatch)
    first, count = held
    assert moe.SMALL_BUFFER_LOADS * count >= n_routed
    small, whole = moe.chunk_buffer_rows(chunk_rows, count, n_routed, gm.pick_block_t(chunk_rows, count))
    assert small == whole == moe.buffer_rows(chunk_rows, count, gm.pick_block_t(chunk_rows, count))
    experts = jax.tree_util.tree_map(lambda a: jnp.concatenate([a] * count), experts)

    def ffn(experts, x):
        return moe.held_share_ffn(experts, x, idx, gate_w, held, n_routed, chunk_rows)
    seen = moe.plan_counts()
    jaxprs = [jax.make_jaxpr(ffn)(experts, x),
              jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(ffn(*a)[0]), (0, 1)))(experts, x)]
    assert moe.plan_counts()["chunk_two_sizes"] == seen["chunk_two_sizes"]
    for jaxpr in jaxprs:
        assert not _equations(jaxpr.jaxpr, lambda e: e.primitive.name == "cond")
    assert float(jax.jit(ffn)(experts, x)[1]) == 0.0


def test_gmm_unknown_backend_rejected():
    from mlx_cuda_distributed_pretraining_tpu.ops import grouped_matmul as gm

    with pytest.raises(ValueError, match="unknown gmm backend"):
        gm.gmm(jnp.zeros((8, 4)), jnp.zeros((2, 4, 4)),
               jnp.asarray([8, 0]), block_t=8, backend="nope")


def test_aux_loss_ignores_group_padding():
    # Regression: aux is computed from real-token router probs before
    # dispatch, so the S=250 -> 256 group padding (and any other group
    # size) must not move it at all.
    import dataclasses

    args = dataclasses.replace(MOE_ARGS, max_position_embeddings=256)
    params = llama.init_params(jax.random.PRNGKey(0), args)
    p = params["layers"][0]["feed_forward"]
    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 250, 32)), jnp.float32)
    auxes = [
        float(moe.moe_block(
            p, x, dataclasses.replace(args, moe_impl=impl, moe_group_size=g))[1])
        for impl in ("einsum", "grouped") for g in (256, 125, 250)
    ]
    assert auxes[0] > 0
    for a in auxes[1:]:
        assert a == auxes[0], f"aux moved with group padding: {auxes}"


@pytest.mark.slow
def test_moe_grouped_ep4_matches_single_device():
    # Pure ep mesh, one expert shard per device: the all_to_all sorted
    # exchange must reproduce the single-device grouped loss.
    if jax.device_count() < 4:
        pytest.skip("needs 4 virtual devices")
    sys_cfg = SystemConfig(seed=0, device="cpu", mesh={"ep": 4})
    mesh = build_mesh(sys_cfg, devices=jax.devices()[:4])
    params = llama.init_params(jax.random.PRNGKey(0), MOE_ARGS)
    tr = TrainingConfig(
        hyperparameters={"learning_rate": 1e-3},
        scheduler={"type": "cosine"},
        optimization={"optimizer": "adamw"},
    )
    opt = build_optimizer(tr, 10)

    def loss_fn(p, b):
        return llama.loss_fn(p, b, MOE_ARGS)

    batch = _batch(bs=4)
    sstep, _ = make_train_step(loss_fn, opt)
    sstate = init_train_state(jax.tree_util.tree_map(jnp.copy, params), opt)
    _, smetrics = sstep(sstate, batch)

    step, shardings = make_train_step(loss_fn, opt, mesh=mesh, params_like=params)
    state = jax.device_put(init_train_state(params, opt), shardings)
    _, metrics = step(state, batch)
    assert float(metrics["loss"]) == pytest.approx(
        float(smetrics["loss"]), rel=1e-6)


@pytest.mark.slow
def test_shampoo_bank_stats_shard_over_ep():
    """Shampoo's per-expert preconditioner stats [E, m, m] must shard over
    ep with their bank, not replicate (parallel/sharding_rules.py
    match_opt_leaf_spec leading-dim inheritance)."""
    if jax.device_count() < 4:
        pytest.skip("needs 4 virtual devices")
    sys_cfg = SystemConfig(seed=0, device="cpu", mesh={"ep": 2, "dp": 2})
    mesh = build_mesh(sys_cfg, devices=jax.devices()[:4])
    params = llama.init_params(jax.random.PRNGKey(0), MOE_ARGS)
    tr = TrainingConfig(
        hyperparameters={"learning_rate": 1e-3},
        scheduler={"type": "cosine"},
        optimization={"optimizer": "shampoo"},
    )
    opt = build_optimizer(tr, 10)

    def loss_fn(p, b):
        return llama.loss_fn(p, b, MOE_ARGS)

    step, shardings = make_train_step(loss_fn, opt, mesh=mesh, params_like=params)
    state = jax.device_put(init_train_state(params, opt), shardings)
    state, metrics = step(state, _batch(bs=8))
    assert np.isfinite(float(metrics["loss"]))

    flat = jax.tree_util.tree_flatten_with_path(state["opt_state"])[0]
    stats = [(str(k), v) for k, v in flat if "stats_l" in str(k) and v.ndim == 3]
    assert stats, "no bank stats found in shampoo state"
    for k, v in stats:
        assert v.sharding.spec and v.sharding.spec[0] == "ep", f"{k}: {v.sharding.spec}"
