"""Multi-device tests on the virtual 8-CPU mesh (SURVEY.md §4 item d):
DP gradient psum correctness, TP/FSDP sharding, ZeRO-1 state sharding."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mlx_cuda_distributed_pretraining_tpu.config import SystemConfig, TrainingConfig
from mlx_cuda_distributed_pretraining_tpu.models import llama
from mlx_cuda_distributed_pretraining_tpu.models.llama import LlamaArgs
from mlx_cuda_distributed_pretraining_tpu.optim import build_optimizer
from mlx_cuda_distributed_pretraining_tpu.parallel import build_mesh
from mlx_cuda_distributed_pretraining_tpu.parallel.mesh import mesh_axis_sizes
from mlx_cuda_distributed_pretraining_tpu.train.train_step import (
    init_train_state,
    make_train_step,
)

ARGS = LlamaArgs(
    vocab_size=64, hidden_size=32, intermediate_size=64, num_layers=2,
    num_heads=4, num_kv_heads=2, head_dim=8, max_position_embeddings=64,
)


def _setup(mesh_cfg, zero=0, seed=0):
    sys_cfg = SystemConfig(seed=seed, device="cpu", mesh=mesh_cfg,
                           zero_optimization_level=zero)
    mesh = build_mesh(sys_cfg)
    tr_cfg = TrainingConfig(
        hyperparameters={"learning_rate": 1e-2, "gradient_clip": 1.0},
        scheduler={"type": "constant"},
        optimization={"optimizer": "adamw"},
    )
    opt = build_optimizer(tr_cfg, 100)
    params = llama.init_params(jax.random.PRNGKey(seed), ARGS)

    def loss_fn(params, batch):
        return llama.loss_fn(params, batch, ARGS)

    step, shardings = make_train_step(loss_fn, opt, mesh=mesh, zero_level=zero, params_like=params)
    state = jax.device_put(init_train_state(params, opt), shardings)
    return mesh, step, state, shardings


def _batch(bs=8, seq=16, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.integers(1, 60, size=(bs, seq + 1)).astype(np.int32)
    return {
        "inputs": jnp.asarray(x[:, :-1]),
        "targets": jnp.asarray(x[:, 1:]),
        "mask": jnp.ones((bs, seq), jnp.float32),
    }


def test_mesh_axis_sizes():
    sizes = mesh_axis_sizes(SystemConfig(seed=0, device="cpu", mesh={"dp": -1, "tp": 2}), 8)
    assert sizes["dp"] == 4 and sizes["tp"] == 2
    # explicit sub-device mesh is allowed (prefix of devices)
    assert mesh_axis_sizes(SystemConfig(seed=0, device="cpu", mesh={"dp": 3}), 8)["dp"] == 3
    with pytest.raises(ValueError):
        mesh_axis_sizes(SystemConfig(seed=0, device="cpu", mesh={"dp": 16}), 8)
    with pytest.raises(ValueError):  # -1 with non-divisible fixed axis
        mesh_axis_sizes(SystemConfig(seed=0, device="cpu", mesh={"dp": -1, "tp": 3}), 8)


@pytest.mark.slow
def test_dp_matches_single_device():
    """8-way DP step == single-device step on the same global batch."""
    batch = _batch()
    mesh, step, state, _ = _setup({"dp": 8})
    new_state, metrics = step(state, batch)

    # single-device
    params = llama.init_params(jax.random.PRNGKey(0), ARGS)
    tr_cfg = TrainingConfig(
        hyperparameters={"learning_rate": 1e-2, "gradient_clip": 1.0},
        scheduler={"type": "constant"},
        optimization={"optimizer": "adamw"},
    )
    opt = build_optimizer(tr_cfg, 100)
    sstep, _ = make_train_step(lambda p, b: llama.loss_fn(p, b, ARGS), opt)
    sstate = init_train_state(params, opt)
    ref_state, ref_metrics = sstep(sstate, batch)

    np.testing.assert_allclose(float(metrics["loss"]), float(ref_metrics["loss"]), rtol=1e-5)
    a = np.asarray(new_state["params"]["layers"][0]["attention"]["wq"]["weight"])
    b = np.asarray(ref_state["params"]["layers"][0]["attention"]["wq"]["weight"])
    np.testing.assert_allclose(a, b, atol=1e-5)


@pytest.mark.parametrize("mesh_cfg", [{"dp": 2, "tp": 4}, {"dp": 2, "fsdp": 2, "tp": 2}])
@pytest.mark.slow
def test_tp_fsdp_matches_single_device(mesh_cfg):
    batch = _batch()
    mesh, step, state, shardings = _setup(mesh_cfg)
    new_state, metrics = step(state, batch)

    params = llama.init_params(jax.random.PRNGKey(0), ARGS)
    tr_cfg = TrainingConfig(
        hyperparameters={"learning_rate": 1e-2, "gradient_clip": 1.0},
        scheduler={"type": "constant"},
        optimization={"optimizer": "adamw"},
    )
    opt = build_optimizer(tr_cfg, 100)
    sstep, _ = make_train_step(lambda p, b: llama.loss_fn(p, b, ARGS), opt)
    ref_state, ref_metrics = sstep(init_train_state(params, opt), batch)
    np.testing.assert_allclose(float(metrics["loss"]), float(ref_metrics["loss"]), rtol=1e-4)

    # TP actually shards: wq weight [32, 32] over tp on dim 1
    wq_shard = new_state["params"]["layers"][0]["attention"]["wq"]["weight"].sharding
    tp = mesh.shape["tp"]
    assert wq_shard.shard_shape((32, 32))[1] == 32 // tp


def test_zero1_shards_optimizer_state():
    mesh, step, state, shardings = _setup({"dp": 8}, zero=1)
    new_state, _ = step(state, _batch())
    # adam mu for the embedding [64, 32]: param replicated (dp only mesh),
    # but optimizer state sharded over dp on dim 0
    mu = None
    # chain state: [clip:{}, adam:{mu,nu}, wd:{}, schedule:{count}] -> find mu
    for s in new_state["opt_state"]:
        if isinstance(s, dict) and "mu" in s:
            mu = s["mu"]["tok_embeddings"]["weight"]
    assert mu is not None
    assert mu.sharding.shard_shape((64, 32))[0] == 64 // 8
    # params stay replicated
    emb = new_state["params"]["tok_embeddings"]["weight"]
    assert emb.sharding.shard_shape((64, 32)) == (64, 32)


def test_sharding_no_shape_collision():
    """wq [D, H*Dh] and wo [H*Dh, D] have the same shape when H*Dh == D;
    their optimizer state must still get the matching (not transposed)
    spec — regression for suffix-vs-shape matching."""
    args = LlamaArgs(
        vocab_size=64, hidden_size=32, intermediate_size=64, num_layers=1,
        num_heads=4, num_kv_heads=4, head_dim=8, max_position_embeddings=64,
    )  # H*Dh = 32 = D
    sys_cfg = SystemConfig(seed=0, device="cpu", mesh={"fsdp": 2, "tp": 4})
    mesh = build_mesh(sys_cfg)
    tr_cfg = TrainingConfig(hyperparameters={"learning_rate": 1e-2},
                            optimization={"optimizer": "adamw"})
    opt = build_optimizer(tr_cfg, 10)
    params = llama.init_params(jax.random.PRNGKey(0), args)
    _, shardings = make_train_step(
        lambda p, b: llama.loss_fn(p, b, args), opt, mesh=mesh, params_like=params)

    def find_mu(tree):
        for s in tree:
            if isinstance(s, dict) and "mu" in s:
                return s["mu"]

    mu = find_mu(shardings["opt_state"])
    wq_param = shardings["params"]["layers"][0]["attention"]["wq"]["weight"]
    wo_param = shardings["params"]["layers"][0]["attention"]["wo"]["weight"]
    wq_mu = mu["layers"][0]["attention"]["wq"]["weight"]
    wo_mu = mu["layers"][0]["attention"]["wo"]["weight"]
    assert wq_mu.spec == wq_param.spec
    assert wo_mu.spec == wo_param.spec
    assert wq_param.spec != wo_param.spec  # transposed rules really differ


@pytest.mark.slow
def test_sp_fused_ce_matches_dense():
    """Sequence-sharded fused CE (ops/fused_ce.py::fused_cross_entropy_sp,
    auto-routed by llama.loss_fn on sp meshes with tp == 1): loss AND
    grads match the single-device unfused reference on a dp x sp mesh —
    the shard_map path just distributes the row chunks."""
    import dataclasses

    import numpy as np

    from mlx_cuda_distributed_pretraining_tpu.config import SystemConfig
    from mlx_cuda_distributed_pretraining_tpu.models import llama
    from mlx_cuda_distributed_pretraining_tpu.parallel import build_mesh
    from mlx_cuda_distributed_pretraining_tpu.parallel.context import set_mesh

    mesh = build_mesh(SystemConfig(seed=0, device="cpu",
                                   mesh={"dp": 2, "sp": 4}))
    args = llama.LlamaArgs(
        vocab_size=128, hidden_size=32, intermediate_size=64, num_layers=2,
        num_heads=2, num_kv_heads=2, head_dim=16,
        max_position_embeddings=256, attention_type="ring")
    params = llama.init_params(jax.random.PRNGKey(0), args)
    rng = np.random.default_rng(0)
    x = rng.integers(1, 120, size=(4, 257)).astype(np.int32)
    b = {"inputs": jnp.asarray(x[:, :-1]), "targets": jnp.asarray(x[:, 1:]),
         "mask": jnp.ones((4, 256), jnp.float32)}

    set_mesh(None)
    dargs = dataclasses.replace(args, attention_type="simple")
    dense, dg = jax.value_and_grad(
        lambda p: llama.loss_fn(p, b, dargs, ce_chunk=0)[0])(params)

    set_mesh(mesh)
    try:
        loss, g = jax.jit(jax.value_and_grad(
            lambda p: llama.loss_fn(p, b, args, ce_chunk=64)[0]))(params)
        assert abs(float(loss) - float(dense)) < 1e-4
        mx = max(float(jnp.max(jnp.abs(a - b2))) for a, b2 in
                 zip(jax.tree_util.tree_leaves(dg),
                     jax.tree_util.tree_leaves(g)))
        assert mx < 1e-6, mx
    finally:
        set_mesh(None)
