"""Pipeline parallelism (pp axis, parallel/pipeline.py).

The reference has no pipeline parallelism (SURVEY.md §2.4) — this is new
TPU-native capability. Correctness bar: the GPipe schedule must reproduce
the single-device loss and gradients exactly (same math, token-weighted),
and the Trainer must train/checkpoint/resume through the pipeline path.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import mesh_utils
from jax.sharding import Mesh

from mlx_cuda_distributed_pretraining_tpu.models import llama
from mlx_cuda_distributed_pretraining_tpu.parallel import pipeline as pl

ARGS = llama.LlamaArgs(
    vocab_size=128, hidden_size=32, intermediate_size=64, num_layers=4,
    num_heads=2, num_kv_heads=2, head_dim=16, max_position_embeddings=64,
)


def _mesh(shape=(2, 2), names=("pp", "dp")):
    if jax.device_count() < int(np.prod(shape)):
        pytest.skip(f"needs {np.prod(shape)} devices")
    devs = mesh_utils.create_device_mesh(shape, devices=jax.devices()[: int(np.prod(shape))])
    return Mesh(devs, names)


def _batch(bs=8, seq=16, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.integers(1, 120, size=(bs, seq + 1)).astype(np.int32)
    return {
        "inputs": jnp.asarray(x[:, :-1]),
        "targets": jnp.asarray(x[:, 1:]),
        "mask": jnp.ones((bs, seq), jnp.float32),
    }


def test_stack_unstack_roundtrip():
    params = llama.init_params(jax.random.PRNGKey(0), ARGS)
    stacked = pl.stack_layers(params)
    assert stacked["layers"]["attention"]["wq"]["weight"].shape[0] == ARGS.num_layers
    back = pl.unstack_layers(stacked, ARGS.num_layers)
    for a, b in zip(
        jax.tree_util.tree_leaves(params), jax.tree_util.tree_leaves(back)
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_opt_state_stack_unstack_roundtrip():
    from mlx_cuda_distributed_pretraining_tpu.config import TrainingConfig
    from mlx_cuda_distributed_pretraining_tpu.optim import build_optimizer

    params = llama.init_params(jax.random.PRNGKey(0), ARGS)
    tr = TrainingConfig(
        hyperparameters={"learning_rate": 1e-3},
        scheduler={"type": "cosine"},
        optimization={"optimizer": "adamw"},
    )
    opt = build_optimizer(tr, 10)
    stacked_state = opt.init(pl.stack_layers(params))
    unstacked = pl.unstack_opt_state(stacked_state, ARGS.num_layers)
    # unstacked layout mirrors the canonical opt state (list-of-layers)
    canonical = opt.init(params)
    assert jax.tree_util.tree_structure(unstacked) == jax.tree_util.tree_structure(canonical)
    back = pl.stack_opt_state(unstacked, ARGS.num_layers)
    for a, b in zip(
        jax.tree_util.tree_leaves(stacked_state), jax.tree_util.tree_leaves(back)
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_pipeline_loss_matches_single_device():
    mesh = _mesh()
    params = llama.init_params(jax.random.PRNGKey(0), ARGS)
    batch = _batch()
    ref, ref_toks = llama.loss_fn(params, batch, ARGS)
    loss_fn = pl.make_pipeline_loss(ARGS, mesh, num_microbatches=4)
    got, toks = jax.jit(loss_fn)(pl.stack_layers(params), batch)
    assert float(toks) == float(ref_toks)
    np.testing.assert_allclose(float(got), float(ref), rtol=1e-5)


@pytest.mark.slow
def test_pipeline_grads_match_single_device():
    mesh = _mesh()
    params = llama.init_params(jax.random.PRNGKey(0), ARGS)
    batch = _batch()
    loss_fn = pl.make_pipeline_loss(ARGS, mesh, num_microbatches=2)
    g_ref = jax.grad(lambda p: llama.loss_fn(p, batch, ARGS)[0])(params)
    g_pp = jax.jit(jax.grad(lambda p: loss_fn(p, batch)[0]))(pl.stack_layers(params))
    g_pp = pl.unstack_layers(g_pp, ARGS.num_layers)
    ref_flat = {str(k): v for k, v in jax.tree_util.tree_flatten_with_path(g_ref)[0]}
    for k, v in jax.tree_util.tree_flatten_with_path(g_pp)[0]:
        np.testing.assert_allclose(
            np.asarray(ref_flat[str(k)]), np.asarray(v), atol=3e-5, err_msg=str(k)
        )


@pytest.mark.slow
def test_pipeline_remat_matches():
    mesh = _mesh()
    params = llama.init_params(jax.random.PRNGKey(0), ARGS)
    batch = _batch()
    plain = pl.make_pipeline_loss(ARGS, mesh, num_microbatches=2)
    remat = pl.make_pipeline_loss(ARGS, mesh, num_microbatches=2, remat="full")
    stacked = pl.stack_layers(params)
    g1 = jax.jit(jax.grad(lambda p: plain(p, batch)[0]))(stacked)
    g2 = jax.jit(jax.grad(lambda p: remat(p, batch)[0]))(stacked)
    for a, b in zip(jax.tree_util.tree_leaves(g1), jax.tree_util.tree_leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


@pytest.mark.slow
def test_pipeline_train_step_runs_and_shards():
    from mlx_cuda_distributed_pretraining_tpu.config import TrainingConfig
    from mlx_cuda_distributed_pretraining_tpu.optim import build_optimizer
    from mlx_cuda_distributed_pretraining_tpu.train.train_step import init_train_state

    mesh = _mesh()
    params = llama.init_params(jax.random.PRNGKey(0), ARGS)
    tr = TrainingConfig(
        hyperparameters={"learning_rate": 1e-3},
        scheduler={"type": "cosine"},
        optimization={"optimizer": "adamw"},
    )
    opt = build_optimizer(tr, 10)
    step, shardings = pl.make_pipeline_train_step(
        ARGS, opt, mesh, num_microbatches=4, params_like=params
    )
    state = jax.device_put(init_train_state(pl.stack_layers(params), opt), shardings)
    state, metrics = step(state, _batch())
    assert np.isfinite(float(metrics["loss"]))
    assert int(state["step"]) == 1
    spec = state["params"]["layers"]["attention"]["wq"]["weight"].sharding.spec
    assert spec and spec[0] == "pp", f"layer dim not pp-sharded: {spec}"


def test_pipeline_moe_loss_finite():
    import dataclasses

    mesh = _mesh()
    margs = dataclasses.replace(
        ARGS, num_local_experts=4, num_experts_per_tok=2, moe_group_size=8
    )
    from mlx_cuda_distributed_pretraining_tpu.parallel.context import use_mesh

    params = llama.init_params(jax.random.PRNGKey(0), margs)
    with use_mesh(None):  # shield from a base mesh left by Trainer tests (the expert layer asks for it)
        loss_fn = pl.make_pipeline_loss(margs, mesh, num_microbatches=2)
        loss, toks = jax.jit(loss_fn)(pl.stack_layers(params), _batch())
        assert np.isfinite(float(loss))
        # aux excluded for eval
        ev = pl.make_pipeline_loss(margs, mesh, num_microbatches=2, include_aux=False)
        l_eval, _ = jax.jit(ev)(pl.stack_layers(params), _batch())
        assert float(loss) > float(l_eval)


@pytest.mark.slow
def test_trainer_pipeline_end_to_end(tmp_path):
    """Full Trainer drive over a pp mesh: train, checkpoint, resume."""
    import json
    import yaml

    from mlx_cuda_distributed_pretraining_tpu.train.trainer import Trainer

    if jax.device_count() < 4:
        pytest.skip("needs 4 devices")
    data = tmp_path / "train.jsonl"
    with open(data, "w") as f:
        for i in range(64):
            f.write(json.dumps({"text": "hello world " * (3 + i % 5)}) + "\n")
    cfg = {
        "name": "pp-e2e",
        "overwrite": True,
        "data": {
            "input_file": str(data),
            "validation_file": str(data),
            "preprocessing": {"max_context_size": 32},
            "tokenizer": {"normal_vocab_size": 256,
                          "special_tokens": {"pad": "<pad>", "bos": "<bos>", "eos": "<eos>"}},
        },
        "model": {
            "architecture": "llama",
            "dimensions": {"hidden_size": 32, "intermediate_size": 64, "num_layers": 4},
            "attention": {"num_heads": 2, "num_kv_heads": 2, "head_dim": 16,
                          "max_position_embeddings": 32},
        },
        "training": {
            "hyperparameters": {"batch_size": 8, "learning_rate": 1e-3, "iters": 4},
            "scheduler": {"type": "cosine"},
            "optimization": {"optimizer": "adamw"},
        },
        "logging": {"steps": {"logging_interval": 2, "checkpoint_interval": 2,
                              "validation_interval": 0}},
        "system": {"seed": 0, "device": "cpu", "mesh": {"pp": 2, "dp": 2},
                   "pipeline_microbatches": 2},
    }
    cfg_path = tmp_path / "cfg.yaml"
    with open(cfg_path, "w") as f:
        yaml.safe_dump(cfg, f)
    t = Trainer(str(cfg_path), runs_root=str(tmp_path / "runs"))
    assert t.pipeline
    t.train()
    ckpt_dir = tmp_path / "runs" / "pp-e2e" / "checkpoints"
    assert (ckpt_dir / "step_final_model.safetensors").exists()

    # checkpoints are saved unstacked: loadable for plain inference
    from mlx_cuda_distributed_pretraining_tpu.train.trainer import load_trained

    params, margs, tok, _ = load_trained("pp-e2e", runs_root=str(tmp_path / "runs"))
    logits, _ = llama.forward(params, jnp.ones((1, 8), jnp.int32), margs)
    assert logits.shape[-1] == tok.vocab_size

    # resume from step 2 on the same pp mesh
    cfg["overwrite"] = False
    cfg["training"]["hyperparameters"]["iters"] = 6
    cfg["resume"] = {"checkpoint": "2"}
    with open(cfg_path, "w") as f:
        yaml.safe_dump(cfg, f)
    t2 = Trainer(str(cfg_path), runs_root=str(tmp_path / "runs"))
    assert t2.start_step == 2
    t2.train()
    assert int(t2.state["step"]) == 6

    # cross-layout resume: the pp checkpoint loads on a plain (no-pp) mesh
    # with optimizer moments intact (saved unstacked).
    cfg["system"] = {"seed": 0, "device": "cpu"}
    with open(cfg_path, "w") as f:
        yaml.safe_dump(cfg, f)
    t3 = Trainer(str(cfg_path), runs_root=str(tmp_path / "runs"))
    assert not t3.pipeline and t3.start_step == 2
    mu_leaves = [
        np.abs(np.asarray(x)).sum()
        for x in jax.tree_util.tree_leaves(t3.state["opt_state"])
    ]
    assert sum(mu_leaves) > 0, "optimizer moments were lost across layouts"


def test_pipeline_fused_ce_matches_unfused():
    """ce_chunk threads through the pipeline head: fused chunked CE on the
    last stage equals the full-logits pipeline loss and the single-device
    reference (incl. a chunk that does not divide the microbatch rows)."""
    mesh = _mesh()
    params = llama.init_params(jax.random.PRNGKey(0), ARGS)
    batch = _batch()
    ref, _ = llama.loss_fn(params, batch, ARGS, ce_chunk=0)
    stacked = pl.stack_layers(params)
    for chunk in (8, 24):  # mb rows = (8/4)*16 = 32; 24 pads
        loss_fn = pl.make_pipeline_loss(ARGS, mesh, num_microbatches=4, ce_chunk=chunk)
        got, _ = jax.jit(loss_fn)(stacked, batch)
        np.testing.assert_allclose(float(got), float(ref), rtol=1e-5)


def test_pipeline_z_loss_matches_single_device():
    """z_loss plumbs through the pipeline head: pp loss with z equals the
    non-pp loss_fn with the same weight (a pp>1 config must not silently
    drop the regularizer)."""
    mesh = _mesh()
    params = llama.init_params(jax.random.PRNGKey(0), ARGS)
    batch = _batch()
    w = 1e-2
    ref, _ = llama.loss_fn(params, batch, ARGS, z_loss_weight=w)
    loss_fn = pl.make_pipeline_loss(ARGS, mesh, num_microbatches=4,
                                    z_loss_weight=w)
    got, _ = jax.jit(loss_fn)(pl.stack_layers(params), batch)
    np.testing.assert_allclose(float(got), float(ref), rtol=1e-5)
    # and the z term is actually active (differs from the pure-CE loss)
    plain, _ = llama.loss_fn(params, batch, ARGS)
    assert float(got) > float(plain)


# --- zero-waste schedule: interleave, compute-skip, honest accounting -------


def test_interleave_stack_layout_and_roundtrip():
    """stacked[v, j] under interleave=V is global layer v*(L/V)+j (round-robin
    circuits over contiguous chunks), and unstack inverts it exactly."""
    params = llama.init_params(jax.random.PRNGKey(0), ARGS)
    V, Lv = 2, ARGS.num_layers // 2
    stacked = pl.stack_layers(params, interleave=V)
    wq = stacked["layers"]["attention"]["wq"]["weight"]
    assert wq.shape[:2] == (V, Lv)
    flat = pl.stack_layers(params)["layers"]["attention"]["wq"]["weight"]
    for v in range(V):
        for j in range(Lv):
            np.testing.assert_array_equal(
                np.asarray(wq[v, j]), np.asarray(flat[v * Lv + j]))
    back = pl.unstack_layers(stacked, ARGS.num_layers, interleave=V)
    for a, b in zip(
        jax.tree_util.tree_leaves(params), jax.tree_util.tree_leaves(back)
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_interleave_opt_state_roundtrip():
    from mlx_cuda_distributed_pretraining_tpu.config import TrainingConfig
    from mlx_cuda_distributed_pretraining_tpu.optim import build_optimizer

    params = llama.init_params(jax.random.PRNGKey(0), ARGS)
    tr = TrainingConfig(
        hyperparameters={"learning_rate": 1e-3},
        scheduler={"type": "cosine"},
        optimization={"optimizer": "adamw"},
    )
    opt = build_optimizer(tr, 10)
    stacked_state = opt.init(pl.stack_layers(params, interleave=2))
    unstacked = pl.unstack_opt_state(stacked_state, ARGS.num_layers, interleave=2)
    assert jax.tree_util.tree_structure(unstacked) == jax.tree_util.tree_structure(
        opt.init(params))
    back = pl.stack_opt_state(unstacked, ARGS.num_layers, interleave=2)
    for a, b in zip(
        jax.tree_util.tree_leaves(stacked_state), jax.tree_util.tree_leaves(back)
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("interleave", [1, 2])
def test_interleave_loss_matches_single_device(interleave):
    mesh = _mesh()
    params = llama.init_params(jax.random.PRNGKey(0), ARGS)
    batch = _batch()
    ref, ref_toks = llama.loss_fn(params, batch, ARGS)
    loss_fn = pl.make_pipeline_loss(ARGS, mesh, num_microbatches=4,
                                    interleave=interleave)
    got, toks = jax.jit(loss_fn)(
        pl.stack_layers(params, interleave=interleave), batch)
    assert float(toks) == float(ref_toks)
    np.testing.assert_allclose(float(got), float(ref), rtol=1e-5)


@pytest.mark.slow
@pytest.mark.parametrize("interleave,remat,ce_chunk", [
    (2, None, -1),     # plain interleaved schedule
    (2, "full", -1),   # + remat through the virtual-stage slabs
    (2, None, 8),      # + fused chunked CE head on the last stage
    (1, None, 8),      # fused head without interleave (skip-path coverage)
])
def test_interleave_grads_match_single_device(interleave, remat, ce_chunk):
    """Interleaved circular schedule is gradient-exact vs the single-device
    reference, including the remat arm and the fused-CE head."""
    mesh = _mesh()
    params = llama.init_params(jax.random.PRNGKey(0), ARGS)
    batch = _batch()
    loss_fn = pl.make_pipeline_loss(
        ARGS, mesh, num_microbatches=4, interleave=interleave,
        remat=remat, ce_chunk=ce_chunk)
    g_ref = jax.grad(lambda p: llama.loss_fn(p, batch, ARGS)[0])(params)
    g_pp = jax.jit(jax.grad(lambda p: loss_fn(p, batch)[0]))(
        pl.stack_layers(params, interleave=interleave))
    g_pp = pl.unstack_layers(g_pp, ARGS.num_layers, interleave=interleave)
    ref_flat = {str(k): v for k, v in jax.tree_util.tree_flatten_with_path(g_ref)[0]}
    for k, v in jax.tree_util.tree_flatten_with_path(g_pp)[0]:
        np.testing.assert_allclose(
            np.asarray(ref_flat[str(k)]), np.asarray(v), atol=3e-5, err_msg=str(k)
        )


@pytest.mark.parametrize("interleave", [1, 2])
def test_compute_skip_bit_identical(interleave):
    """Skipping bubble ticks changes WHAT runs, not the math: the loss with
    compute_skip on is bitwise equal to the all-ticks schedule."""
    mesh = _mesh()
    params = llama.init_params(jax.random.PRNGKey(0), ARGS)
    batch = _batch()
    stacked = pl.stack_layers(params, interleave=interleave)
    on = pl.make_pipeline_loss(ARGS, mesh, num_microbatches=4,
                               interleave=interleave, compute_skip=True)
    off = pl.make_pipeline_loss(ARGS, mesh, num_microbatches=4,
                                interleave=interleave, compute_skip=False)
    l_on, t_on = jax.jit(on)(stacked, batch)
    l_off, t_off = jax.jit(off)(stacked, batch)
    assert float(l_on) == float(l_off), "compute-skip changed the loss"
    assert float(t_on) == float(t_off)


@pytest.mark.parametrize("interleave,compute_skip", [
    (1, True), (1, False), (2, True), (2, False),
])
def test_compute_skip_slab_application_count(interleave, compute_skip):
    """The schedule really skips bubble ticks: slab applications summed
    over stages drop from P*(V*M + P-1) to P*(V*M) with compute_skip on
    (an int32 carried through the ticks, incremented inside the cond's
    work branch, returned in the loss aux)."""
    mesh = _mesh((2, 1))
    params = llama.init_params(jax.random.PRNGKey(0), ARGS)
    batch = _batch()
    M, P, V = 4, 2, interleave
    loss_fn = pl.make_pipeline_loss(ARGS, mesh, num_microbatches=M,
                                    interleave=V, compute_skip=compute_skip,
                                    with_slab_count=True)
    _, (_, slabs) = jax.jit(loss_fn)(pl.stack_layers(params, interleave=V), batch)
    n = [int(slabs)]
    expected = P * (V * M) if compute_skip else P * (V * M + P - 1)
    assert n[0] == expected, f"slab applications {n[0]} != {expected}"


@pytest.mark.slow
@pytest.mark.parametrize("interleave", [1, 2])
def test_pipeline_moe_stats_parity(interleave):
    """MoE routing stats thread through the pipeline loss aux: same grouped
    load / dropped counts as the single-device loss_fn taps."""
    import dataclasses

    from mlx_cuda_distributed_pretraining_tpu.parallel.context import use_mesh

    mesh = _mesh()
    margs = dataclasses.replace(
        ARGS, num_local_experts=4, num_experts_per_tok=2, moe_group_size=8)
    params = llama.init_params(jax.random.PRNGKey(0), margs)
    batch = _batch()
    with use_mesh(None):  # shield from a base mesh left by Trainer tests
        ref_loss, (ref_toks, ref_stats) = llama.loss_fn(
            params, batch, margs, with_moe_stats=True)
        loss_fn = pl.make_pipeline_loss(margs, mesh, num_microbatches=4,
                                        interleave=interleave,
                                        with_moe_stats=True)
        loss, (toks, stats) = jax.jit(loss_fn)(
            pl.stack_layers(params, interleave=interleave), batch)
    assert float(toks) == float(ref_toks)
    np.testing.assert_allclose(float(loss), float(ref_loss), atol=3e-4)
    assert sorted(stats) == sorted(ref_stats)
    np.testing.assert_allclose(
        np.asarray(stats["moe_load"]), np.asarray(ref_stats["moe_load"]),
        rtol=1e-5)
    np.testing.assert_allclose(
        float(np.asarray(stats["moe_dropped"]).sum()),
        float(np.asarray(ref_stats["moe_dropped"]).sum()))


def test_bubble_accounting():
    from mlx_cuda_distributed_pretraining_tpu.obs.flops import (
        pipeline_bubble_frac, pipeline_executed_flops_ratio)

    assert pipeline_bubble_frac(4, 8) == pytest.approx(3 / 11)
    assert pipeline_bubble_frac(4, 8, interleave=2) == pytest.approx(3 / 19)
    assert pipeline_bubble_frac(1, 8) == 0.0
    assert pipeline_executed_flops_ratio(4, 8, compute_skip=True) == 1.0
    assert pipeline_executed_flops_ratio(4, 8, compute_skip=False) == pytest.approx(11 / 8)
    assert pipeline_executed_flops_ratio(4, 8, interleave=2, compute_skip=False) == pytest.approx(19 / 16)


@pytest.mark.parametrize("interleave", [1, 2])
def test_load_params_stacked_pp_placement(interleave):
    """An unstacked (fsdp-layout) checkpoint loads straight into the stacked
    pp-sharded placement: correct specs, exact values, and a per-device byte
    budget — no device ever holds a full replica of the stacked tree."""
    import tempfile

    from mlx_cuda_distributed_pretraining_tpu.checkpoint.manager import (
        CheckpointIntegrityError, CheckpointManager)
    from mlx_cuda_distributed_pretraining_tpu.checkpoint.safetensors_io import (
        save_safetensors)
    from mlx_cuda_distributed_pretraining_tpu.utils.tree import flatten_dict

    mesh = _mesh((2, 2), ("pp", "fsdp"))
    params = llama.init_params(jax.random.PRNGKey(0), ARGS)
    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "model.safetensors")
        save_safetensors(
            path, {k: np.asarray(v) for k, v in flatten_dict(params).items()})
        placed = CheckpointManager.load_params_stacked(
            path, mesh, ARGS.num_layers, interleave=interleave)
    want = pl.stack_layers(params, interleave=interleave)
    n_dev = mesh.devices.size
    for k, v in flatten_dict(placed).items():
        np.testing.assert_array_equal(
            np.asarray(v), np.asarray(flatten_dict(want)[k]), err_msg=k)
        spec = v.sharding.spec
        if k.startswith("layers."):
            # layer dim pp-sharded: [V, L/V, ...] circuits lead, else [L, ...]
            assert spec[1 if interleave > 1 else 0] == "pp", (k, spec)
            sharded = int(np.prod([
                mesh.shape[a] for a in jax.tree_util.tree_leaves(tuple(spec))
                if isinstance(a, str)]))
            for s in v.addressable_shards:
                assert s.data.nbytes == v.nbytes // sharded, (k, spec)
            assert sum(s.data.nbytes for s in v.addressable_shards) \
                == v.nbytes * n_dev // sharded


def test_load_params_stacked_rejects_mismatch():
    """A checkpoint whose per-layer dtype does not match the live tree fails
    loudly at load time (not as a runtime donation error mid-step)."""
    import tempfile

    from mlx_cuda_distributed_pretraining_tpu.checkpoint.manager import (
        CheckpointIntegrityError, CheckpointManager)
    from mlx_cuda_distributed_pretraining_tpu.checkpoint.safetensors_io import (
        save_safetensors)
    from mlx_cuda_distributed_pretraining_tpu.utils.tree import flatten_dict

    mesh = _mesh((2, 2), ("pp", "fsdp"))
    params = llama.init_params(jax.random.PRNGKey(0), ARGS)
    like = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16), pl.stack_layers(params))
    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "model.safetensors")
        save_safetensors(
            path, {k: np.asarray(v) for k, v in flatten_dict(params).items()})
        with pytest.raises(CheckpointIntegrityError, match="re-materialize"):
            CheckpointManager.load_params_stacked(
                path, mesh, ARGS.num_layers, like_stacked=like)


@pytest.mark.slow
def test_fsdp_checkpoint_resumes_on_pp_mesh(tmp_path):
    """Train+checkpoint on a dp x fsdp mesh, resume the SAME run on a
    pp x dp mesh with interleave: the stacked params must come up pp-sharded
    (per-device live bytes == leaf/pp, never a full stacked replica) with
    values identical to the saved step. Runs in a subprocess so the fsdp and
    pp trainers each get a clean 4-device runtime."""
    import sys

    from conftest import spawn_with_devices

    worker = tmp_path / "worker.py"
    worker.write_text(PP_RESUME_WORKER)
    proc = spawn_with_devices([sys.executable, str(worker), str(tmp_path)], 4)
    out, _ = proc.communicate(timeout=600)
    assert proc.returncode == 0, out
    assert "PP_RESUME_OK" in out, out


PP_RESUME_WORKER = """
import json
import sys

import numpy as np
import yaml

import jax

from mlx_cuda_distributed_pretraining_tpu.parallel import pipeline as pl
from mlx_cuda_distributed_pretraining_tpu.train.trainer import Trainer
from mlx_cuda_distributed_pretraining_tpu.utils.tree import flatten_dict

tmp = sys.argv[1]
assert jax.device_count() == 4, jax.devices()

data = tmp + "/train.jsonl"
with open(data, "w") as f:
    for i in range(64):
        f.write(json.dumps({"text": "hello world " * (3 + i % 5)}) + "\\n")

cfg = {
    "name": "xresume",
    "overwrite": True,
    "data": {
        "input_file": data,
        "validation_file": data,
        "preprocessing": {"max_context_size": 32},
        "tokenizer": {"normal_vocab_size": 256,
                      "special_tokens": {"pad": "<pad>", "bos": "<bos>",
                                         "eos": "<eos>"}},
    },
    "model": {
        "architecture": "llama",
        "dimensions": {"hidden_size": 32, "intermediate_size": 64,
                       "num_layers": 4},
        "attention": {"num_heads": 2, "num_kv_heads": 2, "head_dim": 16,
                      "max_position_embeddings": 32},
    },
    "training": {
        "hyperparameters": {"batch_size": 8, "learning_rate": 1e-3, "iters": 2},
        "scheduler": {"type": "cosine"},
        "optimization": {"optimizer": "adamw"},
    },
    "logging": {"steps": {"logging_interval": 2, "checkpoint_interval": 2,
                          "validation_interval": 0}},
    "system": {"seed": 0, "device": "cpu", "mesh": {"dp": 2, "fsdp": 2}},
}
cfg_path = tmp + "/cfg.yaml"
with open(cfg_path, "w") as f:
    yaml.safe_dump(cfg, f)
t1 = Trainer(cfg_path, runs_root=tmp + "/runs")
assert not t1.pipeline
t1.train()
saved = {k: np.asarray(v) for k, v in flatten_dict(t1._host_params()).items()}
del t1

cfg["overwrite"] = False
cfg["training"]["hyperparameters"]["iters"] = 4
cfg["resume"] = {"checkpoint": "2"}
cfg["system"] = {"seed": 0, "device": "cpu", "mesh": {"pp": 2, "dp": 2},
                 "pipeline_microbatches": 2, "pipeline_interleave": 2}
with open(cfg_path, "w") as f:
    yaml.safe_dump(cfg, f)
t2 = Trainer(cfg_path, runs_root=tmp + "/runs")
assert t2.pipeline and t2.pipeline_interleave == 2
assert t2.start_step == 2, t2.start_step

# per-device live-byte budget: every stacked layer leaf is pp-sharded --
# each device holds exactly leaf/pp bytes, no full stacked replica anywhere
pp = 2
layers = flatten_dict(t2.state["params"]["layers"])
assert layers
for k, v in layers.items():
    for s in v.addressable_shards:
        assert s.data.nbytes == v.nbytes // pp, (k, s.data.nbytes, v.nbytes)

# values identical to the step-2 checkpoint (no lossy round trip)
back = flatten_dict(
    pl.unstack_layers(jax.device_get(t2.state["params"]),
                      4, interleave=2))
for k, want in saved.items():
    np.testing.assert_array_equal(np.asarray(back[k]), want, err_msg=k)

# and the resumed pipeline actually trains on
t2.train()
assert int(t2.state["step"]) == 4

print("PP_RESUME_OK", json.dumps({"leaves": len(layers)}))
"""
