"""The plain reference against the program at a tiny size: forward logits
against ``models/llama.py::forward``, one optimizer step against the
program's optimizers. (The reference imports nothing of the program; this
test imports both.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import llama_dense as ref
from benchmark.reference import optimizers as ref_opt

CFG = {"hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
       "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 512,
       "rope_theta": 1e6, "rms_norm_eps": 1e-5}


def _args():
    from mlx_cuda_distributed_pretraining_tpu.models.llama import LlamaArgs

    return LlamaArgs(vocab_size=512, hidden_size=64, intermediate_size=128, num_layers=2,
                     num_heads=4, num_kv_heads=2, head_dim=16, max_position_embeddings=512,
                     rms_norm_eps=1e-5, rope_theta=1e6, tie_word_embeddings=False)


def test_weights_depend_on_seed_only_and_have_the_programs_layout():
    from mlx_cuda_distributed_pretraining_tpu.models import llama

    a, b, c = ref.init_params(7, CFG), ref.init_params(7, CFG), ref.init_params(2 ** 31 + 9, CFG)
    la, lb, lc = (jax.tree_util.tree_leaves(t) for t in (a, b, c))
    assert all(np.array_equal(x, y) for x, y in zip(la, lb))
    assert any(not np.array_equal(x, y) for x, y in zip(la, lc))
    theirs = jax.eval_shape(lambda: llama.init_params(jax.random.PRNGKey(0), _args()))
    assert jax.tree_util.tree_structure(a) == jax.tree_util.tree_structure(theirs)
    assert all(x.shape == y.shape and x.dtype == y.dtype
               for x, y in zip(la, jax.tree_util.tree_leaves(theirs)))


def test_forward_matches_the_program():
    from mlx_cuda_distributed_pretraining_tpu.models import llama

    params = ref.init_params(11, CFG)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 96), 0, 512)
    want = ref.logits_at(params, tokens, CFG)
    got, _ = llama.forward(params, tokens, _args())
    assert float(jnp.max(jnp.abs(got - want))) < 2e-5
    # the loss is the mean cross-entropy of those logits
    logp = jax.nn.log_softmax(want)
    targets = jnp.roll(tokens, -1, axis=1)
    nll = -jnp.take_along_axis(logp, targets[..., None], -1).mean()
    assert float(ref.loss(params, tokens, targets, CFG)) == pytest.approx(float(nll), rel=1e-5)


@pytest.mark.parametrize("precision", ["bfloat16", "fp8"])
def test_lower_precisions_differ_from_the_reference(precision):
    params = ref.init_params(11, CFG)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (1, 96), 0, 512)
    want = ref.logits_at(params, tokens, CFG)
    got = ref.logits_at(params, tokens, CFG, precision)
    err = float(jnp.max(jnp.abs(got - want)))
    assert 1e-4 < err < 0.5


@pytest.mark.parametrize("name,hp", [
    ("adafactor", {"name": "adafactor", "learning_rate": 1e-3, "gradient_clip": 1.0,
                   "decay_rate": 0.8, "clipping_threshold": 1.0, "weight_decay": 0.0}),
    ("adamw", {"name": "adamw", "learning_rate": 3e-4, "gradient_clip": 1.0,
               "betas": [0.9, 0.95], "eps": 1e-8, "weight_decay": 0.1}),
])
def test_optimizer_steps_match_the_program(name, hp):
    from types import SimpleNamespace

    from mlx_cuda_distributed_pretraining_tpu.optim.factory import build_optimizer
    from mlx_cuda_distributed_pretraining_tpu.optim.fused import fused_apply_of
    from mlx_cuda_distributed_pretraining_tpu.optim.base import apply_updates
    from benchmark.traffic_kinds.train_job import (program_first_gradient_norms,
                                                   program_first_gradient_profiles)

    cfg = dict(CFG, hidden_size=128, head_dim=32, intermediate_size=256)  # factored leaves
    params = ref.init_params(5, cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, 512)
    targets = jnp.roll(tokens, -1, axis=1)
    opt_cfg = {k: v for k, v in hp.items() if k not in ("name", "learning_rate", "gradient_clip",
                                                         "weight_decay")}
    training = SimpleNamespace(
        optimizer_name=name, weight_decay=hp["weight_decay"], gradient_clip=hp["gradient_clip"],
        learning_rate=hp["learning_rate"], hyperparameters={}, scheduler={"type": "constant"},
        optimization=dict(opt_cfg, optimizer=name))
    theirs = build_optimizer(training, 100)
    init, step, norms_of, profiles_of = ref_opt.get(name)
    p_ref, s_ref = params, init(params)
    p_prog, s_prog = params, theirs.init(params)
    for i in range(2):
        _, grads = ref.loss_and_grads(p_ref, tokens, targets, cfg)
        p_ref, s_ref = step(p_ref, grads, s_ref, hp)
        _, grads_p = ref.loss_and_grads(p_prog, tokens, targets, cfg)
        fused = fused_apply_of(theirs)
        if fused is not None:
            p_prog, s_prog = fused(grads_p, s_prog, p_prog)
        else:
            upd, s_prog = theirs.update(grads_p, s_prog, p_prog)
            p_prog = apply_updates(p_prog, upd)
        if i == 0:
            mine = jax.tree_util.tree_leaves(norms_of(s_ref, p_ref, hp))
            prog = jax.tree_util.tree_leaves(
                program_first_gradient_norms(name, s_prog, p_prog, hp))
            clipped = min(1.0, 1.0 / float(ref_opt._global_norm(grads)))
            true = [float(jnp.linalg.norm(g)) * clipped for g in jax.tree_util.tree_leaves(grads)]
            assert np.allclose(mine, true, rtol=1e-4) and np.allclose(prog, true, rtol=1e-4)
            # the second-moment profiles: same leaves, same layout, same numbers
            ours = profiles_of(s_ref, p_ref, hp)
            theirs_p = program_first_gradient_profiles(name, s_prog, hp)
            assert len(ours) == len(theirs_p) == len(true)
            for a, b in zip(ours, theirs_p):
                assert a.shape == b.shape and np.allclose(a, b, rtol=1e-4, atol=1e-30)
    for a, b in zip(jax.tree_util.tree_leaves(p_ref), jax.tree_util.tree_leaves(p_prog)):
        assert float(jnp.max(jnp.abs(a - b))) <= 1e-6 + 1e-4 * float(jnp.max(jnp.abs(a)))
