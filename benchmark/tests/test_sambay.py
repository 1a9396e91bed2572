"""The plain reference of ``architecture: sambay``, the count functions and the
readers this configuration brought, at a tiny size. (The reference imports
nothing of the program; the one test that compares them imports both.) Not
tier-1: ``tests/test_sambay.py`` holds the program to the reference there."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import run as harness
from benchmark.flops import flash_diff, sambay as flops, ssm_scan
from benchmark.reference import sambay as ref

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(ROOT, "benchmark/configs/phi4-mini-flash-l6.json")) as f:
    FULL = json.load(f)
with open(os.path.join(ROOT, "benchmark/rehearse_sambay.json")) as f:
    CFG = harness.merge_into(FULL, json.load(f)["config"])


def _batch(B=1, S=64):
    toks = jax.random.randint(jax.random.PRNGKey(0), (B, S + 1), 3, CFG["vocab_size"])
    return toks[:, :-1], toks[:, 1:]


def test_weights_depend_on_seed_only_and_follow_the_recipe():
    a, b, c = ref.init_params(7, CFG), ref.init_params(7, CFG), ref.init_params(2 ** 31 + 9, CFG)
    la, lb, lc = (jax.tree_util.tree_leaves(t) for t in (a, b, c))
    assert all(np.array_equal(x, y) for x, y in zip(la, lb))
    assert any(not np.array_equal(x, y) for x, y in zip(la, lc))
    assert sum(int(x.size) for x in la) == flops.total_params(CFG)
    ssm = a["layers"][0]["ssm"]
    assert np.allclose(np.exp(ssm["A_log"]), np.arange(1, CFG["ssm"]["d_state"] + 1))
    step = np.asarray(jax.nn.softplus(ssm["dt_proj"]["bias"]))
    assert 0.999e-3 <= step.min() and step.max() <= 0.1001 and step.std() > 0
    assert float(jnp.abs(a["layers"][1]["attention"]["wqkv"]["bias"]).max()) == 0.0
    assert 0.05 < float(jnp.std(a["layers"][1]["attention"]["lambda_q1"])) < 0.2


def test_the_scan_is_the_recurrence_written_out():
    """``selective_scan`` (blocks of steps under checkpoint) against a Python loop."""
    ks = jax.random.split(jax.random.PRNGKey(1), 6)
    Bt, S, Di, N = 1, 24, 8, 3
    c, B_, C_ = (jax.random.normal(k, s) for k, s in zip(ks, ((Bt, S, Di), (Bt, S, N), (Bt, S, N))))
    delta = jax.nn.softplus(jax.random.normal(ks[3], (Bt, S, Di)))
    A, D = -jnp.exp(jax.random.normal(ks[4], (Di, N))), jax.random.normal(ks[5], (Di,))
    h, want = np.zeros((Di, N)), []
    for t in range(S):
        h = np.exp(np.asarray(delta[0, t])[:, None] * np.asarray(A)) * h \
            + np.asarray(delta[0, t] * c[0, t])[:, None] * np.asarray(B_[0, t])[None, :]
        want.append(h @ np.asarray(C_[0, t]) + np.asarray(D) * np.asarray(c[0, t]))
    np.testing.assert_allclose(np.asarray(ref.selective_scan(c, delta, A, B_, C_, D))[0], want,
                               rtol=2e-5, atol=2e-6)


def test_the_loss_is_the_mean_cross_entropy_of_the_tied_logits():
    params = ref.init_params(11, CFG)
    inputs, targets = _batch()
    logp = jax.nn.log_softmax(ref.logits_at(params, inputs, CFG))
    nll = -jnp.take_along_axis(logp, targets[..., None], -1).mean()
    assert float(ref.loss(params, inputs, targets, CFG)) == pytest.approx(float(nll), rel=1e-5)
    (value,), grads = ref.loss_and_grads(params, inputs, targets, CFG)
    assert float(value) == pytest.approx(float(nll), rel=1e-5)
    # one table: the gather's gradient and the head's land on the same leaf
    assert "output" not in grads and float(jnp.linalg.norm(grads["tok_embeddings"]["weight"])) > 0


@pytest.mark.parametrize("precision", ["bfloat16", "fp8", "float32_bf16_scan"])
def test_lower_precisions_differ_from_the_reference(precision):
    params = ref.init_params(11, CFG)
    inputs, _ = _batch()
    want = ref.logits_at(params, inputs, CFG)
    got = ref.logits_at(params, inputs, CFG, precision)
    gap = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
    assert 1e-4 < gap < 0.2, gap


def test_the_program_follows_the_reference():
    from benchmark.traffic_kinds import train_job_sambay as kind
    from mlx_cuda_distributed_pretraining_tpu.config import Config
    from mlx_cuda_distributed_pretraining_tpu.models import sambay

    model = kind.arch.MODEL_SECTIONS["sambay"](CFG, {"attention_type": "simple"})
    args = sambay.SambaYArgs.from_config(Config.from_dict({"name": "t", "model": model}).model,
                                         CFG["vocab_size"])
    params = ref.init_params(11, CFG)
    inputs, _ = _batch(2)
    got, _ = sambay.forward(params, inputs, args)
    assert float(jnp.max(jnp.abs(got - ref.logits_at(params, inputs, CFG)))) < 2e-5


def test_count_functions():
    assert flops.total_params(FULL) == 1_145_237_632
    assert flops.total_params(FULL, published=True) == FULL["published"]["parameters"] == 3_852_562_944
    assert flops.layer_params(FULL) == {k: v for k, v in FULL["published"]["parameters_by_kind"].items()
                                        if k in "MSFGC"}
    S = 16384
    assert flops.train_flops_per_token(FULL, S) > 6 * flops.matmul_params(FULL)
    assert flash_diff.bwd_dq(1, 80, S, 64) / flash_diff.fwd(1, 80, S, 128) == pytest.approx(4 / 3)
    assert flash_diff.bwd_dkv(1, 80, S, 64) / flash_diff.fwd(1, 80, S, 128) == 2.0
    assert ssm_scan.BYTES_BY_KERNEL["ssm_scan_bwd"](1, S, 5120, 16) == ssm_scan.bwd_bytes(1, S, 5120, 16)
