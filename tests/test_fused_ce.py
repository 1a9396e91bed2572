"""``ops/fused_ce.py``: the head's gradients from the forward chunk walk.

``fused_cross_entropy`` is a ``jax.custom_vjp`` whose differentiated forward
computes ``dX``, ``dW`` and ``dbias`` chunk by chunk; here its loss and
gradients are held to autodiff of the materialized-logits path over the
head's forms (tied or untied, bias, ``logit_scale``, z-loss, a chunk that
pads, fp32 or bf16 compute), with rows masked out and the loss divided by a
token count so that the cotangent reaching the function is not 1.
"""

import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mlx_cuda_distributed_pretraining_tpu.ops import fused_ce

B, S, D, V = 2, 24, 16, 96  # 48 rows: chunk 16 divides them, chunk 20 pads


def _inputs(tied, bias, dtype):
    rng = np.random.default_rng(7)
    params = {"h": jnp.asarray(rng.normal(size=(B, S, D)), jnp.float32),
              # the program's shapes: a tied table is [V, D], an untied head [D, V]
              "w": jnp.asarray(0.3 * rng.normal(size=(V, D) if tied else (D, V)), jnp.float32)}
    if bias:
        params["b"] = jnp.asarray(0.1 * rng.normal(size=(V,)), jnp.float32)
    tokens = jnp.asarray(rng.integers(0, V, size=(B, S)), jnp.int32)
    targets = jnp.asarray(rng.integers(0, V, size=(B, S)), jnp.int32)
    mask = jnp.asarray(rng.uniform(size=(B, S)) > 0.25, jnp.float32)
    return params, tokens, targets, mask


def _head_operands(params, tokens, tied, dtype):
    hidden = params["h"]
    if tied:  # the table also feeds the input side: its gradient has two sources
        hidden = hidden + params["w"][tokens]
        w_vd = params["w"]
    else:
        w_vd = params["w"].T
    return hidden.astype(dtype), w_vd.astype(dtype)


def _materialized(hidden, w_vd, targets, mask, bias_v, logit_scale, z_weight):
    logits = jax.lax.dot_general(hidden, w_vd, (((2,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    if bias_v is not None:
        logits = logits + bias_v.astype(jnp.float32)
    if logit_scale:
        logits = logits * logit_scale
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.sum((logz - gold + z_weight * jnp.square(logz)) * mask)


CASES = list(itertools.product(("tied", "untied"), ("bias", "nobias"), (None, 0.5),
                               (0.0, 1e-2), (16, 20), ("float32", "bfloat16")))


@pytest.mark.parametrize(
    "tied,bias,logit_scale,z_weight,chunk,dtype", CASES,
    ids=["-".join(map(str, c)) for c in CASES])
def test_value_and_grad_match_materialized_logits(tied, bias, logit_scale, z_weight,
                                                  chunk, dtype):
    tied, bias, dtype = tied == "tied", bias == "bias", jnp.dtype(dtype)
    params, tokens, targets, mask = _inputs(tied, bias, dtype)
    count = jnp.maximum(mask.sum(), 1.0)

    def loss(p, fused):
        hidden, w_vd = _head_operands(p, tokens, tied, dtype)
        if fused:
            total = fused_ce.fused_cross_entropy(
                hidden, w_vd, targets, mask, bias_v=p.get("b"), logit_scale=logit_scale,
                chunk=chunk, z_weight=z_weight)
        else:
            total = _materialized(hidden, w_vd, targets, mask, p.get("b"), logit_scale,
                                  z_weight)
        return total / count  # the cotangent the function sees is 1 / count

    before = fused_ce.plan_counts()
    l1, g1 = jax.value_and_grad(lambda p: loss(p, True))(params)
    after = fused_ce.plan_counts()
    assert (after["grad_in_forward"], after["forward_only"]) == (
        before["grad_in_forward"] + 1, before["forward_only"])
    l0, g0 = jax.value_and_grad(lambda p: loss(p, False))(params)

    np.testing.assert_allclose(float(l1), float(l0), rtol=2e-6)
    # fp32: the same sums in another order. bf16: the fused path hands the
    # MXU d rounded to bf16, as a TPU does with autodiff's fp32 d; the CPU
    # reference multiplies the fp32 d unrounded (2^-9 an element).
    tol = 2e-6 if dtype == jnp.float32 else 6e-3
    for name in g0:
        scale = float(jnp.max(jnp.abs(g0[name])))
        np.testing.assert_allclose(np.asarray(g1[name]), np.asarray(g0[name]),
                                   atol=tol * scale, rtol=0, err_msg=name)

    # a masked row gets no gradient at all, not a small one
    if not tied:
        dead = np.asarray(mask) == 0
        assert dead.any() and not np.asarray(g1["h"])[dead].any()


def test_undifferentiated_call_walks_the_forward_only():
    params, tokens, targets, mask = _inputs(False, True, jnp.float32)
    hidden, w_vd = _head_operands(params, tokens, False, jnp.float32)
    before = fused_ce.plan_counts()
    got = jax.jit(lambda h, w, b: fused_ce.fused_cross_entropy(
        h, w, targets, mask, bias_v=b, chunk=20, z_weight=1e-2))(hidden, w_vd, params["b"])
    after = fused_ce.plan_counts()
    assert (after["grad_in_forward"], after["forward_only"]) == (
        before["grad_in_forward"], before["forward_only"] + 1)
    want = _materialized(hidden, w_vd, targets, mask, params["b"], None, 1e-2)
    np.testing.assert_allclose(float(got), float(want), rtol=2e-6)


def test_forward_mode_is_refused():
    """Only reverse mode is defined (the docstrings say so): a jvp through
    the function raises instead of returning a wrong tangent."""
    params, tokens, targets, mask = _inputs(False, False, jnp.float32)
    hidden, w_vd = _head_operands(params, tokens, False, jnp.float32)
    with pytest.raises(TypeError, match="custom_vjp"):
        jax.jvp(lambda h: fused_ce.fused_cross_entropy(h, w_vd, targets, mask, chunk=16),
                (hidden,), (jnp.ones_like(hidden),))


def test_under_checkpoint_gradients_are_the_same():
    """An enclosing ``jax.checkpoint`` (a pipeline's head) runs the forward
    walk first and the gradient walk in the backward pass: both are traced,
    and the gradients are those of the bare call."""
    params, tokens, targets, mask = _inputs(False, False, jnp.float32)

    def loss(p, wrap):
        hidden, w_vd = _head_operands(p, tokens, False, jnp.float32)
        fn = lambda h, w: fused_ce.fused_cross_entropy(h, w, targets, mask, chunk=16)  # noqa: E731
        return (jax.checkpoint(fn) if wrap else fn)(hidden, w_vd) / 7.0

    g0 = jax.grad(lambda p: loss(p, False))(params)
    before = fused_ce.plan_counts()
    g1 = jax.grad(lambda p: loss(p, True))(params)
    after = fused_ce.plan_counts()
    assert after["forward_only"] == before["forward_only"] + 1
    assert after["grad_in_forward"] == before["grad_in_forward"] + 1
    for name in g0:
        np.testing.assert_array_equal(np.asarray(g1[name]), np.asarray(g0[name]))
