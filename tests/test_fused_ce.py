"""``ops/fused_ce.py``: the head's gradients from the forward chunk walk.

``fused_cross_entropy`` is a ``jax.custom_vjp`` whose differentiated forward
computes ``dX``, ``dW`` and ``dbias`` chunk by chunk; here its loss and
gradients are held to autodiff of the materialized-logits path over the
head's forms (tied or untied, bias, ``logit_scale``, z-loss, a chunk that
pads, fp32 or bf16 compute), with rows masked out and the loss divided by a
token count so that the cotangent reaching the function is not 1.

Between its matmuls the differentiated walk runs one Pallas kernel where the
shapes allow (a vocabulary of whole 128-lane registers, no bias) and XLA's
chain elsewhere: the kernel is held to the chain on the same logits and to
autodiff through ``fused_cross_entropy``, the shapes that keep it out are
shown to take the chain, and the block it picks is shown to fit its budget.
"""

import functools
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mlx_cuda_distributed_pretraining_tpu.ops import fused_ce

B, S, D, V = 2, 24, 16, 96  # 48 rows: chunk 16 divides them, chunk 20 pads


def _inputs(tied, bias, dtype, V=V):
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


def _assert_grads_close(got, want, tol):
    for name in want:
        scale = float(jnp.max(jnp.abs(want[name])))
        np.testing.assert_allclose(np.asarray(got[name]), np.asarray(want[name]),
                                   atol=tol * scale, rtol=0, err_msg=name)


def _walked(before, after):
    return {k: after[k] - before[k] for k in after}


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
    _assert_grads_close(g1, g0, 2e-6 if dtype == jnp.float32 else 6e-3)

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


# -- the kernel between the walk's matmuls ------------------------------------------
KERNEL_CASES = list(itertools.product((256, 1152), (48, 40), (0.0, 1e-2), (None, 0.5),
                                      ("float32", "bfloat16")))


@pytest.mark.parametrize("vocab,rows,z_weight,logit_scale,dtype", KERNEL_CASES,
                         ids=["-".join(map(str, c)) for c in KERNEL_CASES])
def test_kernel_matches_xlas_chain_on_the_same_logits(vocab, rows, z_weight, logit_scale, dtype):
    """48 rows are whole groups of 16 and 40 are padded; a quarter of the rows
    is masked; the vocabulary is 2 or 9 lane registers (one trip of the
    kernel's unrolled loop and a tail)."""
    dtype = jnp.dtype(dtype)
    rng = np.random.default_rng(vocab + rows)
    logits = jnp.asarray(4.0 * rng.normal(size=(rows, vocab)), jnp.float32)
    tc = jnp.asarray(rng.integers(0, vocab, size=(rows,)), jnp.int32).at[:2].set(
        jnp.asarray([0, vocab - 1]))
    mc = jnp.asarray(rng.uniform(size=(rows,)) > 0.25, jnp.float32)

    assert fused_ce.softmax_grad_rows(rows, vocab, dtype) == 48
    d, logz, terms = jax.jit(lambda x: fused_ce._softmax_grad_call(
        x, tc, mc, logit_scale, z_weight, dtype))(logits)
    loss, d_again = fused_ce._softmax_grad_kernel(logits, tc, mc, logit_scale, z_weight, dtype)
    want_loss, want_d = fused_ce._softmax_grad_xla(logits, tc, mc, logit_scale, z_weight)
    want_logz = jax.nn.logsumexp(logits, axis=-1)

    assert d.shape == (rows, vocab) and d.dtype == dtype
    np.testing.assert_array_equal(np.asarray(d_again, np.float32), np.asarray(d, np.float32))
    np.testing.assert_allclose(np.asarray(logz), np.asarray(want_logz), rtol=1e-6)
    np.testing.assert_allclose(float(jnp.sum(terms * mc)), float(want_loss), rtol=2e-6)
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=2e-6)
    # fp32: the last place of the softmax (e / sum against exp(x - logz)), which
    # is the last place of 1 at a gold logit whose p is near 1; bf16: the
    # chain's d rounded once, and where that last place tips the rounding, one
    # bf16 place of the element
    got, want = np.asarray(d, np.float32), np.asarray(want_d.astype(dtype), np.float32)
    scale = float(np.abs(want).max())
    if dtype == jnp.float32:
        np.testing.assert_allclose(got, want, atol=2e-6 * scale, rtol=0)
    else:
        assert (np.abs(got - want) <= 2.0 ** -8 * np.abs(want) + 2e-6 * scale).all()
        assert (got != want).mean() < 0.01
    dead = np.asarray(mc) == 0
    assert dead.any() and not got[dead].any()


KERNEL_GRAD_CASES = list(itertools.product((256, 1152), (16, 20), ((None, 0.0), (0.5, 1e-2)),
                                           ("float32", "bfloat16")))


@pytest.mark.parametrize("vocab,chunk,scale_z,dtype", KERNEL_GRAD_CASES,
                         ids=["-".join(map(str, c)) for c in KERNEL_GRAD_CASES])
def test_value_and_grad_through_the_kernel_match_materialized_logits(vocab, chunk, scale_z,
                                                                      dtype):
    """A tied table at a vocabulary the kernel takes; chunk 20 pads the 48
    rows to 60 and the kernel's 20 to 32."""
    (logit_scale, z_weight), dtype = scale_z, jnp.dtype(dtype)
    params, tokens, targets, mask = _inputs(True, False, dtype, vocab)
    count = jnp.maximum(mask.sum(), 1.0)

    def loss(p, fused):
        hidden, w_vd = _head_operands(p, tokens, True, dtype)
        fn = (functools.partial(fused_ce.fused_cross_entropy, chunk=chunk) if fused
              else _materialized)
        return fn(hidden, w_vd, targets, mask, bias_v=None, logit_scale=logit_scale,
                  z_weight=z_weight) / count

    before = fused_ce.plan_counts()
    l1, g1 = jax.value_and_grad(lambda p: loss(p, True))(params)
    assert _walked(before, fused_ce.plan_counts()) == {
        "grad_in_forward": 1, "forward_only": 0, "softmax_grad_kernel": 1, "softmax_grad_xla": 0}
    l0, g0 = jax.value_and_grad(lambda p: loss(p, False))(params)
    np.testing.assert_allclose(float(l1), float(l0), rtol=2e-6)
    _assert_grads_close(g1, g0, 2e-6 if dtype == jnp.float32 else 6e-3)


@pytest.mark.parametrize("kept_out", ("vocab_200", "bias", "undifferentiated"))
def test_shapes_that_keep_the_kernel_out_take_xlas_chain(kept_out):
    params, tokens, targets, mask = _inputs(False, kept_out == "bias", jnp.float32,
                                            200 if kept_out == "vocab_200" else 256)

    def loss(p, fn):
        hidden, w_vd = _head_operands(p, tokens, False, jnp.float32)
        return fn(hidden, w_vd, targets, mask, bias_v=p.get("b"), logit_scale=None,
                  z_weight=1e-2)

    fused = functools.partial(fused_ce.fused_cross_entropy, chunk=16)
    want, want_grads = jax.value_and_grad(lambda p: loss(p, _materialized))(params)
    before = fused_ce.plan_counts()
    if kept_out == "undifferentiated":
        got = loss(params, fused)
        walked = {"grad_in_forward": 0, "forward_only": 1, "softmax_grad_kernel": 0,
                  "softmax_grad_xla": 0}
    else:
        got, grads = jax.value_and_grad(lambda p: loss(p, fused))(params)
        walked = {"grad_in_forward": 1, "forward_only": 0, "softmax_grad_kernel": 0,
                  "softmax_grad_xla": 1}
        _assert_grads_close(grads, want_grads, 2e-6)
    assert _walked(before, fused_ce.plan_counts()) == walked
    np.testing.assert_allclose(float(got), float(want), rtol=2e-6)


@pytest.mark.parametrize("vocab,rows,dtype,block", [
    (200064, 2048, "bfloat16", 16),   # phi4-mini-flash-l6: 16 whole rows are 12.8 MB of logits
    (32768, 2048, "bfloat16", 128),   # mistral-7b-v0_3-l4
    (16384, 2048, "bfloat16", 256),   # xing4_0-29b-a4b-ep8
    (16384, 2048, "float32", 128),
    (32768, 40, "bfloat16", 48),      # rows padded to whole groups of 16
    (25024, 2048, "bfloat16", 0),     # trinity-mini-ep8: 195.5 lane registers
    (2 ** 20, 2048, "bfloat16", 0),   # 16 whole rows do not fit
])
def test_the_kernels_block_fits_the_budget_it_states(vocab, rows, dtype, block):
    got = fused_ce.softmax_grad_rows(rows, vocab, dtype)
    assert got == block
    if block:
        assert got % 16 == 0 and fused_ce._whole_groups(rows) % got == 0
        held = fused_ce._block_vmem_bytes(got, vocab, jnp.dtype(dtype).itemsize)
        # logits in and d out, two buffers each, and one group's exponentials
        assert held == (2 * got * vocab * (4 + jnp.dtype(dtype).itemsize) + 16 * vocab * 4)
        assert held <= fused_ce._VMEM_BUDGET < fused_ce._VMEM_LIMIT <= 100 * 2 ** 20
    else:
        assert vocab % 128 or fused_ce._block_vmem_bytes(
            16, vocab, jnp.dtype(dtype).itemsize) > fused_ce._VMEM_BUDGET


def test_under_a_mesh_gspmd_gets_xlas_chain_and_a_shard_map_the_kernel():
    """GSPMD cannot partition a Mosaic kernel: with a mesh of several devices
    active the walk it shards takes XLA's chain, and the sequence-sharded
    call, whose walk runs inside a ``shard_map`` over all of the mesh, takes
    the kernel; both give the materialised gradients."""
    from jax.sharding import Mesh

    from mlx_cuda_distributed_pretraining_tpu.parallel.context import use_mesh

    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("dp", "sp"))
    params, tokens, targets, mask = _inputs(False, False, jnp.float32, 256)

    def loss(p, fn):
        hidden, w_vd = _head_operands(p, tokens, False, jnp.float32)
        return fn(hidden, w_vd, targets, mask, bias_v=None, logit_scale=None, z_weight=0.0)

    want, want_grads = jax.value_and_grad(lambda p: loss(p, _materialized))(params)
    forms = {"softmax_grad_xla": functools.partial(fused_ce.fused_cross_entropy, chunk=16),
             "softmax_grad_kernel": functools.partial(fused_ce.fused_cross_entropy_sp,
                                                      mesh=mesh, chunk=16)}
    with use_mesh(mesh):
        for key, fn in forms.items():
            before = fused_ce.plan_counts()
            got, grads = jax.jit(jax.value_and_grad(lambda p, fn=fn: loss(p, fn)))(params)
            walked = _walked(before, fused_ce.plan_counts())
            assert walked[key] == walked["grad_in_forward"] >= 1, (key, walked)
            assert walked["softmax_grad_kernel"] + walked["softmax_grad_xla"] == walked[key]
            np.testing.assert_allclose(float(got), float(want), rtol=2e-6)
            _assert_grads_close(grads, want_grads, 2e-6)
