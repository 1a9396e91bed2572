"""Pallas flash/flex kernel parity vs the einsum reference (SURVEY.md §4
item a): per mask type, forward and gradients, GQA/MQA, fp32.

Runs the real kernel code in Pallas interpret mode on CPU; the identical
code compiles to Mosaic on TPU.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mlx_cuda_distributed_pretraining_tpu.ops import masks as M
from mlx_cuda_distributed_pretraining_tpu.ops.attention import reference_attention
from mlx_cuda_distributed_pretraining_tpu.ops.flash_attention import flash_attention
from mlx_cuda_distributed_pretraining_tpu.ops.flex_attention import (
    alibi_score_fn,
    flex_attention,
    soft_cap_score_fn,
)

B, S, D = 2, 256, 32
BLOCK = 64


def _qkv(hq=4, hkv=4, seed=0, s=S):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(B, s, hq, D)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, s, hkv, D)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, s, hkv, D)).astype(np.float32))
    return q, k, v


MASKS = {
    "causal": M.causal(),
    "sliding_window": M.sliding_window(96),
    "prefix_lm": M.prefix_lm(80),
    "full": None,
}


@pytest.mark.parametrize("mask_type", list(MASKS))
def test_forward_parity(mask_type):
    q, k, v = _qkv()
    out = flash_attention(q, k, v, mask_type=mask_type, window_size=96,
                          prefix_len=80, block_q=BLOCK, block_kv=BLOCK)
    ref = reference_attention(q, k, v, mask_mod=MASKS[mask_type])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("hq,hkv", [(4, 2), (4, 1)])
def test_forward_parity_gqa_mqa(hq, hkv):
    q, k, v = _qkv(hq, hkv)
    out = flash_attention(q, k, v, block_q=BLOCK, block_kv=BLOCK)
    ref = reference_attention(q, k, v, mask_mod=M.causal())
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("mask_type", ["causal", "sliding_window", "full"])
def test_gradient_parity(mask_type):
    q, k, v = _qkv()

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, mask_type=mask_type, window_size=96,
                            block_q=BLOCK, block_kv=BLOCK)
        return jnp.sum(o * jnp.cos(o))  # nontrivial cotangent

    def loss_ref(q, k, v):
        o = reference_attention(q, k, v, mask_mod=MASKS[mask_type])
        return jnp.sum(o * jnp.cos(o))

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4, rtol=5e-3,
                                   err_msg=f"d{name} mismatch for {mask_type}")


def test_gradient_parity_gqa():
    q, k, v = _qkv(4, 2)

    def loss(fn):
        def inner(q, k, v):
            return jnp.sum(fn(q, k, v) ** 2)

        return inner

    gf = jax.grad(loss(lambda q, k, v: flash_attention(q, k, v, block_q=BLOCK, block_kv=BLOCK)),
                  argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss(lambda q, k, v: reference_attention(q, k, v, mask_mod=M.causal())),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4, rtol=5e-3)


def test_flex_alibi_parity():
    q, k, v = _qkv()
    out = flex_attention(q, k, v, mask_mod=M.causal(), score_mod=alibi_score_fn(4),
                         block_q=BLOCK, block_kv=BLOCK)

    slopes = M.alibi_slopes(4)

    def ref_score(s, qi, ki):
        # s [B, Hkv, G, Sq, Skv] with Hkv=4, G=1
        bias = jnp.abs(qi - ki)[None, None, None]
        return s - jnp.asarray(slopes, jnp.float32)[None, :, None, None, None] * bias

    ref = reference_attention(q, k, v, mask_mod=M.causal(), score_mod=ref_score)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def _soft_cap_ref(q, k, v, cap=5.0):
    def ref_score(s, qi, ki):
        return cap * jnp.tanh(s / cap)

    return reference_attention(q, k, v, mask_mod=M.causal(), score_mod=ref_score)


def test_flex_soft_cap_forward_parity():
    q, k, v = _qkv()
    capped = flex_attention(q, k, v, mask_mod=M.causal(), score_mod=soft_cap_score_fn(5.0),
                            block_q=BLOCK, block_kv=BLOCK)
    ref = _soft_cap_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(capped), np.asarray(ref), atol=2e-5, rtol=2e-5)
    plain = flex_attention(q, k, v, mask_mod=M.causal(), block_q=BLOCK, block_kv=BLOCK)
    assert not np.allclose(np.asarray(capped), np.asarray(plain))


def test_flex_soft_cap_gradient_parity():
    """Non-additive score mod: backward must chain through the tanh
    Jacobian (regression for the missing sech^2 factor)."""
    q, k, v = _qkv()

    def loss_flex(q, k, v):
        o = flex_attention(q, k, v, mask_mod=M.causal(), score_mod=soft_cap_score_fn(5.0),
                           block_q=BLOCK, block_kv=BLOCK)
        return jnp.sum(o * jnp.cos(o))

    def loss_ref(q, k, v):
        return jnp.sum(_soft_cap_ref(q, k, v) * jnp.cos(_soft_cap_ref(q, k, v)))

    gf = jax.grad(loss_flex, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4, rtol=5e-3,
                                   err_msg=f"d{name} mismatch for soft_cap")


def test_fallback_preserves_mask_and_score():
    """Odd sequence length must NOT silently drop the mask/score program."""

    def mod(q, k):
        return (q >= k) & ((k % 7) != 0)

    q, k, v = _qkv(s=100)  # 100 % 64 != 0 -> fallback path
    out = flex_attention(q, k, v, mask_mod=mod, score_mod=soft_cap_score_fn(5.0),
                         block_q=BLOCK, block_kv=BLOCK)

    def ref_score(s, qi, ki):
        return 5.0 * jnp.tanh(s / 5.0)

    ref = reference_attention(q, k, v, mask_mod=mod, score_mod=ref_score)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)
    # ALiBi through the fallback as well (head-dependent slope)
    out_a = flex_attention(q, k, v, mask_mod=M.causal(),
                           score_mod=__import__(
                               "mlx_cuda_distributed_pretraining_tpu.ops.flex_attention",
                               fromlist=["alibi_score_fn"]).alibi_score_fn(4),
                           block_q=BLOCK, block_kv=BLOCK)
    slopes = M.alibi_slopes(4)

    def ref_alibi(s, qi, ki):
        bias = jnp.abs(qi - ki)[None, None, None]
        return s - jnp.asarray(slopes, jnp.float32)[None, :, None, None, None] * bias

    ref_a = reference_attention(q, k, v, mask_mod=M.causal(), score_mod=ref_alibi)
    np.testing.assert_allclose(np.asarray(out_a), np.asarray(ref_a), atol=2e-5, rtol=2e-5)


def test_flex_custom_mask_exact():
    """An arbitrary untagged mask mod (causal AND not-multiple-of-7 col) is
    applied exactly, not block-sampled."""

    def mod(q, k):
        return (q >= k) & ((k % 7) != 0)

    q, k, v = _qkv()
    out = flex_attention(q, k, v, mask_mod=mod, block_q=BLOCK, block_kv=BLOCK)
    ref = reference_attention(q, k, v, mask_mod=mod)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_bf16_inputs():
    q, k, v = _qkv()
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    out = flash_attention(qb, kb, vb, block_q=BLOCK, block_kv=BLOCK)
    assert out.dtype == jnp.bfloat16
    ref = reference_attention(q, k, v, mask_mod=M.causal())
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref), atol=2e-2, rtol=2e-2)


def test_odd_sizes_fallback():
    """Non-tile-divisible sequence falls back to the reference path."""
    q, k, v = _qkv(s=100)
    out = flash_attention(q, k, v, block_q=BLOCK, block_kv=BLOCK)
    ref = reference_attention(q, k, v, mask_mod=M.causal())
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_model_level_flash_matches_simple():
    from mlx_cuda_distributed_pretraining_tpu.models import llama
    from mlx_cuda_distributed_pretraining_tpu.models.llama import LlamaArgs

    base = LlamaArgs(vocab_size=64, hidden_size=32, intermediate_size=64,
                     num_layers=2, num_heads=4, num_kv_heads=2, head_dim=8,
                     max_position_embeddings=256)
    flash = LlamaArgs(**{**base.__dict__, "attention_type": "flash"})
    params = llama.init_params(jax.random.PRNGKey(0), base)
    tokens = jnp.asarray(np.random.default_rng(0).integers(1, 60, size=(2, 128)), jnp.int32)
    l_simple, _ = llama.forward(params, tokens, base)
    l_flash, _ = llama.forward(params, tokens, flash)
    np.testing.assert_allclose(np.asarray(l_simple), np.asarray(l_flash), atol=1e-3, rtol=1e-3)


def test_interior_tile_fast_path_matches():
    """canonical_mask=True (interior tiles skip in-tile masking) produces
    identical outputs to the always-masked path for every canonical mask."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mlx_cuda_distributed_pretraining_tpu.ops import masks as M
    from mlx_cuda_distributed_pretraining_tpu.ops.flash_attention import flash_fwd

    B, H, S, D = 1, 2, 512, 32
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq, (B, H, S, D), jnp.float32)
    k = jax.random.normal(kk, (B, H, S, D), jnp.float32)
    v = jax.random.normal(kv, (B, H, S, D), jnp.float32)
    cases = [
        ("causal", M.causal(), {}),
        ("sliding_window", M.sliding_window(96), {"window": 96}),
        ("prefix_lm", M.prefix_lm(130), {"prefix_len": 130}),
    ]
    for mask_type, mask_fn, kw in cases:
        o0, l0 = flash_fwd(q, k, v, mask_fn=mask_fn, mask_type=mask_type,
                           block_q=128, block_kv=128, canonical_mask=False, **kw)
        o1, l1 = flash_fwd(q, k, v, mask_fn=mask_fn, mask_type=mask_type,
                           block_q=128, block_kv=128, canonical_mask=True, **kw)
        np.testing.assert_allclose(np.asarray(o0), np.asarray(o1), atol=1e-6,
                                   err_msg=mask_type)
        np.testing.assert_allclose(np.asarray(l0), np.asarray(l1), atol=1e-6,
                                   err_msg=mask_type)


def test_band_mask_multiblock_matches_reference():
    """Band masks (sliding-window ring chunks) with negative/partial edges
    across MULTIPLE kv blocks — exercises empty tile ranges whose index
    maps must stay in [0, n_blocks-1] (OOB DMA regression guard)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mlx_cuda_distributed_pretraining_tpu.ops import masks as M
    from mlx_cuda_distributed_pretraining_tpu.ops.attention import reference_attention
    from mlx_cuda_distributed_pretraining_tpu.ops.flash_attention import flash_fwd

    B, H, S, D = 1, 2, 512, 16
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(kq, (B, H, S, D), jnp.float32)
    k = jax.random.normal(kk, (B, H, S, D), jnp.float32)
    v = jax.random.normal(kv, (B, H, S, D), jnp.float32)
    for t in (-384, -100, 64, 700):  # deep-negative edge, partial, beyond-S
        o, lse = flash_fwd(q, k, v, mask_type="band", window=t,
                           mask_fn=M.band(t), canonical_mask=True,
                           block_q=128, block_kv=128, scale=D ** -0.5)
        # reference with the same band mask; rows with no valid key carry
        # weight ~0 in lse -- compare only rows that have any valid key.
        ref = reference_attention(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), mask_mod=M.band(t),
        ).transpose(0, 2, 1, 3)
        rows = np.arange(S)
        valid = rows < (S - 1 + t)  # row - col < t has a solution c <= S-1
        if valid.any():
            np.testing.assert_allclose(np.asarray(o)[:, :, valid],
                                       np.asarray(ref)[:, :, valid],
                                       atol=1e-5, err_msg=f"t={t}")
        # fully-masked rows must report lse ~ NEG_INF (zero merge weight)
        if (~valid).any():
            assert np.all(np.asarray(lse)[:, :, 0][:, :, ~valid] < -1e29)


def test_flash_under_mesh_matches_unsharded():
    """Under a mesh the kernel runs inside a shard_map over batch (dp) and
    heads (tp) — GSPMD cannot partition a Mosaic kernel on the chip — and a
    GQA group never straddles two head shards. Same values, same grads."""
    from jax.sharding import Mesh

    from mlx_cuda_distributed_pretraining_tpu.parallel.context import use_mesh

    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (4, 128, 4, 16))
    k = jax.random.normal(ks[1], (4, 128, 2, 16))
    v = jax.random.normal(ks[2], (4, 128, 2, 16))

    def make_fn():
        # A fresh function per trace: the mesh is read from a context at
        # trace time and is no part of jit's cache key.
        def loss(q, k, v):
            o = flash_attention(q, k, v, mask_type="causal")
            return (o * jnp.cos(o)).sum(), o

        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True))

    (_, want_o), want_g = make_fn()(q, k, v)
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("dp", "tp"))
    with use_mesh(mesh):
        lowered = make_fn().lower(q, k, v)
        (_, got_o), got_g = lowered.compile()(q, k, v)
    assert "num_partitions = 4" in lowered.as_text()
    np.testing.assert_allclose(got_o, want_o, atol=1e-5)
    for got, want in zip(got_g, want_g):
        np.testing.assert_allclose(got, want, atol=1e-5)
