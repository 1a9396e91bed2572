"""Pallas flash/flex kernel parity vs the einsum reference (SURVEY.md §4
item a): per mask type, forward and gradients, GQA/MQA, fp32.

Runs the real kernel code in Pallas interpret mode on CPU; the identical
code compiles to Mosaic on TPU. A test that takes ``flash_path``
(conftest.py) runs under both paths of every kernel, forward and backward:
resident and streamed.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mlx_cuda_distributed_pretraining_tpu.ops import masks as M
from mlx_cuda_distributed_pretraining_tpu.ops.attention import reference_attention
from mlx_cuda_distributed_pretraining_tpu.ops import flash_attention as fa
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
def test_forward_parity(mask_type, flash_path):
    q, k, v = _qkv()
    out = flash_attention(q, k, v, mask_type=mask_type, window_size=96,
                          prefix_len=80, block_q=BLOCK, block_kv=BLOCK)
    ref = reference_attention(q, k, v, mask_mod=MASKS[mask_type])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("hq,hkv", [(4, 2), (8, 2), (4, 1)])  # GQA 2:1, 4:1, MQA
def test_forward_parity_gqa_mqa(hq, hkv, flash_path):
    q, k, v = _qkv(hq, hkv)
    out = flash_attention(q, k, v, block_q=BLOCK, block_kv=BLOCK)
    ref = reference_attention(q, k, v, mask_mod=M.causal())
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("mask_type", ["causal", "sliding_window", "prefix_lm", "full"])
def test_gradient_parity(mask_type, flash_path):
    q, k, v = _qkv()

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, mask_type=mask_type, window_size=96,
                            prefix_len=80, block_q=BLOCK, block_kv=BLOCK)
        return jnp.sum(o * jnp.cos(o))  # nontrivial cotangent

    def loss_ref(q, k, v):
        o = reference_attention(q, k, v, mask_mod=MASKS[mask_type])
        return jnp.sum(o * jnp.cos(o))

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4, rtol=5e-3,
                                   err_msg=f"d{name} mismatch for {mask_type}")


def test_gradient_parity_gqa(flash_path):
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


def test_flex_alibi_parity(flash_path):
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


def test_flex_soft_cap_forward_parity(flash_path):
    q, k, v = _qkv()
    capped = flex_attention(q, k, v, mask_mod=M.causal(), score_mod=soft_cap_score_fn(5.0),
                            block_q=BLOCK, block_kv=BLOCK)
    ref = _soft_cap_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(capped), np.asarray(ref), atol=2e-5, rtol=2e-5)
    plain = flex_attention(q, k, v, mask_mod=M.causal(), block_q=BLOCK, block_kv=BLOCK)
    assert not np.allclose(np.asarray(capped), np.asarray(plain))


def test_flex_soft_cap_gradient_parity(flash_path):
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


def test_flex_custom_mask_exact(flash_path):
    """An arbitrary untagged mask mod (causal AND not-multiple-of-7 col) is
    applied exactly, not block-sampled."""

    def mod(q, k):
        return (q >= k) & ((k % 7) != 0)

    q, k, v = _qkv()
    out = flex_attention(q, k, v, mask_mod=mod, block_q=BLOCK, block_kv=BLOCK)
    ref = reference_attention(q, k, v, mask_mod=mod)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_bf16_inputs(flash_path):
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


def test_model_level_flash_matches_simple(flash_path):
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


def test_interior_tile_fast_path_matches(flash_path):
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


def test_band_mask_multiblock_matches_reference(flash_path):
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


def test_flash_under_mesh_matches_unsharded(flash_path):
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


# -- the two paths of each kernel, and the plan that picks one ---------------
def _raw_qkv(hq=2, hkv=2, sq=512, skv=512, d=32, dtype=jnp.float32, seed=3):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (1, hq, sq, d), dtype),
            jax.random.normal(ks[1], (1, hkv, skv, d), dtype),
            jax.random.normal(ks[2], (1, hkv, skv, d), dtype))


RAW_CASES = {
    "causal": dict(mask_type="causal", mask_fn=M.causal()),
    "sliding_window": dict(mask_type="sliding_window", window=96,
                           mask_fn=M.sliding_window(96)),
    "prefix_lm": dict(mask_type="prefix_lm", prefix_len=130, mask_fn=M.prefix_lm(130)),
    "full": dict(mask_type="full", mask_fn=None),
    "band_partial": dict(mask_type="band", window=64, mask_fn=M.band(64)),
    # every query tile but the last has lo > hi: an empty KV walk (and every
    # KV tile but the first an empty query walk)
    "band_empty_ranges": dict(mask_type="band", window=-384, mask_fn=M.band(-384)),
    "custom_mask": dict(mask_type="full", canonical_mask=False,
                        mask_fn=lambda r, c: (r >= c) & ((c % 7) != 0)),
    "gqa_4to1": dict(mask_type="causal", mask_fn=M.causal(), hq=8, hkv=2),
    "short_q": dict(mask_type="full", mask_fn=None, sq=128),
    "short_kv_causal": dict(mask_type="causal", mask_fn=M.causal(), skv=256),
    "bf16": dict(mask_type="causal", mask_fn=M.causal(), dtype=jnp.bfloat16),
    # a non-additive score program: the backward chains through its _d_score
    "soft_cap": dict(mask_type="causal", mask_fn=M.causal(), score_fn=soft_cap_score_fn(5.0)),
    "alibi_gqa": dict(mask_type="causal", mask_fn=M.causal(), score_fn=alibi_score_fn(4),
                      hq=4, hkv=2),
    "d64": dict(mask_type="causal", mask_fn=M.causal(), d=64),
    "d128_window": dict(mask_type="sliding_window", window=200,
                        mask_fn=M.sliding_window(200), d=128),
    # two copies of 512 rows: every block fits a copy, the default ones too
    "block_diffusion": dict(mask_type="block_diffusion", window=4, prefix_len=512,
                            mask_fn=M.block_diffusion(512, 4), sq=1024, skv=1024),
    "block_diffusion_b64_gqa": dict(mask_type="block_diffusion", window=64, prefix_len=512,
                                    mask_fn=M.block_diffusion(512, 64), sq=1024, skv=1024,
                                    hq=4, hkv=2),
    # the plan's tiles under another mask program: every live tile masked in-tile
    "block_diffusion_custom": dict(mask_type="block_diffusion", window=4, prefix_len=512,
                                   canonical_mask=False, sq=1024, skv=1024,
                                   mask_fn=lambda r, c: M.block_diffusion(512, 4)(r, c) & ((c % 5) != 0)),
}


def _close(got, want, name, tol):
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32),
                               atol=tol, rtol=tol, err_msg=name)


# "auto": each path's own default blocks, which differ (512x512 against 256x512)
@pytest.mark.parametrize("blocks", [(128, 128), (64, 128), (256, 64), (None, None)],
                         ids=lambda b: "auto" if b[0] is None else f"{b[0]}x{b[1]}")
@pytest.mark.parametrize("kernels", ["fwd", "bwd"])
@pytest.mark.parametrize("name", sorted(RAW_CASES))
def test_resident_matches_streamed(name, kernels, blocks):
    """One input through both paths of the forward (``fwd``) or of dQ and
    dK/dV (``bwd``): the same tiles with the same arithmetic, so the results
    agree to float32 round-off (the order of the sums where the blocks
    differ). An empty walk leaves o, dq, dk and dv zero and lse at its floor
    in both."""
    case = dict(RAW_CASES[name])
    shape = {key: case.pop(key) for key in ("hq", "hkv", "sq", "skv", "d", "dtype")
             if key in case}
    case.setdefault("canonical_mask", True)
    q, k, v = _raw_qkv(**shape)
    case.update(block_q=blocks[0], block_kv=blocks[1], scale=q.shape[-1] ** -0.5)
    tol = 1e-2 if q.dtype == jnp.bfloat16 else 1e-6
    o, lse = fa.flash_fwd(q, k, v, _path="streamed", **case)
    # a row with no key to see has lse at its floor and an o that depends on
    # which tiles were live: compared, and handed on, as a merge would take it
    seen = np.asarray(lse)[0, 0, 0] > -1e29
    if kernels == "fwd":
        o_r, lse_r = fa.flash_fwd(q, k, v, _path="resident", **case)
        _close(o_r[:, :, seen], o[:, :, seen], "o", tol)
        np.testing.assert_allclose(np.asarray(lse_r), np.asarray(lse), rtol=1e-6, atol=1e-5)
        outs = [o_r, lse_r]
    else:
        g = jnp.cos(jax.random.normal(jax.random.PRNGKey(11), q.shape, q.dtype))
        lse = jnp.where(lse > -1e29, lse, 0.0)  # ring attention's merged lse is finite
        delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)[:, :, None, :]
        got = {path: (fa.flash_bwd_dq(q, k, v, g, lse, delta, _path=path, **case),
                      *fa.flash_bwd_dkv(q, k, v, g, lse, delta, _path=path, **case))
               for path in ("resident", "streamed")}
        # sums of up to 512 products of O(1) terms in another order
        for r, s, grad in zip(got["resident"], got["streamed"], ("dq", "dk", "dv")):
            _close(r, s, grad, 2e-2 if q.dtype == jnp.bfloat16 else 2e-5)
        outs = got["resident"]
    if name == "band_empty_ranges":
        # row - col < -384: only rows under 128 see anything, and only
        # columns from 385 on are seen
        dead_q, dead_kv = np.arange(512) >= 256, np.arange(512) < 256
        if kernels == "fwd":
            if blocks[0]:  # "auto" has one query tile, live
                assert np.all(np.asarray(outs[0])[:, :, dead_q] == 0)
            assert np.all(np.asarray(outs[1])[:, :, 0][:, :, dead_q] < -1e29)
        else:
            assert np.all(np.asarray(outs[0])[:, :, dead_q] == 0)
            assert np.all(np.asarray(outs[1])[:, :, dead_kv] == 0)
            assert np.all(np.asarray(outs[2])[:, :, dead_kv] == 0)


@pytest.mark.parametrize("mask_type,sq,skv", [("full", 128, 512), ("causal", 512, 256)])
def test_unequal_lengths_match_reference(mask_type, sq, skv, flash_path):
    """Sq != Skv, as a ring-attention chunk or a cross-attention call has it."""
    q, k, v = _raw_qkv(sq=sq, skv=skv)
    mask = M.causal() if mask_type == "causal" else None
    o, _ = fa.flash_fwd(q, k, v, mask_type=mask_type, mask_fn=mask, canonical_mask=True,
                        block_q=64, block_kv=128, scale=32 ** -0.5)
    ref = reference_attention(*(t.transpose(0, 2, 1, 3) for t in (q, k, v)),
                              mask_mod=mask).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("mask_type,window,prefix", [
    ("causal", 0, 0), ("sliding_window", 96, 0), ("sliding_window", 300, 0),
    ("sliding_window", 1, 0), ("prefix_lm", 0, 130), ("prefix_lm", 0, 512),
    ("band", 64, 0), ("band", -384, 0), ("band", 700, 0)])
@pytest.mark.parametrize("bq,bkv", [(128, 128), (64, 256), (256, 64)])
@pytest.mark.parametrize("axis", ["kv", "q"])
def test_full_range_is_the_run_of_full_tiles(mask_type, window, prefix, bq, bkv, axis):
    """A resident walk's unmasked run [a, b), clamped into [lo, hi) as the
    kernels clamp it, holds exactly the tiles _full_tile_fn calls full: along
    the KV axis for a query tile (forward, dQ), along the query axis for a KV
    tile (dK/dV)."""
    S = 1024
    nq, nkv = S // bq, S // bkv
    full = fa._full_tile_fn(mask_type, window, prefix, bq, bkv)
    if axis == "kv":
        lo_fn, hi_fn = fa._kv_range(mask_type, window, prefix, bq, bkv, nkv)
        a_fn, b_fn = fa._full_range(mask_type, window, prefix, bq, bkv)
        n, is_full = nq, full
    else:
        lo_fn, hi_fn = fa._q_range(mask_type, window, prefix, bq, bkv, nq)
        a_fn, b_fn = fa._full_range_q(mask_type, window, prefix, bq, bkv)
        n, is_full = nkv, lambda ki, j: full(j, ki)
    for i in range(n):
        lo, hi = int(lo_fn(i)), int(hi_fn(i))
        a = lo if a_fn is None else int(jnp.clip(a_fn(i), lo, hi))
        b = hi if b_fn is None else int(jnp.clip(b_fn(i), a, hi))
        for j in range(lo, hi):
            assert bool(is_full(i, j)) == (a <= j < b), (i, j, lo, a, b, hi)


def test_flash_plan_picks_by_shape():
    """The path is a function of the shapes alone: K and V of a KV head in
    VMEM where they fit the budget, the streamed kernel beyond it, no kernel
    where no block divides; and every traced call is tallied by path."""
    # mistral-7b-v0_3-l4.train-1chip: 4 x 4,096, heads of 128, bf16
    cell = fa.flash_plan(4096, 4096, 128, jnp.bfloat16)
    assert cell.path == "resident"
    assert (cell.block_q, cell.block_kv) == fa._RESIDENT_BLOCKS
    long = fa.flash_plan(32768, 32768, 128, jnp.bfloat16)
    assert (long.path, long.block_q, long.block_kv) == ("streamed", *fa._STREAMED_BLOCKS)
    # the budget counts bytes: float32 K/V fill it at half the length
    edge = max(s for s in (2 ** n for n in range(10, 17))
               if fa.flash_plan(s, s, 128, jnp.bfloat16).path == "resident")
    assert fa.flash_plan(edge, edge, 128, jnp.float32).path == "streamed"
    assert fa.flash_plan(edge // 2, edge // 2, 128, jnp.float32).path == "resident"
    # a narrow head is padded to whole registers in VMEM
    assert fa.flash_plan(edge, edge, 64, jnp.bfloat16).path == "resident"
    assert fa.flash_plan(2 * edge, 2 * edge, 64, jnp.bfloat16).path == "streamed"
    assert fa.flash_plan(192, 192, 32, jnp.float32).path == "reference"
    assert fa.flash_plan(4096, 192, 128, jnp.bfloat16).path == "reference"
    assert fa.flash_plan(100, 100, 32, jnp.float32, 64, 64).path == "reference"
    # a block the caller names is taken, one left out is the path's default
    assert fa.flash_plan(4096, 4096, 128, jnp.bfloat16, 128, None)[1:] == (
        128, fa._RESIDENT_BLOCKS[1])

    before = fa.plan_counts()
    q, k, v = _qkv()
    flash_attention(q, k, v, block_q=BLOCK, block_kv=BLOCK)            # resident
    flash_attention(*_qkv(s=100), block_q=BLOCK, block_kv=BLOCK)       # odd: reference
    rq, rk, rv = _raw_qkv()
    fa.flash_fwd(rq, rk, rv, mask_fn=M.causal(), block_q=128, block_kv=128,
                 _path="streamed")
    after = fa.plan_counts()
    assert {p: after[p] - before[p] for p in after if after[p] != before[p]} == {
        "resident": 1, "streamed": 1, "reference": 1}  # no backward was traced


@pytest.mark.parametrize("kernel", ["flash_bwd_dq", "flash_bwd_dkv"])
def test_flash_plan_backward_at_the_budgets_edge(kernel):
    """The backward kernels answer from the same budget: the bytes of the two
    operands they hold (K and V for dQ; Q and dO, with the lse and delta
    rows, for dK/dV) plus four float32 chunk arrays where the forward has
    two, so a backward kernel never stays resident past the forward and may
    leave it earlier; and every traced call is tallied by kernel and path."""
    cell = fa.flash_plan(4096, 4096, 128, jnp.bfloat16, kernel=kernel)
    assert cell.path == "resident"
    assert (cell.block_q, cell.block_kv) == fa._RESIDENT_BLOCKS
    sizes = [2 ** n for n in range(10, 17)]
    edge = {kern: max(s for s in sizes
                      if fa.flash_plan(s, s, 128, jnp.bfloat16, kernel=kern).path == "resident")
            for kern in ("flash_fwd", kernel)}
    assert 4096 < edge[kernel] <= edge["flash_fwd"]
    past = fa.flash_plan(2 * edge[kernel], 2 * edge[kernel], 128, jnp.bfloat16, kernel=kernel)
    assert (past.path, past.block_q, past.block_kv) == ("streamed", *fa._STREAMED_BLOCKS)
    assert fa.flash_plan(edge[kernel], edge[kernel], 128, jnp.float32, kernel=kernel).path == "streamed"
    # what a kernel holds is what counts: K/V for dQ, Q/dO for dK/dV
    long, short = 2 * edge[kernel], 1024
    held_long = dict(flash_bwd_dq=(short, long), flash_bwd_dkv=(long, short))[kernel]
    assert fa.flash_plan(*held_long, 128, jnp.bfloat16, kernel=kernel).path == "streamed"
    assert fa.flash_plan(*held_long[::-1], 128, jnp.bfloat16, kernel=kernel).path == "resident"
    # a chunk's temporaries count: blocks too large for the budget stream
    assert fa.flash_plan(edge[kernel], edge[kernel], 128, jnp.bfloat16, 2048, 2048,
                         kernel=kernel).path == "streamed"
    assert fa.flash_plan(192, 192, 32, jnp.float32, kernel=kernel).path == "reference"

    before = fa.plan_counts()
    q, k, v = _raw_qkv()
    stat = jnp.zeros((1, 2, 1, 512), jnp.float32)
    entry = getattr(fa, kernel)
    entry(q, k, v, q, stat, stat, mask_fn=M.causal())
    entry(q, k, v, q, stat, stat, mask_fn=M.causal(), _path="streamed")
    after = fa.plan_counts()
    short_name = kernel[len("flash_"):]
    assert {p: after[p] - before[p] for p in after if after[p] != before[p]} == {
        f"{short_name}_resident": 1, f"{short_name}_streamed": 1}


# -- head sizes that differ: q and k wider than v (latent attention) ----------
def _qkv_two_widths(dk, dv, hq=4, hkv=4, s=S, seed=3):
    rng = np.random.default_rng(seed)
    draw = lambda h, d: jnp.asarray(rng.normal(size=(B, s, h, d)).astype(np.float32))
    return draw(hq, dk), draw(hkv, dk), draw(hkv, dv)


@pytest.mark.parametrize("dk,dv", [(48, 32), (24, 16), (32, 64)])
def test_two_head_sizes_forward_and_gradients(dk, dv, flash_path):
    """q and k of one head size, v of another, through all three kernels of
    either path against the einsum reference: output [.., dv], dq and dk
    [.., dk], dv [.., dv]."""
    q, k, v = _qkv_two_widths(dk, dv)
    scale = 0.7 * dk ** -0.5  # a scale the caller names, as latent attention does
    weight = jnp.cos(jnp.arange(dv, dtype=jnp.float32))
    got = lambda q, k, v: jnp.sum(weight * flash_attention(
        q, k, v, scale=scale, block_q=BLOCK, block_kv=BLOCK))
    want = lambda q, k, v: jnp.sum(weight * reference_attention(
        q, k, v, mask_mod=M.causal(), scale=scale))
    out = flash_attention(q, k, v, scale=scale, block_q=BLOCK, block_kv=BLOCK)
    assert out.shape == (B, S, 4, dv)
    np.testing.assert_allclose(np.asarray(out), np.asarray(
        reference_attention(q, k, v, mask_mod=M.causal(), scale=scale)), atol=2e-5, rtol=2e-5)
    for g, r, name in zip(jax.grad(got, (0, 1, 2))(q, k, v), jax.grad(want, (0, 1, 2))(q, k, v),
                          ("dq", "dk", "dv")):
        assert g.shape == r.shape, name
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), atol=5e-5, rtol=5e-5,
                                   err_msg=name)


def test_two_head_sizes_grouped_queries(flash_path):
    q, k, v = _qkv_two_widths(48, 32, hq=4, hkv=2)
    got = lambda q, k, v: jnp.sum(jnp.sin(flash_attention(q, k, v, block_q=BLOCK, block_kv=BLOCK)))
    want = lambda q, k, v: jnp.sum(jnp.sin(reference_attention(q, k, v, mask_mod=M.causal())))
    for g, r in zip(jax.grad(got, (0, 1, 2))(q, k, v), jax.grad(want, (0, 1, 2))(q, k, v)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), atol=5e-5, rtol=5e-5)


def test_flash_plan_takes_both_head_sizes():
    """The plan's VMEM count holds one operand of each width; with equal
    widths it is the count it always was, so a dense model's plan (and its
    compiled step) does not move."""
    for kernel in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        same = fa.flash_plan(4096, 4096, 128, jnp.bfloat16, kernel=kernel)
        assert same == fa.flash_plan(4096, 4096, 128, jnp.bfloat16, kernel=kernel, Dv=128)
        assert fa.flash_plan(4096, 4096, 192, jnp.bfloat16, kernel=kernel, Dv=128) == same
    # 192 pads to 256 lanes: held K (256) + V (128) is 3/4 of a 256/256 call
    edge = max(s for s in (2 ** n for n in range(9, 18))
               if fa.flash_plan(s, s, 192, jnp.bfloat16, Dv=128).path == "resident")
    assert fa.flash_plan(edge, edge, 192, jnp.bfloat16, Dv=192).path == "resident"
    assert fa.flash_plan(2 * edge, 2 * edge, 192, jnp.bfloat16, Dv=128).path == "streamed"


# -- the two-copy mask of diffusion over blocks ----------------------------------------
# (rows a copy, block length, tile, KV heads of 4 query heads, the squares a
# noised-diagonal tile is walked in on the resident path: 0 = the whole tile at once)
_BD_CASES = [(128, 4, 128, 2, 0), (256, 4, 128, 2, 0), (512, 8, 128, 2, 0), (256, 128, 128, 2, 0),
             (512, 64, 128, 2, 0),
             # the benchmark cell's tiles of 512 x 512, two a copy, GQA 4:1: squares of
             # max(128, B') where the tile holds several, else the tile whole
             (1024, 4, 512, 1, 4), (1024, 64, 512, 1, 4), (1024, 128, 512, 1, 4),
             (1024, 256, 512, 1, 2), (1024, 512, 512, 1, 0)]
# under a mask program of the caller's: a case a tile size, and one whose squares are one block
_BD_CUSTOM = [case for case in _BD_CASES if case[:2] in ((256, 4), (1024, 4), (1024, 128))]


@pytest.mark.parametrize("L,Bp,block,hkv,squares,custom",
                         [c + (False,) for c in _BD_CASES] + [c + (True,) for c in _BD_CUSTOM],
                         ids=lambda v: {True: "custom_mask_fn", False: "canonical"}.get(v, str(v))
                         if isinstance(v, bool) else str(v))
def test_block_diffusion_kernels_match_reference(L, Bp, block, hkv, squares, custom, flash_path):
    """Forward, dQ and dK/dV under ``block_diffusion`` against
    ``reference_attention`` on the materialised mask, where a copy is 1, 2 and
    4 tiles and a block is a few rows, a square of a tile or a whole tile; the
    canonical mask through the cut segments' closed forms and the narrow walk
    of the noised diagonal, a custom ``mask_fn`` through the caller's program
    on every live tile, whole."""
    rng = np.random.default_rng(L + Bp)
    q, k, v, g = (jnp.asarray(rng.normal(size=(1, 2 * L, h, D)).astype(np.float32))
                  for h in (4, hkv, hkv, 4))
    mod = M.block_diffusion(L, Bp)
    if custom:
        mod = lambda r, c, named=mod: named(r, c) & (((r + c) % 7) != 0)  # noqa: E731
    out, vjp = jax.vjp(lambda q, k, v: flash_attention(
        q, k, v, mask_type="block_diffusion", window_size=Bp, block_q=block, block_kv=block,
        mask_fn=mod if custom else None), q, k, v)
    want, vjp_ref = jax.vjp(lambda q, k, v: reference_attention(q, k, v, mask_mod=mod), q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5, rtol=2e-5)
    for got, ref, name in zip(vjp(g), vjp_ref(g), ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-4, rtol=1e-4,
                                   err_msg=name)
    tiles = fa.block_diffusion_tiles(L, Bp, block, block)
    live, cut, diagonal = int((tiles > 0).sum()), int((tiles == 1).sum()), L // block
    narrow = diagonal if squares and flash_path == "resident" and not custom else 0
    assert fa.bd_tiles_traced() == {"live": live, "grid": tiles.size, "narrow": narrow,
                                    "masked": (live if custom else cut) - narrow}
    if not custom:  # flex takes the named mask's plan from its tag
        flex = flex_attention(q, k, v, mask_mod=mod, block_q=block, block_kv=block)
        np.testing.assert_allclose(np.asarray(flex), np.asarray(want), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("L,Bp,bq,bkv", [
    (512, 4, 128, 128), (512, 4, 256, 64), (512, 4, 64, 256), (512, 64, 64, 128),
    (512, 128, 128, 128), (1024, 16, 512, 512), (1024, 8, 256, 512), (256, 4, 256, 256)])
def test_block_diffusion_plan_visits_the_live_tiles_and_no_other(L, Bp, bq, bkv):
    """Both axes' segment plans against ``masks.block_mask_map`` of the
    materialised mask: dead tiles in no segment, whole tiles in an unmasked
    one, cut tiles in a masked one; and what the streamed grid derives from the
    segments (its live and whole tests, the tile its pipeline holds)."""
    want = M.block_mask_map(M.block_diffusion(L, Bp), 2 * L, 2 * L, bq, bkv)
    assert np.array_equal(fa.block_diffusion_tiles(L, Bp, bq, bkv), want)
    nq, nkv = want.shape
    plans = {"kv": (fa._bd_kv_segments(L, Bp, bq, bkv), fa._BD_KV_GROUPS, nq, nkv, lambda i, j: want[i, j]),
             "q": (fa._bd_q_segments(L, Bp, bq, bkv), fa._BD_Q_GROUPS, nkv, nq, lambda i, j: want[j, i])}
    for axis, (segments, groups, n, m, tile) in plans.items():
        # the grid's form of the plan: the query axis' first two cut segments as one
        live_full, clamp = fa._segment_tests(
            fa._bd_q_segments(L, Bp, bq, bkv, resident=False) if axis == "q" else segments, groups)
        for i in range(n):
            seen = np.zeros(m, np.int8)
            last = -1
            for lo, hi, masked in segments(i):
                lo, hi = int(lo), int(hi)
                assert lo >= last or hi <= lo, (axis, i)           # ascending, no tile twice
                if hi > lo:
                    assert not seen[lo:hi].any()
                    seen[lo:hi] = 1 if masked else 2
                    last = hi
            assert np.array_equal(seen, [tile(i, j) for j in range(m)]), (axis, i)
            held = [int(clamp(i, j)) for j in range(m)]
            for j in range(m):
                live, full = (bool(x) for x in live_full(i, j))
                assert (live, full) == (seen[j] > 0, seen[j] == 2), (axis, i, j)
                assert seen[held[j]] > 0 and (held[j] == j if live else True), (axis, i, j)
            assert held == sorted(held)                              # the pipeline never goes back
    # 288 of 1,024 tiles at the benchmark cell's size, 48 of them cut by an edge:
    # the 16 of the noised diagonal walked in four squares of 128, the others whole
    cell = fa.block_diffusion_tiles(8192, 4, 512, 512)
    assert (int((cell > 0).sum()), int((cell == 1).sum()), cell.size) == (288, 48, 1024)
    assert fa.block_diffusion_walk(8192, 4, 512, 512) == {
        "live": 288, "grid": 1024, "masked": 32, "narrow": 16}
    assert fa.block_diffusion_walk(8192, 4, 512, 512, resident=False) == {
        "live": 288, "grid": 1024, "masked": 48, "narrow": 0}
    assert fa.block_diffusion_walk(8192, 4, 512, 512, canonical=False) == {
        "live": 288, "grid": 1024, "masked": 288, "narrow": 0}
    walk = fa.block_diffusion_walk(L, Bp, bq, bkv)
    assert (walk["live"], walk["masked"] + walk["narrow"]) == (int((want > 0).sum()),
                                                              int((want == 1).sum()))
    assert fa._bd_narrow_width(4, 512, 512) == 128 and fa._bd_narrow_width(256, 512, 512) == 256
    assert [fa._bd_narrow_width(*a) for a in ((512, 512, 512), (4, 128, 128), (4, 256, 512),
                                              (96, 384, 384))] == [None] * 4


def test_block_diffusion_takes_the_reference_path_where_a_tile_would_straddle():
    """``B'`` divides every block the plan can choose and a block divides a
    copy, or the call runs without the kernels, as indivisible sequences do."""
    rng = np.random.default_rng(0)
    mk = lambda s: tuple(jnp.asarray(rng.normal(size=(1, s, 2, D)).astype(np.float32)) for _ in range(3))
    for rows, Bp, blocks in ((384, 4, {}),                             # 192 rows a copy: no block
                             (512, 4, {}),                             # default blocks span both copies
                             (512, 32, dict(block_q=128, block_kv=128)),
                             (768, 96, dict(block_q=128, block_kv=128))):  # a block is no multiple of B'
        q, k, v = mk(rows)
        before = fa.plan_counts()
        out = flash_attention(q, k, v, mask_type="block_diffusion", window_size=Bp, **blocks)
        after = fa.plan_counts()
        kernel = (rows, Bp) == (512, 32)
        assert after["reference"] - before["reference"] == (0 if kernel else 1), (rows, Bp)
        assert after["resident"] - before["resident"] == (1 if kernel else 0)
        want = reference_attention(q, k, v, mask_mod=M.block_diffusion(rows // 2, Bp))
        np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5, rtol=2e-5)
    q, k, v = mk(256)
    with pytest.raises(ValueError, match="two copies of one sequence"):
        flash_attention(q, k[:, :128], v[:, :128], mask_type="block_diffusion", window_size=4)
    with pytest.raises(ValueError, match="two copies of one sequence"):
        flash_attention(q, k, v, mask_type="block_diffusion", window_size=3)
