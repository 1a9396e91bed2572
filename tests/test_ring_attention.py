"""Ring attention (sequence/context parallelism) on the virtual 8-CPU mesh:
exact parity with single-device attention, gradients included. A test that
takes ``flash_path`` (conftest.py) runs its per-chunk kernel calls, forward
and backward, under both paths, resident and streamed."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from mlx_cuda_distributed_pretraining_tpu.config import SystemConfig
from mlx_cuda_distributed_pretraining_tpu.ops import masks as M
from mlx_cuda_distributed_pretraining_tpu.ops.attention import reference_attention
from mlx_cuda_distributed_pretraining_tpu.ops.ring_attention import make_ring_attention
from mlx_cuda_distributed_pretraining_tpu.parallel import build_mesh


def _mesh(cfg):
    return build_mesh(SystemConfig(seed=0, device="cpu", mesh=cfg))


def _qkv(hq=4, hkv=4, b=2, s=64, d=16, seed=0):
    rng = np.random.default_rng(seed)
    return tuple(
        jnp.asarray(rng.normal(size=(b, s, h, d)).astype(np.float32))
        for h in (hq, hkv, hkv)
    )


def test_ring_matches_reference_causal(flash_path):
    mesh = _mesh({"sp": 8})
    q, k, v = _qkv()
    ring = make_ring_attention(mesh, mask_mod=M.causal())
    out = jax.jit(ring)(q, k, v)
    ref = reference_attention(q, k, v, mask_mod=M.causal())
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5)


def test_ring_gqa_and_dp_axis(flash_path):
    mesh = _mesh({"dp": 2, "sp": 4})
    q, k, v = _qkv(hq=4, hkv=2)
    ring = make_ring_attention(mesh, mask_mod=M.causal())
    out = jax.jit(ring)(q, k, v)
    ref = reference_attention(q, k, v, mask_mod=M.causal())
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5)


def test_ring_sliding_window(flash_path):
    mesh = _mesh({"sp": 4})
    q, k, v = _qkv(s=64)
    ring = make_ring_attention(mesh, mask_mod=M.sliding_window(24))
    out = jax.jit(ring)(q, k, v)
    ref = reference_attention(q, k, v, mask_mod=M.sliding_window(24))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5)


def test_ring_gradients_match(flash_path):
    mesh = _mesh({"sp": 4})
    q, k, v = _qkv(s=32)
    ring = make_ring_attention(mesh, mask_mod=M.causal())

    def loss_ring(q, k, v):
        return jnp.sum(ring(q, k, v) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(reference_attention(q, k, v, mask_mod=M.causal()) ** 2)

    gr = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    gf = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gr, gf):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("mask", ["causal", "sliding_window"])
@pytest.mark.parametrize("named", [None, (16, 8)], ids=["plan", "named"])
def test_ring_chunks_take_the_plans_blocks(mask, named, monkeypatch):
    """Ring attention names no block of its own: its chunk calls hand all
    three kernels ``None`` and so get ``flash_plan``'s blocks for the chunk's
    shape, and a block its caller names reaches all three."""
    import functools

    from mlx_cuda_distributed_pretraining_tpu.ops import flash_attention as fa

    seen = []

    def recording(entry):
        raw = getattr(fa, entry)

        @functools.wraps(raw)
        def call(*args, **kw):
            seen.append((entry, kw["block_q"], kw["block_kv"]))
            return raw(*args, **kw)

        return call

    for entry in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        monkeypatch.setattr(fa, entry, recording(entry))
    mesh = _mesh({"sp": 4})
    q, k, v = _qkv(s=128)
    mask_mod = M.causal() if mask == "causal" else M.sliding_window(40)
    kw = dict(block_q=named[0], block_kv=named[1]) if named else {}
    ring = make_ring_attention(mesh, mask_mod=mask_mod, **kw)
    grads = jax.jit(jax.grad(lambda q, k, v: jnp.sum(ring(q, k, v) ** 2),
                             argnums=(0, 1, 2)))(q, k, v)
    ref = jax.grad(lambda q, k, v: jnp.sum(
        reference_attention(q, k, v, mask_mod=mask_mod) ** 2), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(grads, ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4)
    assert {entry for entry, _, _ in seen} == {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}
    assert {(bq, bkv) for _, bq, bkv in seen} == {named or (None, None)}


@pytest.mark.slow
def test_ring_model_level_end_to_end():
    """Full model with attention_type='ring' on an sp mesh == simple
    attention single device, and a sharded train step executes."""
    from mlx_cuda_distributed_pretraining_tpu.config import TrainingConfig
    from mlx_cuda_distributed_pretraining_tpu.models import llama
    from mlx_cuda_distributed_pretraining_tpu.models.llama import LlamaArgs
    from mlx_cuda_distributed_pretraining_tpu.optim import build_optimizer
    from mlx_cuda_distributed_pretraining_tpu.parallel.context import use_mesh
    from mlx_cuda_distributed_pretraining_tpu.train.train_step import (
        init_train_state,
        make_train_step,
    )

    base = LlamaArgs(vocab_size=64, hidden_size=32, intermediate_size=64,
                     num_layers=2, num_heads=4, num_kv_heads=2, head_dim=8,
                     max_position_embeddings=64)
    ring_args = LlamaArgs(**{**base.__dict__, "attention_type": "ring"})
    params = llama.init_params(jax.random.PRNGKey(0), base)
    tokens = jnp.asarray(np.random.default_rng(0).integers(1, 60, (4, 32)), jnp.int32)

    mesh = _mesh({"dp": 2, "sp": 4})
    with use_mesh(mesh):
        logits_ring, _ = jax.jit(
            lambda p, t: llama.forward(p, t, ring_args))(params, tokens)
    logits_ref, _ = llama.forward(params, tokens, base)
    np.testing.assert_allclose(np.asarray(logits_ring), np.asarray(logits_ref),
                               atol=2e-4, rtol=2e-4)

    # full sharded train step with sp axis
    tr_cfg = TrainingConfig(hyperparameters={"learning_rate": 1e-2},
                            optimization={"optimizer": "adamw"})
    opt = build_optimizer(tr_cfg, 10)
    with use_mesh(mesh):
        step, shardings = make_train_step(
            lambda p, b: llama.loss_fn(p, b, ring_args), opt,
            mesh=mesh, params_like=params)
        state = jax.device_put(init_train_state(params, opt), shardings)
        batch = {
            "inputs": tokens,
            "targets": jnp.roll(tokens, -1, axis=1),
            "mask": jnp.ones((4, 32), jnp.float32),
        }
        state, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"]))


def test_ring_non_divisible_shard_falls_back_exactly():
    """S_local not tileable by the Pallas blocks (e.g. 24 rows) must route
    to the exact jnp path, not silently truncate (r2 review finding)."""
    mesh = _mesh({"sp": 4})
    q, k, v = _qkv(s=768)  # S_local = 192: fit_block gives 128, 192 % 128 != 0
    ring = make_ring_attention(mesh, mask_mod=M.causal())
    out = jax.jit(ring)(q, k, v)
    ref = reference_attention(q, k, v, mask_mod=M.causal())
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5)


def test_flash_raw_entries_reject_non_divisible():
    import pytest as _pytest

    from mlx_cuda_distributed_pretraining_tpu.ops.flash_attention import flash_fwd

    q = jnp.zeros((1, 2, 640, 16), jnp.float32)
    with _pytest.raises(ValueError, match="block-divisible"):
        flash_fwd(q, q, q, block_q=256, block_kv=256)


def test_ring_sliding_window_band_grads_match(flash_path):
    """Forward and gradients through the tiled sliding-window ring at a
    window that makes its second hop a ``band`` chunk (Sl=16, window 24:
    the diagonal, then a band clipped to the corner), under both forward
    kernels; the slow test below walks the other chunk kinds."""
    mesh = _mesh({"sp": 4})
    q, k, v = _qkv(s=64, seed=24)
    ring = make_ring_attention(mesh, mask_mod=M.sliding_window(24))

    def loss_ring(q, k, v):
        return (ring(q, k, v) ** 2).sum()

    def loss_ref(q, k, v):
        return (reference_attention(q, k, v, mask_mod=M.sliding_window(24)) ** 2).sum()

    g_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4, rtol=1e-4)


@pytest.mark.slow
def test_ring_sliding_window_tiled_grads_match():
    """The statically-unrolled tiled sliding-window ring (fwd+bwd custom
    VJP) matches single-device reference gradients, across window sizes
    that hit all three chunk kinds (diagonal / full / band) and the
    early-rotation-stop path (window < S_local)."""
    mesh = _mesh({"sp": 4})
    for window in (8, 24, 40, 64):  # Sl=16: early-stop, band, full+band, all-full
        q, k, v = _qkv(s=64, seed=window)
        ring = make_ring_attention(mesh, mask_mod=M.sliding_window(window))

        def loss_ring(q, k, v):
            return (jax.jit(ring)(q, k, v) ** 2).sum()

        def loss_ref(q, k, v):
            return (reference_attention(q, k, v, mask_mod=M.sliding_window(window)) ** 2).sum()

        g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_ring, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-4, rtol=1e-4,
                                       err_msg=f"window={window}")


def test_ring_sliding_window_gqa(flash_path):
    mesh = _mesh({"dp": 2, "sp": 4})
    q, k, v = _qkv(hq=4, hkv=2, s=64)
    ring = make_ring_attention(mesh, mask_mod=M.sliding_window(20))
    out = jax.jit(ring)(q, k, v)
    ref = reference_attention(q, k, v, mask_mod=M.sliding_window(20))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5)


def test_ring_live_hops_formula():
    """The public early-stop bound matches the kernel's static unroll:
    full causal rings visit all sp chunks; a window smaller than the local
    shard stops after ~2 hops regardless of total sequence length."""
    from mlx_cuda_distributed_pretraining_tpu.ops.ring_attention import ring_live_hops

    assert ring_live_hops(4, 64, None) == 4        # full causal: no early stop
    assert ring_live_hops(4, 64, 96) == 3          # dryrun phase D
    assert ring_live_hops(4, 8192, 1024) == 2      # dryrun phase E (32k/sp4)
    assert ring_live_hops(8, 4096, 1024) == 2      # the 32k/sp8 pitch
    assert ring_live_hops(2, 16, 1000) == 2        # clamped to sp
    # Edge: a row's furthest visible key is window-1 back, so distance-2
    # chunks only come alive once window >= seq_local + 2.
    assert ring_live_hops(4, 64, 64) == 2
    assert ring_live_hops(4, 64, 65) == 2
    assert ring_live_hops(4, 64, 66) == 3
