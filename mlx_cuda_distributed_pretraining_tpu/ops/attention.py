"""Reference (einsum) attention — the correctness baseline.

Equivalent capability to the reference's SimpleAttention / full-matrix
"flash" (reference: models/attention/simple_attention.py,
flash_attention.py:134-151) but fully vectorized and traceable: GQA handled
by reshaping to head groups (no materialized repeat), fp32 softmax, mask and
score mods applied on index lattices.

Layout convention throughout the framework: ``q [B, Sq, Hq, D]``,
``k/v [B, Skv, Hkv, D]`` with Hq a multiple of Hkv; v's head size may
differ from q's and k's (latent attention), and is then the output's.
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from .masks import NEG_INF, MaskMod, ScoreMod, materialize_mask


def reference_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mask_mod: Optional[MaskMod] = None,
    score_mod: Optional[ScoreMod] = None,
    scale: Optional[float] = None,
    q_offset: int = 0,
    explicit_mask: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Multi-head attention with GQA and traceable mask/score mods.

    ``explicit_mask`` ([Sq, Skv] or broadcastable bool, True = attend) is an
    alternative to ``mask_mod`` for precomputed masks (e.g. padding).
    """
    B, Sq, Hq, D = q.shape
    _, Skv, Hkv, _ = k.shape
    if Hq % Hkv != 0:
        raise ValueError(f"query heads {Hq} not a multiple of kv heads {Hkv}")
    G = Hq // Hkv
    scale = (D ** -0.5) if scale is None else scale

    # [B, Hkv, G, Sq, D] x [B, Hkv, Skv, D] -> [B, Hkv, G, Sq, Skv]
    qg = q.transpose(0, 2, 1, 3).reshape(B, Hkv, G, Sq, D)
    kh = k.transpose(0, 2, 1, 3)
    vh = v.transpose(0, 2, 1, 3)
    scores = jnp.einsum("bhgqd,bhkd->bhgqk", qg, kh) * scale
    scores = scores.astype(jnp.float32)

    if score_mod is not None:
        q_idx = jnp.arange(Sq, dtype=jnp.int32)[:, None] + q_offset
        k_idx = jnp.arange(Skv, dtype=jnp.int32)[None, :]
        scores = score_mod(scores, q_idx, k_idx)

    m = explicit_mask
    if mask_mod is not None:
        mm = materialize_mask(mask_mod, Sq, Skv, q_offset)
        m = mm if m is None else (m & mm)
    if m is not None:
        scores = jnp.where(m, scores, NEG_INF)

    probs = jnp.exp(scores - jnp.max(scores, axis=-1, keepdims=True))
    probs = probs / jnp.sum(probs, axis=-1, keepdims=True)
    probs = probs.astype(v.dtype)

    out = jnp.einsum("bhgqk,bhkd->bhgqd", probs, vh)
    return out.reshape(B, Hq, Sq, v.shape[-1]).transpose(0, 2, 1, 3)  # v may be narrower than q and k
