"""Reference (einsum) attention — the correctness baseline.

Equivalent capability to the reference's SimpleAttention / full-matrix
"flash" (reference: models/attention/simple_attention.py,
flash_attention.py:134-151) but fully vectorized and traceable: GQA handled
by reshaping to head groups (no materialized repeat), fp32 softmax, mask and
score mods applied on index lattices.

Layout convention throughout the framework: ``q [B, Sq, Hq, D]``,
``k/v [B, Skv, Hkv, D]`` with Hq a multiple of Hkv; v's head size may
differ from q's and k's (latent attention), and is then the output's.

:func:`attention_core` is where a model's training path chooses between this
and the flash kernels (ops/flash_attention.py): one mask description, the
scopes a profile's readers key on, and the tally of what was traced.
"""

from __future__ import annotations

import collections
import contextlib
import threading
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from . import masks as masks_lib
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


# -- which kernel runs a core -------------------------------------------------------
def named_mask_mod(mask_type: str = "causal", window_size: int = 512,
                   prefix_len: int = 0) -> MaskMod:
    """The ``masks`` closure of a mask said as the flash kernels take it."""
    if mask_type == "sliding_window":
        return masks_lib.sliding_window(window_size)
    if mask_type == "prefix_lm":
        return masks_lib.prefix_lm(prefix_len)
    if mask_type == "block_diffusion":  # blocks of window_size in two copies of prefix_len rows
        return masks_lib.block_diffusion(prefix_len, window_size)
    return masks_lib.causal()


# A kind of layer's name in the tally -> the scope its core runs under, each name
# spelled out (tests/test_scopes.py reads the vocabulary from the source): a cross
# layer reads the global layer's keys and values under the global layer's mask.
_KIND_SCOPE = {None: contextlib.nullcontext,
               "window": lambda: jax.named_scope("attn_window"),
               "global": lambda: jax.named_scope("attn_global"),
               "cross": lambda: jax.named_scope("attn_global"),
               "blockdiff": lambda: jax.named_scope("attn_blockdiff")}
_FLASH_KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")

# Layers traced by kind, and for each kind the path ``flash_plan`` gives each of the
# three kernels at the traced shapes (``window_fwd_resident`` ...; ``*_simple`` where
# the model runs without the kernels). Counts traces, as the other tallies do: a
# scanned stack counts each kind once.
_core_counts: Dict[str, int] = collections.Counter()
_core_counts_lock = threading.Lock()


def core_counts() -> Dict[str, int]:
    with _core_counts_lock:
        return dict(_core_counts)


def _count_core(kind: str, q, k, v, flash: bool) -> None:
    keys = [f"{kind}_layers"]
    if flash:
        from .flash_attention import flash_plan

        keys += [f"{kind}_{kernel[len('flash_'):]}_"
                 f"{flash_plan(q.shape[1], k.shape[1], q.shape[3], q.dtype, kernel=kernel, Dv=v.shape[3]).path}"
                 for kernel in _FLASH_KERNELS]
    else:
        keys.append(f"{kind}_simple")
    with _core_counts_lock:
        _core_counts.update(keys)


def attention_core(q, k, v, attention_type: str, kind: Optional[str] = None,
                   scale: Optional[float] = None, precision: Optional[str] = None,
                   mask_type: str = "causal", window_size: int = 512, prefix_len: int = 0):
    """One attention core of the training path, on ``q [B, S, Hq, D]``, ``k``,
    ``v``: the flash kernels where ``attention_type`` is ``"flash"``, else
    :func:`reference_attention`, under the mask said once, as the kernels take
    it (``mask_type="sliding_window", window_size=w``; ``mask_type="prefix_lm",
    prefix_len=p``; ``mask_type="block_diffusion", window_size=b``: blocks of
    ``b`` in the two copies that are the halves of ``S``; nothing: causal). ``kind``
    (``window | global | cross | blockdiff``) puts the core under its kind's scope above
    ``attn_core`` and adds the layer and its kernels' paths to
    :func:`core_counts`; a model of one kind of layer names none."""
    flash = attention_type == "flash"
    if mask_type == "block_diffusion":
        prefix_len = q.shape[1] // 2
    if kind is not None:
        _count_core(kind, q, k, v, flash)
    with _KIND_SCOPE[kind](), jax.named_scope("attn_core"):
        if flash:
            from .flash_attention import flash_attention

            return flash_attention(q, k, v, mask_type=mask_type, window_size=window_size,
                                   prefix_len=prefix_len, scale=scale, precision=precision)
        return reference_attention(q, k, v, scale=scale,
                                   mask_mod=named_mask_mod(mask_type, window_size, prefix_len))
