"""Traceable mask/score modifiers ("flex attention" the TPU way).

The reference's FlexAttention applies ``mask_mod(b, h, q, kv)`` /
``score_mod(score, b, h, q, kv)`` via quadruple-nested Python loops
(reference: models/attention/flex_attention.py:220-275) — untraceable and
O(B·H·S²) Python calls. Here a mod is a **vectorized function of index
arrays**, evaluated (a) on full index lattices for the reference path,
(b) at block granularity to build block-sparsity maps for the Pallas kernel.

A ``MaskMod`` maps broadcastable int32 arrays ``(q_idx, kv_idx)`` → bool
(True = attend). A ``ScoreMod`` maps ``(score, q_idx, kv_idx)`` → score.
Builders below cover the reference's shipped patterns: causal, sliding
window, prefix-LM, document/padding masks, ALiBi and soft-capping.
"""

from __future__ import annotations

from functools import lru_cache
from typing import Callable, Optional

import jax.numpy as jnp
import numpy as np

MaskMod = Callable[[jnp.ndarray, jnp.ndarray], jnp.ndarray]
ScoreMod = Callable[[jnp.ndarray, jnp.ndarray, jnp.ndarray], jnp.ndarray]

NEG_INF = -1e30  # large-but-finite: keeps softmax well-defined on fully-masked rows

# Builders are lru_cached so identical arguments return the identical
# function object — kernel caches (flash_attention._cached_core, jit static
# args) key on function identity.


# -- mask mods --------------------------------------------------------------
# Each named builder tags its mod with ``_plan = (mask_type, window, prefix)``
# so the flash kernel can recover the exact block-sparsity plan
# (``block_diffusion`` says its block length and its copy's rows there).


@lru_cache(maxsize=None)
def causal() -> MaskMod:
    def mod(q, k):
        return q >= k

    mod._plan = ("causal", 0, 0)
    return mod


@lru_cache(maxsize=None)
def full() -> MaskMod:
    def mod(q, k):
        return jnp.ones(jnp.broadcast_shapes(jnp.shape(q), jnp.shape(k)), bool)

    mod._plan = ("full", 0, 0)
    return mod


@lru_cache(maxsize=None)
def sliding_window(window: int, causal_: bool = True) -> MaskMod:
    """Attend to the last ``window`` positions (reference flex tests use this:
    tests/test_flex_attention.py:64-80)."""

    def mod(q, k):
        near = (q - k) < window
        if causal_:
            return (q >= k) & near
        return jnp.abs(q - k) < window

    if causal_:
        mod._plan = ("sliding_window", window, 0)
    return mod


@lru_cache(maxsize=None)
def band(window: int) -> MaskMod:
    """Left band alone: valid iff ``q - k < window``, NO causal bound
    (window may be <= 0). The shape of an off-diagonal rotation chunk in
    sliding-window ring attention, where the inter-chunk offset already
    guarantees causality (ops/ring_attention.py)."""

    def mod(q, k):
        return (q - k) < window

    mod._plan = ("band", window, 0)
    return mod


@lru_cache(maxsize=None)
def prefix_lm(prefix_len: int) -> MaskMod:
    """Bidirectional over the first ``prefix_len`` tokens, causal after."""

    def mod(q, k):
        return (q >= k) | (k < prefix_len)

    mod._plan = ("prefix_lm", 0, prefix_len)
    return mod


def block_index(block_length: int) -> Callable:
    """``r -> r // block_length`` for index arrays that are never negative: a
    shift where it can be one (a vector divide is the slow way to say it inside
    a kernel)."""
    Bp = int(block_length)
    if Bp & (Bp - 1) == 0:
        return lambda r: jnp.right_shift(r, Bp.bit_length() - 1)
    return lambda r: r // Bp


@lru_cache(maxsize=None)
def block_diffusion(seq_len: int, block_length: int) -> MaskMod:
    """The training mask of diffusion over blocks (BD3-LM, arXiv:2503.09573),
    over ``2 * seq_len`` rows: the noised copy of a sequence, then its clean
    copy. With ``blk(r) = (r mod seq_len) // block_length``, query ``q`` sees
    key ``k`` iff

    - both noised: ``blk(k) == blk(q)`` (its own block, both directions);
    - ``q`` noised, ``k`` clean: ``blk(k) < blk(q)`` (every earlier block, clean);
    - both clean: ``blk(k) <= blk(q)`` (causal by block);
    - ``q`` clean, ``k`` noised: never."""
    L, Bp = int(seq_len), int(block_length)
    if L < 1 or Bp < 1 or L % Bp:
        raise ValueError(f"block_diffusion: block length {Bp} does not divide the sequence {L}")

    blk = block_index(Bp)

    def mod(q, k):
        q_clean, k_clean = q >= L, k >= L
        qb = blk(jnp.where(q_clean, q - L, q))
        kb = blk(jnp.where(k_clean, k - L, k))
        # the four cases in and/or alone: Mosaic has no select between booleans
        return (k_clean & (kb < qb)) | ((kb == qb) & ~(q_clean ^ k_clean))

    mod._plan = ("block_diffusion", Bp, L)  # (mask_type, block length, rows a copy)
    return mod


def document_mask(doc_ids: jnp.ndarray) -> MaskMod:
    """Block attention across packed-document boundaries. ``doc_ids`` is a
    per-position int array [S]; same id ⇒ may attend."""

    def mod(q, k):
        return (q >= k) & (doc_ids[q] == doc_ids[k])

    return mod


def and_masks(*mods: MaskMod) -> MaskMod:
    def mod(q, k):
        out = mods[0](q, k)
        for m in mods[1:]:
            out = out & m(q, k)
        return out

    return mod


def or_masks(*mods: MaskMod) -> MaskMod:
    def mod(q, k):
        out = mods[0](q, k)
        for m in mods[1:]:
            out = out | m(q, k)
        return out

    return mod


# -- score mods -------------------------------------------------------------
def alibi(slope: float) -> ScoreMod:
    """ALiBi linear positional bias for one head."""
    return lambda s, q, k: s - slope * jnp.abs(q - k)


def alibi_slopes(num_heads: int) -> np.ndarray:
    """Standard geometric ALiBi slopes per head."""
    base = 2.0 ** (-8.0 / num_heads)
    return base ** np.arange(1, num_heads + 1)


def soft_cap(cap: float) -> ScoreMod:
    return lambda s, q, k: cap * jnp.tanh(s / cap)


def relative_bias(bias_table: jnp.ndarray, max_distance: int) -> ScoreMod:
    def mod(s, q, k):
        d = jnp.clip(q - k, -max_distance, max_distance) + max_distance
        return s + bias_table[d]

    return mod


# -- materialization --------------------------------------------------------
def materialize_mask(mod: Optional[MaskMod], q_len: int, kv_len: int, q_offset: int = 0) -> Optional[jnp.ndarray]:
    """Evaluate a mask mod on the full [q_len, kv_len] lattice. ``q_offset``
    shifts query positions (decode-time: query at absolute position
    offset+i)."""
    if mod is None:
        return None
    q = jnp.arange(q_len, dtype=jnp.int32)[:, None] + q_offset
    k = jnp.arange(kv_len, dtype=jnp.int32)[None, :]
    return mod(q, k)


def block_mask_map(mod: MaskMod, q_len: int, kv_len: int, block_q: int, block_kv: int) -> np.ndarray:
    """Classify each (q-block, kv-block) tile: 0 = skip, 1 = partial (apply
    mask inside kernel), 2 = dense (no masking needed). This is the traceable
    replacement for the reference's block-midpoint sampling heuristic
    (reference: flex_attention.py:90-138), computed exactly via corner/full
    evaluation on the block index lattice."""
    q = np.arange(q_len, dtype=np.int64)
    k = np.arange(kv_len, dtype=np.int64)
    m = np.asarray(materialize_mask(mod, q_len, kv_len))
    nq = (q_len + block_q - 1) // block_q
    nk = (kv_len + block_kv - 1) // block_kv
    out = np.zeros((nq, nk), np.int8)
    for i in range(nq):
        rows = m[i * block_q : (i + 1) * block_q]
        for j in range(nk):
            tile = rows[:, j * block_kv : (j + 1) * block_kv]
            if tile.all():
                out[i, j] = 2
            elif tile.any():
                out[i, j] = 1
    return out
