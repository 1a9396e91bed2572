"""Adafactor — sublinear-memory adaptive optimizer (Shazeer & Stern 2018).

Not in the reference (its optimizer set is adam/adamw/sgd/lion/muon/
shampoo/hybrid): added because Adafactor is THE TPU-native answer to
optimizer-state HBM pressure — the motivating case here is a 1B model
on one chip, where AdamW's fp32 m+v alone is ~7.7 GB of the 16 GB chip while
Adafactor's factored second moments for a [V, D] or [D, I] matrix are one
row vector + one column vector (~KBs). With it, 1B-on-one-chip trains
with batch headroom instead of at the OOM edge.

Semantics mirror ``optax.adafactor`` (verified against it in
tests/test_optim.py, including weight decay under an equivalent mask):
factored RMS with the 1 - t^-0.8 decay schedule, per-block update-RMS
clipping, optional relative (parameter-scale) steps, optional EMA
momentum, decoupled weight decay, final sign flip. ONE deliberate
divergence: weight decay applies this repo's house mask (matrices only —
biases and norm gains are never decayed, optim/base.py::default_wd_mask),
where optax's default decays every param; pass
``weight_decay_mask`` to optax to reproduce. State and math follow
optax's ``scale_by_factored_rms`` (optax/_src/factorized.py); the
implementation below is this repo's Transform style (pure init/update
closures, fp32 state).
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp
import numpy as np

from .base import (
    Schedule,
    Transform,
    add_decayed_weights,
    chain,
    identity,
    maybe_clip,
    scale,
    scale_by_schedule,
    tree_map,
)


class _Leaf:
    """Opaque per-leaf result bundle — a pytree LEAF (plain object), so
    tree_map over (grads, state...) never descends into it (the same
    trick as optax's _UpdateResult dataclass)."""

    __slots__ = ("u", "vr", "vc", "v")

    def __init__(self, u, vr, vc, v):
        self.u, self.vr, self.vc, self.v = u, vr, vc, v


def _factored_dims(shape, min_dim_size_to_factor: int):
    """The two largest axes to reduce over, or None (no factoring) when
    the second-largest dim is below the threshold (mirrors optax)."""
    if len(shape) < 2:
        return None
    sorted_dims = np.argsort(shape)
    if shape[sorted_dims[-2]] < min_dim_size_to_factor:
        return None
    return int(sorted_dims[-2]), int(sorted_dims[-1])


def scale_by_factored_rms(
    decay_rate: float = 0.8,
    min_dim_size_to_factor: int = 128,
    eps: float = 1e-30,
) -> Transform:
    """Scale by a factored estimate of the gradient RMS.

    For a leaf with two dims >= ``min_dim_size_to_factor`` the second
    moment is kept as a (row, col) outer-product estimate — O(n+m) memory
    instead of O(nm); other leaves fall back to a full accumulator.
    Placeholder (1,) zeros fill the unused slots so the three state trees
    stay tree_map-parallel with params (same trick as optax)."""

    def init(params):
        def init_leaf(p):
            f = _factored_dims(p.shape, min_dim_size_to_factor)
            if f is not None:
                d1, d0 = f
                return _Leaf(
                    None,
                    jnp.zeros(tuple(np.delete(p.shape, d0)), jnp.float32),
                    jnp.zeros(tuple(np.delete(p.shape, d1)), jnp.float32),
                    jnp.zeros((1,), jnp.float32),
                )
            return _Leaf(None, jnp.zeros((1,), jnp.float32),
                         jnp.zeros((1,), jnp.float32),
                         jnp.zeros(p.shape, jnp.float32))

        leaves = tree_map(init_leaf, params)
        return {
            "count": jnp.zeros((), jnp.int32),
            "v_row": tree_map(lambda p, t: t.vr, params, leaves),
            "v_col": tree_map(lambda p, t: t.vc, params, leaves),
            "v": tree_map(lambda p, t: t.v, params, leaves),
        }

    def update(grads, state, params):
        count = state["count"]
        # Original power decay: t^-0.8 -> 1; first step uses the raw
        # squared gradient (decay_rate_t == 0).
        t = count.astype(jnp.float32) + 1.0
        decay_rate_t = 1.0 - t ** (-decay_rate)

        def upd(g, v_row, v_col, v):
            g = g.astype(jnp.float32)
            f = _factored_dims(g.shape, min_dim_size_to_factor)
            grad_sqr = jnp.square(g) + eps
            if f is not None:
                d1, d0 = f
                new_v_row = decay_rate_t * v_row \
                    + (1.0 - decay_rate_t) * jnp.mean(grad_sqr, axis=d0)
                new_v_col = decay_rate_t * v_col \
                    + (1.0 - decay_rate_t) * jnp.mean(grad_sqr, axis=d1)
                reduced_d1 = d1 - 1 if d1 > d0 else d1
                row_col_mean = jnp.mean(new_v_row, axis=reduced_d1,
                                        keepdims=True)
                row_factor = (new_v_row / row_col_mean) ** -0.5
                col_factor = new_v_col ** -0.5
                u = (g * jnp.expand_dims(row_factor, axis=d0)
                     * jnp.expand_dims(col_factor, axis=d1))
                return _Leaf(u, new_v_row, new_v_col, v)
            new_v = decay_rate_t * v + (1.0 - decay_rate_t) * grad_sqr
            return _Leaf(g * new_v ** -0.5, v_row, v_col, new_v)

        out = tree_map(upd, grads, state["v_row"], state["v_col"], state["v"])
        pick = lambda attr: tree_map(lambda g, q: getattr(q, attr), grads, out)
        return pick("u"), {"count": count + 1, "v_row": pick("vr"),
                           "v_col": pick("vc"), "v": pick("v")}

    return Transform(init, update)


def clip_update_rms(threshold: float) -> Transform:
    """Per-leaf update-RMS clip (optax clip_by_block_rms): divides each
    leaf by max(1, rms/threshold) — Adafactor's update clipping d=1."""

    def update(updates, state, params):
        def clip(u):
            denom = jnp.maximum(1.0, jnp.sqrt(jnp.mean(jnp.square(u))) / threshold)
            return u / denom

        return tree_map(clip, updates), state

    return Transform(lambda p: {}, update)


def scale_by_param_rms(min_scale: float = 1e-3) -> Transform:
    """Relative step sizes: multiply each leaf's update by
    max(rms(param), min_scale) (optax scale_by_param_block_rms)."""

    def update(updates, state, params):
        def scale(u, p):
            rms = jnp.sqrt(jnp.mean(jnp.square(p.astype(jnp.float32))))
            return u * jnp.maximum(rms, min_scale)

        return tree_map(scale, updates, params), state

    return Transform(lambda p: {}, update)


def ema_of_updates(decay: float) -> Transform:
    """Momentum as an (un-debiased) EMA of the final updates (optax
    transform.ema with debias=False), applied after LR scaling."""

    def init(params):
        return {"ema": tree_map(lambda p: jnp.zeros_like(p, jnp.float32), params)}

    def update(updates, state, params):
        ema = tree_map(lambda e, u: decay * e + (1.0 - decay) * u,
                       state["ema"], updates)
        return ema, {"ema": ema}

    return Transform(init, update)


def adafactor(
    schedule: Schedule,
    weight_decay: float = 0.0,
    decay_rate: float = 0.8,
    clipping_threshold: Optional[float] = 1.0,
    momentum: Optional[float] = None,
    multiply_by_parameter_scale: bool = True,
    min_dim_size_to_factor: int = 128,
    eps: float = 1e-30,
    grad_clip: Optional[float] = None,
) -> Transform:
    """Full Adafactor chain, optax-compatible ordering:
    [global-norm clip] -> factored RMS -> block-RMS clip -> x lr ->
    [x param rms] -> [momentum EMA] -> [+ wd*param] -> x(-1)."""
    parts = [
        maybe_clip(grad_clip),
        scale_by_factored_rms(decay_rate, min_dim_size_to_factor, eps),
        clip_update_rms(clipping_threshold) if clipping_threshold else identity(),
        scale_by_schedule(schedule, flip_sign=False),
        scale_by_param_rms() if multiply_by_parameter_scale else identity(),
        ema_of_updates(momentum) if momentum else identity(),
        # Positioned after lr scaling and before the sign flip, so decay
        # is decoupled from the learning rate (optax adafactor ordering);
        # the house WD mask applies (see module docstring).
        add_decayed_weights(weight_decay) if weight_decay else identity(),
        scale(-1.0),
    ]
    return chain(*parts)
