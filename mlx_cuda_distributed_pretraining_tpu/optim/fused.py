"""Fused AdamW update: one traversal, donation-aliasable, bitwise-equal.

The chained path (enhanced.py ``adamw`` = clip → scale_by_adam →
add_decayed_weights → scale_by_schedule, then ``apply_updates``) walks the
param tree five times and materializes an intermediate ``updates`` tree
between the optimizer and the apply. XLA fuses most of the arithmetic, but
the program still carries full-tree intermediates that (a) block clean
input→output aliasing of the donated params/moments on some leaves and
(b) cost a tree's worth of peak memory between update and apply.

:func:`fused_adamw` keeps the *identical* arithmetic — the same
expressions evaluated in the same order per leaf, so the result is
bitwise equal to the chain (tests/test_fused_optim.py) — but computes
``(new_param, new_mu, new_nu)`` in a single pass over the leaves with no
updates tree. Each output leaf is an elementwise function of the matching
input leaves, which is exactly the shape XLA's buffer-donation pass
aliases: graftaudit's donation-gap on the fused train program is 0 bytes.

Compatibility: :class:`FusedTransform` carries the standard
``(init, update)`` pair delegating to the chain — checkpoints, state
sharding (ZeRO-1), schedule introspection, and every consumer of
``Transform`` see the unchanged four-element chain state
``[{}, {count, mu, nu}, {}, {count}]``. The fused entry point is the
extra ``fused_apply``; train/train_step.py uses it when present.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from .base import Schedule, global_norm, is_vector_like_path
from .enhanced import adamw


class FusedTransform(NamedTuple):
    """A ``Transform`` plus the single-pass ``fused_apply``.

    ``fused_apply(grads, state, params) -> (new_params, new_state)`` —
    the optimizer update and parameter apply in one traversal.
    """

    init: Callable[[Any], Any]
    update: Callable[[Any, Any, Any], tuple]
    fused_apply: Callable[[Any, Any, Any], tuple]


def fused_adamw(
    schedule: Schedule,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
    weight_decay: float = 0.0,
    grad_clip: Optional[float] = None,
    amsgrad: bool = False,
) -> FusedTransform:
    """AdamW with a fused single-pass apply (no EMA — with_ema needs the
    updates tree, so enhanced runs keep the chain)."""
    ref = adamw(schedule, b1=b1, b2=b2, eps=eps, weight_decay=weight_decay,
                grad_clip=grad_clip, amsgrad=amsgrad, ema_decay=None)

    def fused_apply(grads, state, params):
        s_clip, s_adam, s_wd, s_sched = state
        count = s_adam["count"] + 1
        sched_count = s_sched["count"] + 1
        lr = schedule(sched_count)
        cf = count.astype(jnp.float32)
        bc1 = 1 - b1 ** cf
        bc2 = 1 - b2 ** cf
        if grad_clip:
            # same reduction as base.clip_by_global_norm — the one
            # unavoidable extra pass (it is a global reduction)
            with jax.named_scope("grad_clip"):
                norm = global_norm(grads)
                clip_scale = jnp.minimum(1.0, grad_clip / jnp.maximum(norm, 1e-9))

        def leaf(path, p, g, m, v, *vmax):
            # clip → adam → wd → -lr → apply, verbatim expression order
            # from base.py/enhanced.py so the result is bitwise identical
            g32 = g.astype(jnp.float32)
            if grad_clip:
                g32 = g32 * clip_scale
            m_new = b1 * m + (1 - b1) * g32
            v_new = b2 * v + (1 - b2) * jnp.square(g32)
            out = [None, m_new, v_new]
            denom = v_new
            if amsgrad:
                denom = jnp.maximum(vmax[0], v_new)
                out.append(denom)
            u = (m_new / bc1) / (jnp.sqrt(denom / bc2) + eps)
            if weight_decay != 0.0 and jnp.ndim(p) >= 2 \
                    and not is_vector_like_path(path):
                u = u + weight_decay * p.astype(u.dtype)
            u = u * (-lr)
            out[0] = (p.astype(jnp.float32) + u).astype(p.dtype)
            return tuple(out)

        moment_trees = [s_adam["mu"], s_adam["nu"]]
        if amsgrad:
            moment_trees.append(s_adam["nu_max"])
        fused = jax.tree_util.tree_map_with_path(
            leaf, params, grads, *moment_trees)
        is_cell = lambda x: isinstance(x, tuple)
        pick = lambda i: jax.tree_util.tree_map(
            lambda t: t[i], fused, is_leaf=is_cell)
        new_adam = {"count": count, "mu": pick(1), "nu": pick(2)}
        if amsgrad:
            new_adam["nu_max"] = pick(3)
        new_state = [s_clip, new_adam, s_wd, {"count": sched_count}]
        return pick(0), new_state

    return FusedTransform(ref.init, ref.update, fused_apply)


def fused_apply_of(optimizer: Any) -> Optional[Callable]:
    """The optimizer's fused entry point, or None for plain Transforms."""
    return getattr(optimizer, "fused_apply", None)
