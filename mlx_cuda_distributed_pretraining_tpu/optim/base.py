"""Gradient-transform core.

First-party optax-style API: an optimizer is a pure ``(init, update)`` pair
operating on pytrees, so the whole optimizer step jits into the training
step and its state shards like any other pytree (ZeRO-1 falls out for free).
This replaces the reference's stateful ``opt.update(model, grads)`` object
protocol (reference: optimizers/*, mlx_optimizers/*).

Convention: ``update(grads, state, params) -> (updates, new_state)`` where
``new_params = params + updates`` (updates already carry the negative LR).
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp

Schedule = Callable[[jnp.ndarray], jnp.ndarray]  # step -> lr


class Transform(NamedTuple):
    init: Callable[[Any], Any]
    update: Callable[[Any, Any, Any], tuple]  # (grads, state, params) -> (updates, state)


def tree_map(f, *trees, **kwargs):
    return jax.tree_util.tree_map(f, *trees, **kwargs)


def global_norm(tree: Any) -> jnp.ndarray:
    leaves = jax.tree_util.tree_leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32))) for x in leaves))


def apply_updates(params: Any, updates: Any) -> Any:
    return tree_map(lambda p, u: (p.astype(jnp.float32) + u.astype(jnp.float32)).astype(p.dtype), params, updates)


def chain(*transforms: Transform) -> Transform:
    def init(params):
        return [t.init(params) for t in transforms]

    def update(grads, state, params):
        new_state = []
        for t, s in zip(transforms, state):
            grads, s = t.update(grads, s, params)
            new_state.append(s)
        return grads, new_state

    return Transform(init, update)


def identity() -> Transform:
    return Transform(lambda p: {}, lambda g, s, p: (g, s))


def clip_by_global_norm(max_norm: float) -> Transform:
    """Global-norm gradient clipping (reference:
    optimizers/enhanced_optimizers.py:104-119)."""

    def update(grads, state, params):
        with jax.named_scope("grad_clip"):
            norm = global_norm(grads)
            scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-9))
            return tree_map(lambda g: g * scale, grads), state

    return Transform(lambda p: {}, update)


def is_vector_like_path(path) -> bool:
    """True when a pytree key path names a per-layer vector (bias, norm gain)
    regardless of the leaf's rank. Under pipeline parallelism layer params
    are stacked along a leading ``L`` axis, so a norm weight ``[D]`` becomes
    ``[L, D]`` — ndim-based routing would silently treat it as a matrix.
    Routing by name keeps optimizer semantics identical across meshes
    (reference routes bias/norm by name: enhanced_optimizers.py:88-102)."""
    keys = [str(getattr(k, "key", getattr(k, "idx", getattr(k, "name", k)))) for k in path]
    if not keys:
        return False
    last = keys[-1]
    if "bias" in last:
        return True
    if last == "weight" and len(keys) >= 2 and "norm" in keys[-2]:
        return True
    return False


def default_wd_mask(params: Any) -> Any:
    """True where decoupled weight decay applies: only true matrices
    (embeddings/projections); biases and norm gains are skipped by name so
    pipeline-stacked ``[L, D]`` vectors stay excluded (reference:
    enhanced_optimizers.py:88-102 skips bias/norm by name)."""
    return jax.tree_util.tree_map_with_path(
        lambda path, p: jnp.ndim(p) >= 2 and not is_vector_like_path(path), params
    )


def add_decayed_weights(weight_decay: float, mask: Optional[Callable[[Any], Any]] = default_wd_mask) -> Transform:
    def update(grads, state, params):
        if weight_decay == 0.0 or params is None:
            return grads, state
        m = mask(params) if mask is not None else tree_map(lambda p: True, params)
        out = tree_map(
            lambda g, p, use: g + weight_decay * p.astype(g.dtype) if use else g,
            grads, params, m,
        )
        return out, state

    return Transform(lambda p: {}, update)


def scale(factor: float) -> Transform:
    return Transform(lambda p: {}, lambda g, s, p: (tree_map(lambda x: x * factor, g), s))


def scale_by_schedule(schedule: Schedule, flip_sign: bool = True) -> Transform:
    """Multiply by -lr(step); owns the step counter."""

    def init(params):
        return {"count": jnp.zeros((), jnp.int32)}

    def update(grads, state, params):
        count = state["count"] + 1
        lr = schedule(count)
        factor = -lr if flip_sign else lr
        return tree_map(lambda g: g * factor, grads), {"count": count}

    return Transform(init, update)


def trace_momentum(beta: float, nesterov: bool = False) -> Transform:
    def init(params):
        return {"trace": tree_map(lambda p: jnp.zeros_like(p, jnp.float32), params)}

    def update(grads, state, params):
        new_trace = tree_map(lambda t, g: beta * t + g.astype(jnp.float32), state["trace"], grads)
        if nesterov:
            out = tree_map(lambda t, g: beta * t + g.astype(jnp.float32), new_trace, grads)
        else:
            out = new_trace
        return out, {"trace": new_trace}

    return Transform(init, update)


def maybe_clip(max_norm: Optional[float]) -> Transform:
    return clip_by_global_norm(max_norm) if max_norm else identity()


class EmaState(NamedTuple):
    shadow: Any
    inner: Any


def with_ema(inner: Transform, decay: float) -> Transform:
    """Maintain an EMA shadow of the parameters alongside any optimizer
    (reference: enhanced_optimizers.py:67-86). Shadow lives in optimizer
    state; ``ema_params(state)`` extracts it for eval."""

    def init(params):
        return {
            "shadow": tree_map(lambda p: p.astype(jnp.float32), params),
            "inner": inner.init(params),
        }

    def update(grads, state, params):
        updates, inner_state = inner.update(grads, state["inner"], params)
        new_params = apply_updates(params, updates)
        shadow = tree_map(
            lambda s, p: decay * s + (1.0 - decay) * p.astype(jnp.float32),
            state["shadow"], new_params,
        )
        return updates, {"shadow": shadow, "inner": inner_state}

    return Transform(init, update)


def ema_params(state: Any) -> Any:
    return state["shadow"]


def partition(
    label_fn: Callable[[Any], Any], transforms: dict, fallback_label: str = "rest"
) -> Transform:
    """Route different params to different transforms by label
    (optax.multi_transform-style; powers HybridOptimizer — reference:
    optimizers/hybrid_optimizer.py:16-125).

    ``label_fn(params) -> pytree of str labels`` (same structure).
    """

    def _masked(grads, labels, label):
        return tree_map(lambda g, l: g if l == label else None, grads, labels,
                        is_leaf=lambda x: x is None)

    def _merge(parts):
        def pick(*xs):
            for x in xs:
                if x is not None:
                    return x
            return None

        return tree_map(pick, *parts, is_leaf=lambda x: x is None)

    def init(params):
        labels = label_fn(params)
        return {
            k: t.init(_mask_params(params, labels, k)) for k, t in transforms.items()
        }

    def _mask_params(params, labels, label):
        return tree_map(lambda p, l: p if l == label else None, params, labels,
                        is_leaf=lambda x: x is None)

    def update(grads, state, params):
        labels = label_fn(params)
        outs, new_state = [], {}
        for k, t in transforms.items():
            g_k = _masked(grads, labels, k)
            p_k = _mask_params(params, labels, k)
            u_k, s_k = t.update(g_k, state[k], p_k)
            outs.append(u_k)
            new_state[k] = s_k
        return _merge(outs), new_state

    return Transform(init, update)
