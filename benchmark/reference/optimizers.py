"""Plain optimizers for the reference's training steps, written from the
papers and from the settings a job file states: Adafactor (Shazeer & Stern
2018, as optax arranges it) and AdamW (Loshchilov & Hutter 2019). Both clip
the gradient by its global norm first, keep float32 state, and take a constant
learning rate. ``first_gradient_norms`` reads, from the state after one step,
the per-leaf norm of the gradient as the update rule received it (after
clipping), which is what the program's own state is read for too.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

tree_map = jax.tree_util.tree_map


def _global_norm(tree) -> jnp.ndarray:
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in jax.tree_util.tree_leaves(tree)))


def _clip(grads, max_norm):
    if not max_norm:
        return grads
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(_global_norm(grads), 1e-9))
    return tree_map(lambda g: g * scale, grads)


def factored_axes(shape, min_dim: int = 128):
    """(second-largest axis, largest axis) to average over, or None."""
    if len(shape) < 2:
        return None
    order = np.argsort(shape)
    if shape[order[-2]] < min_dim:
        return None
    return int(order[-2]), int(order[-1])


# -- Adafactor -----------------------------------------------------------------
def adafactor_init(params):
    def leaf(p):
        f = factored_axes(p.shape)
        if f is None:
            return {"v": jnp.zeros(p.shape, jnp.float32)}
        d1, d0 = f
        return {"v_row": jnp.zeros(tuple(np.delete(p.shape, d0)), jnp.float32),
                "v_col": jnp.zeros(tuple(np.delete(p.shape, d1)), jnp.float32)}
    return {"count": jnp.zeros((), jnp.int32), "leaves": tree_map(leaf, params)}


def adafactor_step(params, grads, state, hp: Dict[str, Any]):
    """clip -> factored RMS (decay 1 - t^-0.8) -> per-leaf update-RMS clip ->
    x lr -> x max(rms(param), 1e-3) -> subtract."""
    lr, eps = float(hp["learning_rate"]), 1e-30
    decay_rate = float(hp.get("decay_rate", 0.8))
    threshold = float(hp.get("clipping_threshold", 1.0))
    grads = _clip(grads, hp.get("gradient_clip"))
    t = state["count"].astype(jnp.float32) + 1.0
    beta = 1.0 - t ** (-decay_rate)

    def leaf(p, g, s):
        g = g.astype(jnp.float32)
        g2 = jnp.square(g) + eps
        f = factored_axes(g.shape)
        if f is None:
            v = beta * s["v"] + (1.0 - beta) * g2
            u, new = g * v ** -0.5, {"v": v}
        else:
            d1, d0 = f
            v_row = beta * s["v_row"] + (1.0 - beta) * jnp.mean(g2, axis=d0)
            v_col = beta * s["v_col"] + (1.0 - beta) * jnp.mean(g2, axis=d1)
            rd1 = d1 - 1 if d1 > d0 else d1
            row = (v_row / jnp.mean(v_row, axis=rd1, keepdims=True)) ** -0.5
            u = g * jnp.expand_dims(row, d0) * jnp.expand_dims(v_col ** -0.5, d1)
            new = {"v_row": v_row, "v_col": v_col}
        u = u / jnp.maximum(1.0, jnp.sqrt(jnp.mean(jnp.square(u))) / threshold)
        u = u * lr * jnp.maximum(jnp.sqrt(jnp.mean(jnp.square(p))), 1e-3)
        return p - u, new

    is_state = lambda x: isinstance(x, dict) and ("v" in x or "v_row" in x)
    flat_p, treedef = jax.tree_util.tree_flatten(params)
    flat_g = treedef.flatten_up_to(grads)
    flat_s = jax.tree_util.tree_leaves(state["leaves"], is_leaf=is_state)
    out = [leaf(p, g, s) for p, g, s in zip(flat_p, flat_g, flat_s)]
    new_params = jax.tree_util.tree_unflatten(treedef, [o[0] for o in out])
    new_leaves = jax.tree_util.tree_unflatten(treedef, [o[1] for o in out])
    return new_params, {"count": state["count"] + 1, "leaves": new_leaves}


def adafactor_first_gradient_norms(state, params_like):
    """At t = 1 the decay is 0, so v_row = mean(g^2, largest axis): its sum
    times that axis' length is |g|^2 (the 1e-30 epsilon is below float32)."""
    is_state = lambda x: isinstance(x, dict) and ("v" in x or "v_row" in x)
    flat_p, treedef = jax.tree_util.tree_flatten(params_like)
    flat_s = jax.tree_util.tree_leaves(state["leaves"], is_leaf=is_state)
    out = []
    for p, s in zip(flat_p, flat_s):
        f = factored_axes(p.shape)
        if f is None:
            out.append(jnp.sqrt(jnp.sum(s["v"])))
        else:
            out.append(jnp.sqrt(jnp.sum(s["v_row"]) * p.shape[f[1]]))
    return jax.tree_util.tree_unflatten(treedef, out)


def _profile(g2):
    """Row and column means of a squared gradient (all of it for a vector)."""
    f = factored_axes(g2.shape)
    if f is None:
        return g2.ravel()
    return jnp.concatenate([jnp.mean(g2, axis=f[1]).ravel(), jnp.mean(g2, axis=f[0]).ravel()])


def adafactor_first_gradient_profiles(state, params_like, hp=None):
    """Per leaf, the step-1 second-moment statistics themselves: at t = 1 they
    are the row and column means of the squared gradient (the whole squared
    gradient for a vector). Thousands of numbers a leaf, so their relative
    difference from the reference's is steady from seed to seed, where the gap
    of two norms is one noisy number."""
    is_state = lambda x: isinstance(x, dict) and ("v" in x or "v_row" in x)
    flat_s = jax.tree_util.tree_leaves(state["leaves"], is_leaf=is_state)
    return [s["v"].ravel() if "v" in s else
            jnp.concatenate([s["v_row"].ravel(), s["v_col"].ravel()]) for s in flat_s]


# -- AdamW ---------------------------------------------------------------------
def adamw_init(params):
    zeros = lambda p: jnp.zeros(p.shape, jnp.float32)
    return {"count": jnp.zeros((), jnp.int32), "mu": tree_map(zeros, params),
            "nu": tree_map(zeros, params)}


def adamw_step(params, grads, state, hp: Dict[str, Any]):
    """clip -> Adam moments with bias correction -> decoupled weight decay on
    matrices (never on norm gains) -> x lr -> subtract."""
    lr = float(hp["learning_rate"])
    b1, b2 = (float(b) for b in hp.get("betas", (0.9, 0.999)))
    eps, wd = float(hp.get("eps", 1e-8)), float(hp.get("weight_decay", 0.0))
    grads = _clip(grads, hp.get("gradient_clip"))
    count = state["count"] + 1
    c = count.astype(jnp.float32)
    mu = tree_map(lambda m, g: b1 * m + (1 - b1) * g.astype(jnp.float32), state["mu"], grads)
    nu = tree_map(lambda v, g: b2 * v + (1 - b2) * jnp.square(g.astype(jnp.float32)),
                  state["nu"], grads)

    def leaf(p, m, v):
        u = (m / (1 - b1 ** c)) / (jnp.sqrt(v / (1 - b2 ** c)) + eps)
        if wd and p.ndim >= 2:
            u = u + wd * p
        return p - lr * u

    return tree_map(leaf, params, mu, nu), {"count": count, "mu": mu, "nu": nu}


def adamw_first_gradient_norms(state, params_like, hp: Dict[str, Any]):
    """After one step mu = (1 - b1) g."""
    b1 = float(hp.get("betas", (0.9, 0.999))[0])
    return tree_map(lambda m: jnp.sqrt(jnp.sum(jnp.square(m))) / (1 - b1), state["mu"])


def adamw_first_gradient_profiles(state, params_like, hp: Dict[str, Any]):
    """The same row and column means, from mu = (1 - b1) g after one step."""
    b1 = float(hp.get("betas", (0.9, 0.999))[0])
    return [_profile(jnp.square(m / (1 - b1))) for m in jax.tree_util.tree_leaves(state["mu"])]


OPTIMIZERS = {
    "adafactor": (adafactor_init, adafactor_step,
                  lambda s, p, hp: adafactor_first_gradient_norms(s, p),
                  adafactor_first_gradient_profiles),
    "adamw": (adamw_init, adamw_step, adamw_first_gradient_norms,
              adamw_first_gradient_profiles),
}


def get(name: str) -> Tuple[Any, Any, Any, Any]:
    try:
        return OPTIMIZERS[name]
    except KeyError:
        raise ValueError(f"the reference has no optimizer {name!r}; "
                         f"it knows {sorted(OPTIMIZERS)}") from None
