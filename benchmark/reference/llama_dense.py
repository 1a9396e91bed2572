"""Plain reference for the dense pre-norm decoder both configurations share.

RMSNorm -> rotary grouped-query attention -> residual -> RMSNorm -> SwiGLU ->
residual, untied input and output tables, no bias. Straightforward
``jax.numpy``: no kernels, no cache, no batching tricks. It imports nothing of
the program and makes its own weights from the seed (``init_params``), so a
fault in the program's initialiser, kernels or cache cannot hide in both.

Departures from a textbook forward, each for memory only and none changing the
mathematics: attention runs one KV-head group at a time and the cross-entropy
one block of positions at a time (``lax.map``), and every layer is
``jax.checkpoint``-ed, so a 16k-token training step at float32 fits beside its
own gradients on one 16 GB chip.

``precision`` selects the arithmetic:

- ``float32``  the reference proper: float32 everywhere, every matmul at
               ``Precision.HIGHEST`` (on a TPU a float32 matmul is otherwise one
               bf16 pass).
- ``bfloat16`` weights, activations and the residual stream in bfloat16, norms
               and softmax internally float32: the control for a configuration
               that states float32.
- ``fp8``      float32, but both operands of every matmul are rounded through
               float8_e4m3 with a per-tensor scale: the control for a
               configuration that states bfloat16 matmuls. Everything else stays
               float32, so it errs less than a real fp8 path would.
- ``float32_default`` float32 with matmuls at the backend's default precision
               (one bf16 pass on a TPU): not a control, a diagnosis of how far
               the arithmetic the program states is from the reference.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

PRECISIONS = ("float32", "bfloat16", "fp8", "float32_default")
INIT_STD = 0.02
CE_BLOCK = 512  # positions per cross-entropy block


def sizes(cfg: Dict[str, Any]) -> Dict[str, int]:
    """The shape-bearing numbers of a configuration file, by short name."""
    return {
        "D": int(cfg["hidden_size"]), "F": int(cfg["intermediate_size"]),
        "L": int(cfg["num_hidden_layers"]), "Hq": int(cfg["num_attention_heads"]),
        "Hkv": int(cfg["num_key_value_heads"]), "Dh": int(cfg["head_dim"]),
        "V": int(cfg["vocab_size"]),
    }


def param_shapes(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """Tree of (shape, init std) in the layout the weights are handed over in:
    matrices are [in, out]; ``layers`` is a list of per-layer dicts."""
    z = sizes(cfg)
    D, F, L, Hq, Hkv, Dh, V = (z[k] for k in ("D", "F", "L", "Hq", "Hkv", "Dh", "V"))
    res_std = INIT_STD / (2 * L) ** 0.5  # residual outputs, GPT-2 style
    layer = {
        "attention_norm": {"weight": ((D,), None)},
        "attention": {
            "wq": {"weight": ((D, Hq * Dh), INIT_STD)},
            "wk": {"weight": ((D, Hkv * Dh), INIT_STD)},
            "wv": {"weight": ((D, Hkv * Dh), INIT_STD)},
            "wo": {"weight": ((Hq * Dh, D), res_std)},
        },
        "ffn_norm": {"weight": ((D,), None)},
        "feed_forward": {
            "w_gate": {"weight": ((D, F), INIT_STD)},
            "w_up": {"weight": ((D, F), INIT_STD)},
            "w_down": {"weight": ((F, D), res_std)},
        },
    }
    return {
        "tok_embeddings": {"weight": ((V, D), INIT_STD)},
        "layers": [layer for _ in range(L)],
        "norm": {"weight": ((D,), None)},
        "output": {"weight": ((D, V), INIT_STD)},
    }


def _is_spec(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def make_params(seed, cfg: Dict[str, Any]):
    """Float32 weights from ``seed`` (a traced or concrete uint32 scalar):
    normal(0, std) matrices, ones for norm gains. Each leaf draws from the key
    folded with its index in the flattened tree, so a value depends on the seed
    and the leaf alone, never on how the tree is sharded."""
    specs = param_shapes(cfg)
    leaves, treedef = jax.tree_util.tree_flatten(specs, is_leaf=_is_spec)
    key = jax.random.PRNGKey(seed)
    out = []
    for i, (shape, std) in enumerate(leaves):
        if std is None:
            out.append(jnp.ones(shape, jnp.float32))
        else:
            out.append(jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32) * std)
    return jax.tree_util.tree_unflatten(treedef, out)


def init_params(seed: int, cfg: Dict[str, Any], shardings=None):
    """All weights in one jitted call on the device, optionally laid out by a
    tree of shardings."""
    fn = jax.jit(functools.partial(make_params, cfg=cfg), out_shardings=shardings)
    return fn(jnp.uint32(seed % (2 ** 32)))


# -- arithmetic ---------------------------------------------------------------
def _fp8_round(a):
    """Round through float8_e4m3 with a per-tensor scale; the gradient passes
    straight through the rounding, as fp8 training recipes take it."""
    s = 448.0 / jnp.maximum(jnp.max(jnp.abs(a)), 1e-30)
    rounded = (a * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s
    return a + jax.lax.stop_gradient(rounded - a)


def _mm(a, b, spec: str, precision: str):
    """einsum with the mode's operand treatment; float32 accumulation."""
    if precision == "fp8":
        a, b = _fp8_round(a), _fp8_round(b)
    out = jnp.einsum(spec, a, b, preferred_element_type=jnp.float32,
                     precision=(jax.lax.Precision.DEFAULT if precision == "float32_default"
                                else jax.lax.Precision.HIGHEST))
    return out.astype(jnp.bfloat16) if precision == "bfloat16" else out


def _rms_norm(x, w, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def _rope(x, positions, theta):
    """Half-split rotary embedding on [B, S, H, Dh]."""
    dh = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., : dh // 2], xf[..., dh // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1).astype(x.dtype)


def _attention(q, k, v, precision):
    """Causal grouped-query attention, one KV head (and its query group) at a
    time. q [B,S,Hq,Dh], k/v [B,S,Hkv,Dh] -> [B,S,Hq*Dh]."""
    B, S, Hq, Dh = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    qg = q.reshape(B, S, Hkv, G, Dh).transpose(2, 0, 3, 1, 4)  # [Hkv,B,G,S,Dh]
    kg = k.transpose(2, 0, 1, 3)                               # [Hkv,B,S,Dh]
    vg = v.transpose(2, 0, 1, 3)
    causal = jnp.tril(jnp.ones((S, S), bool))

    @jax.checkpoint
    def group(args):
        qh, kh, vh = args
        s = _mm(qh, kh, "bgqd,bkd->bgqk", precision).astype(jnp.float32) * (Dh ** -0.5)
        s = jnp.where(causal[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1).astype(vh.dtype)
        return _mm(p, vh, "bgqk,bkd->bgqd", precision)

    out = jax.lax.map(group, (qg, kg, vg))                     # [Hkv,B,G,S,Dh]
    return out.transpose(1, 3, 0, 2, 4).reshape(B, S, Hq * Dh)


def _layer(p, x, positions, cfg, precision):
    z = sizes(cfg)
    B, S, _ = x.shape
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    h = _rms_norm(x, p["attention_norm"]["weight"], eps)
    a = p["attention"]
    q = _mm(h, a["wq"]["weight"], "bsd,de->bse", precision).reshape(B, S, z["Hq"], z["Dh"])
    k = _mm(h, a["wk"]["weight"], "bsd,de->bse", precision).reshape(B, S, z["Hkv"], z["Dh"])
    v = _mm(h, a["wv"]["weight"], "bsd,de->bse", precision).reshape(B, S, z["Hkv"], z["Dh"])
    o = _attention(_rope(q, positions, theta), _rope(k, positions, theta), v, precision)
    x = x + _mm(o, a["wo"]["weight"], "bse,ed->bsd", precision)
    h = _rms_norm(x, p["ffn_norm"]["weight"], eps)
    f = p["feed_forward"]
    up = jax.nn.silu(_mm(h, f["w_gate"]["weight"], "bsd,df->bsf", precision)) \
        * _mm(h, f["w_up"]["weight"], "bsd,df->bsf", precision)
    return x + _mm(up, f["w_down"]["weight"], "bsf,fd->bsd", precision)


def hidden_states(params, tokens, cfg, precision: str = "float32"):
    """tokens [B, S] -> final normed hidden states [B, S, D]."""
    if precision not in PRECISIONS:
        raise ValueError(f"unknown precision {precision!r}")
    if precision == "bfloat16":
        params = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), params)
    x = params["tok_embeddings"]["weight"][tokens]
    positions = jnp.arange(tokens.shape[1], dtype=jnp.int32)
    layer = jax.checkpoint(functools.partial(_layer, cfg=cfg, precision=precision))
    for p in params["layers"]:
        x = layer(p, x, positions)
    return _rms_norm(x, params["norm"]["weight"], float(cfg["rms_norm_eps"]))


def logits_at(params, tokens, cfg, precision: str = "float32", first: int = 0):
    """Float32 logits [B, S-first, V] for positions ``first``.. of tokens."""
    h = hidden_states(params, tokens, cfg, precision)[:, first:]
    w = params["output"]["weight"]
    if precision == "bfloat16":
        w = w.astype(jnp.bfloat16)
    return _mm(h, w, "bsd,dv->bsv", precision).astype(jnp.float32)


def loss(params, inputs, targets, cfg, precision: str = "float32"):
    """Mean cross-entropy over every position of [B, S] (the jobs mask none),
    the output projection and log-softmax taken CE_BLOCK positions at a time."""
    h = hidden_states(params, inputs, cfg, precision)
    w = params["output"]["weight"]
    if precision == "bfloat16":
        w = w.astype(jnp.bfloat16)
    B, S, D = h.shape
    blk = min(CE_BLOCK, S)
    if S % blk:
        raise ValueError(f"sequence {S} is not a multiple of {blk}")
    hb = h.reshape(B, S // blk, blk, D).transpose(1, 0, 2, 3)
    tb = targets.reshape(B, S // blk, blk).transpose(1, 0, 2)

    @jax.checkpoint
    def block(args):
        hh, tt = args
        lg = _mm(hh, w, "bsd,dv->bsv", precision).astype(jnp.float32)
        gold = jnp.take_along_axis(lg, tt[..., None], axis=-1)[..., 0]
        return jnp.sum(jax.nn.logsumexp(lg, axis=-1) - gold)

    return jnp.sum(jax.lax.map(block, (hb, tb))) / (B * S)


def loss_and_grads(params, inputs, targets, cfg, precision: str = "float32"):
    return jax.value_and_grad(loss)(params, inputs, targets, cfg, precision)


def served_token_gaps(params, tokens, start, served, cfg, control: Optional[str] = None):
    """For one request: ``tokens`` [1, T] is prompt + served tokens (padded at
    the end, which causal attention never sees), ``served`` [n] the tokens the
    program emitted, the first predicted by position ``start``. Returns, per
    served position, how far the served token's reference logit lies below the
    reference's best, and the reference's log-probability of the served token; with
    ``control``, also that gap for the token a forward in the control
    precision puts first at each position, and the control's log-probability
    of the served token."""
    n = served.shape[0]
    pick = lambda lg, tok: jnp.take_along_axis(lg, tok[:, None], axis=-1)[:, 0]
    h = hidden_states(params, tokens, cfg, "float32")
    hs = jax.lax.dynamic_slice_in_dim(h, start, n, axis=1)
    lg = _mm(hs, params["output"]["weight"], "bsd,dv->bsv", "float32")[0]
    best = jnp.max(lg, axis=-1)
    out = {"gap": best - pick(lg, served),
           "logprob": pick(jax.nn.log_softmax(lg, axis=-1), served)}
    if control is None:
        return out
    hc = hidden_states(params, tokens, cfg, control)
    hcs = jax.lax.dynamic_slice_in_dim(hc, start, n, axis=1)
    wc = params["output"]["weight"]
    if control == "bfloat16":
        wc = wc.astype(jnp.bfloat16)
    lgc = _mm(hcs, wc, "bsd,dv->bsv", control)[0].astype(jnp.float32)
    out["control_gap"] = best - pick(lg, jnp.argmax(lgc, axis=-1))
    out["control_logprob"] = pick(jax.nn.log_softmax(lgc, axis=-1), served)
    return out
