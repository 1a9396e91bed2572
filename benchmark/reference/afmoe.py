"""Plain reference for the decoder whose layers differ in mask and position
encoding, with gated QK-normed attention and a sigmoid-routed expert layer
(``architecture: afmoe``).

Written from the equations in ISSUE 33 ("Layer equations"; the configuration
file's ``equations`` repeats where they come from), in straightforward
``jax.numpy``: no kernels, no sort, no buffers. It imports nothing of the
program and makes its own weights from the seed, in the tree the program
trains (``dense_layers`` and ``layers`` are lists of per-layer dicts).

A layer of type ``sliding_attention`` rotates q and k (half-split RoPE over
the whole head) and lets query ``i`` see keys ``j <= i`` with ``i - j <
sliding_window``; a ``full_attention`` layer has no position encoding and sees
every ``j <= i``. Which is which comes from the configuration's
``layer_types``, entry by entry.

One routed layer as the configuration cuts it: the router is as wide as
published, the weights are normalised over all chosen experts, and only the
``experts_held`` (first, count) add to the output: each held expert is applied
to every token and weighted by that token's gate for it (zero where it was not
chosen). What absent experts would add is left out, here as in the program.

Departures from a textbook forward, for memory only: attention is an explicit
masked softmax over whole key rows, taken one key/value group and one block of
``ATTN_BLOCK`` query rows at a time, the held experts one at a time, the
cross-entropy one block of positions at a time (``lax.map`` / ``lax.scan``
over ``jax.checkpoint``-ed bodies), and every layer is ``jax.checkpoint``-ed:
at 16,384 positions the float32 scores of 8 query heads over 1,024 rows are
0.5 GB, which fits beside float32 weights and gradients on a 16 GB chip; the
whole square does not.

``precision`` as in ``llama_dense.py``: ``float32`` (matmuls at HIGHEST),
``fp8`` (both operands of every matmul rounded through float8_e4m3; the
control), ``bfloat16``, ``float32_default``.
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

from benchmark.reference.llama_dense import (CE_BLOCK, INIT_STD, PRECISIONS, _is_spec, _mm,
                                             _rms_norm, _rope)

ROUTER_BIAS_STD = 0.01
ATTN_BLOCK = 1024  # query rows of one block of explicit scores
SLIDING, FULL = "sliding_attention", "full_attention"


def sizes(cfg: Dict[str, Any]) -> Dict[str, int]:
    held = cfg["experts_held"]
    z = {
        "C": int(cfg["hidden_size"]), "F": int(cfg["intermediate_size"]),
        "L": int(cfg["num_hidden_layers"]), "Ld": int(cfg["num_dense_layers"]),
        "H": int(cfg["num_attention_heads"]), "G": int(cfg["num_key_value_heads"]),
        "D": int(cfg["head_dim"]), "V": int(cfg["vocab_size"]), "E": int(cfg["num_experts"]),
        "K": int(cfg["num_experts_per_tok"]), "Fe": int(cfg["moe_intermediate_size"]),
        "Ns": int(cfg["num_shared_experts"]), "W": int(cfg["sliding_window"]),
        "first": int(held["first"]), "held": int(held["count"]),
    }
    types = list(cfg["layer_types"])
    if len(types) != z["L"] or set(types) - {SLIDING, FULL}:
        raise ValueError(f"layer_types must name {z['L']} layers of {SLIDING!r} or {FULL!r}")
    return z


def param_shapes(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """Tree of (shape, init): a float is normal(0, std), None ones."""
    z = sizes(cfg)
    C, H, G, D = z["C"], z["H"], z["G"], z["D"]
    res_std = INIT_STD / (2 * z["L"]) ** 0.5
    w = lambda shape, init: {"weight": (shape, init)}

    def swiglu(width, lead=()):
        return {"w_gate": w(lead + (C, width), INIT_STD), "w_up": w(lead + (C, width), INIT_STD),
                "w_down": w(lead + (width, C), res_std)}

    def layer(routed):
        ff = swiglu(z["F"])
        if routed:
            ff = {"router": {"weight": ((C, z["E"]), INIT_STD), "bias": ((z["E"],), ROUTER_BIAS_STD)},
                  "shared": swiglu(z["Ns"] * z["Fe"]),
                  "experts": swiglu(z["Fe"], (z["held"],))}
        return {
            "attention_norm": w((C,), None),
            "attention": {
                "wq": w((C, H * D), INIT_STD), "wk": w((C, G * D), INIT_STD),
                "wv": w((C, G * D), INIT_STD), "wg": w((C, H * D), INIT_STD),
                "q_norm": w((D,), None), "k_norm": w((D,), None), "wo": w((H * D, C), res_std)},
            "post_attention_norm": w((C,), None), "ffn_norm": w((C,), None), "feed_forward": ff,
            "post_ffn_norm": w((C,), None),
        }

    return {
        "tok_embeddings": w((z["V"], C), INIT_STD),
        "dense_layers": [layer(False) for _ in range(z["Ld"])],
        "layers": [layer(True) for _ in range(z["L"] - z["Ld"])],
        "norm": w((C,), None),
        "output": w((C, z["V"]), INIT_STD),
    }


def make_params(seed, cfg: Dict[str, Any]):
    """Float32 weights from ``seed`` (a traced or concrete uint32 scalar); each
    leaf draws from the key folded with its index in the flattened tree."""
    leaves, treedef = jax.tree_util.tree_flatten(param_shapes(cfg), is_leaf=_is_spec)
    key = jax.random.PRNGKey(seed)
    out = [jnp.ones(shape, jnp.float32) if init is None
           else jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32) * init
           for i, (shape, init) in enumerate(leaves)]
    return jax.tree_util.tree_unflatten(treedef, out)


def init_params(seed: int, cfg: Dict[str, Any], shardings=None):
    fn = jax.jit(functools.partial(make_params, cfg=cfg), out_shardings=shardings)
    return fn(jnp.uint32(seed % (2 ** 32)))


# -- arithmetic -----------------------------------------------------------------
def _attention(p, x, cfg, precision, layer_type):
    z = sizes(cfg)
    B, S, _ = x.shape
    H, G, D, W = z["H"], z["G"], z["D"], z["W"]
    eps = float(cfg["rms_norm_eps"])
    proj = lambda name, heads: _mm(x, p[name]["weight"], "bsc,ce->bse", precision).reshape(
        B, S, heads, D)
    q = _rms_norm(proj("wq", H), p["q_norm"]["weight"], eps)
    k = _rms_norm(proj("wk", G), p["k_norm"]["weight"], eps)
    v = proj("wv", G)
    gate = jax.nn.sigmoid(_mm(x, p["wg"]["weight"], "bsc,ce->bse", precision).astype(jnp.float32))
    if layer_type == SLIDING:
        positions = jnp.arange(S)
        q, k = (_rope(a, positions, float(cfg["rope_theta"])) for a in (q, k))
    blk = min(ATTN_BLOCK, S)
    if S % blk:
        raise ValueError(f"sequence {S} is not a multiple of {blk}")
    cols = jnp.arange(S)[None, :]

    @jax.checkpoint
    def rows(args):
        qb, kg, vg, row0 = args                                   # [B, blk, H/G, D], [B, S, D]
        i = row0 + jnp.arange(blk)[:, None]
        seen = cols <= i
        if layer_type == SLIDING:
            seen = seen & (i - cols < W)
        s = _mm(qb, kg, "bqhd,bkd->bhqk", precision).astype(jnp.float32) * D ** -0.5
        pr = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), axis=-1).astype(vg.dtype)
        return _mm(pr, vg, "bhqk,bkd->bqhd", precision)

    def group(args):
        qg, kg, vg = args                                         # [B, S, H/G, D], [B, S, D]
        n = S // blk
        blocks = qg.reshape(B, n, blk, H // G, D).swapaxes(0, 1)
        o = jax.lax.map(lambda a: rows((a[0], kg, vg, a[1])), (blocks, jnp.arange(n) * blk))
        return o.swapaxes(0, 1).reshape(B, S, H // G, D)

    by_group = q.reshape(B, S, G, H // G, D).transpose(2, 0, 1, 3, 4)
    o = jax.lax.map(group, (by_group, k.transpose(2, 0, 1, 3), v.transpose(2, 0, 1, 3)))
    o = o.transpose(1, 2, 0, 3, 4).reshape(B, S, H * D)
    return _mm((o.astype(jnp.float32) * gate).astype(o.dtype), p["wo"]["weight"],
               "bse,ec->bsc", precision)


def _swiglu(p, x, precision):
    up = jax.nn.silu(_mm(x, p["w_gate"]["weight"], "bsc,cf->bsf", precision)) \
        * _mm(x, p["w_up"]["weight"], "bsc,cf->bsf", precision)
    return _mm(up, p["w_down"]["weight"], "bsf,fc->bsc", precision)


def route(p, x, cfg, precision):
    """(chosen ids [B, S, K], their weights [B, S, K]), float32."""
    s = jax.nn.sigmoid(_mm(x, p["weight"], "bsc,ce->bse", precision).astype(jnp.float32))
    _, idx = jax.lax.top_k(s + jax.lax.stop_gradient(p["bias"].astype(jnp.float32)),
                           int(cfg["num_experts_per_tok"]))
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    return idx, chosen / (chosen.sum(-1, keepdims=True) + 1e-20) * float(cfg["route_scale"])


def routed_layer(p, x, cfg, precision, first=None, count=None):
    """Shared expert + the experts ``first .. first + count - 1`` (the
    configuration's share by default) of ``p["experts"]``, whose bank ``j`` is
    expert ``first + j``: a masked sum, one expert at a time."""
    z = sizes(cfg)
    first = z["first"] if first is None else first
    count = z["held"] if count is None else count
    idx, g = route(p["router"], x, cfg, precision)

    @jax.checkpoint
    def add_expert(y, bank_and_id):
        bank, e = bank_and_id
        g_e = jnp.sum(jnp.where(idx == e, g, 0.0), axis=-1)       # 0 where not chosen
        return y + g_e[..., None].astype(y.dtype) * _swiglu(bank, x, precision), None

    y, _ = jax.lax.scan(add_expert, _swiglu(p["shared"], x, precision),
                        (p["experts"], first + jnp.arange(count)))
    return y


def _layer(p, x, cfg, precision, routed, layer_type):
    eps = float(cfg["rms_norm_eps"])
    norm = lambda a, name: _rms_norm(a, p[name]["weight"], eps)
    x = x + norm(_attention(p["attention"], norm(x, "attention_norm"), cfg, precision, layer_type),
                 "post_attention_norm")
    h = norm(x, "ffn_norm")
    y = routed_layer(p["feed_forward"], h, cfg, precision) if routed \
        else _swiglu(p["feed_forward"], h, precision)
    return x + norm(y, "post_ffn_norm")


def hidden_states(params, tokens, cfg, precision: str = "float32"):
    """tokens [B, S] → the final-normed state [B, S, C]."""
    if precision not in PRECISIONS:
        raise ValueError(f"unknown precision {precision!r}")
    if precision == "bfloat16":
        params = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), params)
    x = params["tok_embeddings"]["weight"][tokens]
    if cfg["mup_enabled"]:
        x = x * jnp.asarray(int(cfg["hidden_size"]) ** 0.5, x.dtype)
    layers = [(p, False) for p in params["dense_layers"]] + [(p, True) for p in params["layers"]]
    for (p, routed), layer_type in zip(layers, cfg["layer_types"]):
        x = jax.checkpoint(functools.partial(_layer, cfg=cfg, precision=precision, routed=routed,
                                             layer_type=layer_type))(p, x)
    return _rms_norm(x, params["norm"]["weight"], float(cfg["rms_norm_eps"]))


def _head_weight(params, precision):
    w = params["output"]["weight"]
    return w.astype(jnp.bfloat16) if precision == "bfloat16" else w


def logits_at(params, tokens, cfg, precision: str = "float32"):
    """Float32 logits [B, S, V]."""
    h = hidden_states(params, tokens, cfg, precision)
    return _mm(h, _head_weight(params, precision), "bsc,cv->bsv", precision).astype(jnp.float32)


def loss(params, inputs, targets, cfg, precision: str = "float32"):
    """Mean over every position of logsumexp - gold, CE_BLOCK positions at a time."""
    h = hidden_states(params, inputs, cfg, precision)
    w = _head_weight(params, precision)
    B, S, _ = h.shape
    blk = min(CE_BLOCK, S)
    if S % blk:
        raise ValueError(f"sequence {S} is not a multiple of {blk}")
    split = lambda a: a.reshape((B, S // blk, blk) + a.shape[2:]).swapaxes(0, 1)

    @jax.checkpoint
    def block(args):
        hh, tt = args
        lg = _mm(hh, w, "bsc,cv->bsv", precision).astype(jnp.float32)
        gold = jnp.take_along_axis(lg, tt[..., None], axis=-1)[..., 0]
        return jnp.sum(jax.nn.logsumexp(lg, axis=-1) - gold)

    return jnp.sum(jax.lax.map(block, (split(h), split(targets)))) / (B * S)


def loss_and_grads(params, inputs, targets, cfg, precision: str = "float32"):
    """((L,), gradients of L) on the whole batch at once."""
    value, grads = jax.value_and_grad(loss)(params, inputs, targets, cfg, precision)
    return (value,), grads
