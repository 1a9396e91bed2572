"""Plain reference for the hybrid of delta-rule layers whose write strength
reaches 2 and gated grouped-query attention without positions, over a
sigmoid-routed expert layer (``architecture: solar_open2``; Solar-Open2-250B's
published ``config.json``, ``model_type: solar_open2``).

Layer equations (ISSUE 56; ``C`` the hidden size, 4,096 published; RMSNorm eps
1e-5; ``x`` a sub-layer's normed input). Layer ``l`` (0-based, as ``gqa_layers``
is) is ``G`` where ``l`` is in ``gqa_layers``, else ``K``:

- Block, every layer: ``h = x + Mixer_l(RMSNorm(x))``, ``x' = h + MoE(RMSNorm(h))``;
  ``x_0 = Emb(t)``; a final RMSNorm; an untied head; mean cross-entropy. No layer
  has a dense FFN (``first_k_dense_replace`` 0).
- **K** (KDA; ``H`` heads of ``d``, ``linear_attn_config``): ``q^ = SiLU(conv(x
  W_q))``, likewise ``k^``, ``v`` (a causal depthwise convolution over time,
  ``short_conv_kernel_size`` taps a channel, zeros before ``t = 0``, no bias, own
  weights each); a head: ``q_t = q^_t / sqrt(|q^_t|^2 + 1e-6) d^-1/2``, ``k_t =
  k^_t / sqrt(|k^_t|^2 + 1e-6)``; ``g_t = -exp(A_log_h) softplus(x_t W_f^down
  W_f^up + dt_bias)`` in ``R^{H x d}`` (``kda_use_full_proj: false``: the low-rank
  pairs); **``beta_t = 2 sigmoid(x_t W_beta)``** in ``(0, 2)^H``
  (``kda_allow_neg_eigval``; 1 sigmoid without it); state ``S_t [d, d]`` a head,
  ``S_0 = 0``: ``S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t
  v_t^T``, ``o_t = S_t^T q_t``; ``y_t = [RMSNorm_head(o_t) * sigmoid(x_t W_g^down
  W_g^up)] W_o``. No positions.
- **G** (gated grouped-query attention; ``H`` query heads over ``G`` key/value
  heads of ``D``): ``q = x W_q``, ``k = x W_k``, ``v = x W_v``, no rotation
  (``use_rope: false``), no head norm, causal softmax at ``D^-1/2``, a key/value
  head serving ``H / G`` query heads; ``y = (o * sigmoid(x W_z)) W_o``
  (``use_gqa_gate``; ``W_z`` is the leaf ``wg``).
- **MoE**: a shared SwiGLU expert plus ``s = sigmoid(x W_r)`` over
  ``n_routed_experts``, the top-``k`` of ``s + b``, weights ``scale * s_i /
  sum_chosen s_j`` (``norm_topk_prob``), and of the chosen experts those this chip
  holds (``experts_held``).

Written in straightforward ``jax.numpy``, float32, every matmul at
``Precision.HIGHEST``: no kernels, no chunked state. It imports nothing of the
program and makes its own weights from the seed, in the tree the program trains
(``layers`` a list of per-layer dicts). The delta rule a step at a time, the
short convolution and the router's arithmetic are ``reference/kimi_linear.py``'s
own functions, imported as they stand (that file's docstring has the departures
that are a matter of memory or time: the recurrence in checkpointed blocks of
``SCAN_BLOCK`` steps, a sequence at a time through each layer); one more here, of
memory alone: a mixer is summed over groups of its heads, each group through its own
columns of the projections and its own rows of ``W_o`` (``CORE_HEADS`` delta-rule
heads a group; a key/value head with the query heads it serves, a block of
``ATTN_BLOCK`` query rows at a time), so that no float32 array as wide as all 64
heads is made.

``precision`` as in ``reference/kimi_linear.py``: ``float32``, ``fp8`` (the
control), ``bfloat16``, ``float32_default``, and ``float32_bf16_kda`` (the
diagnosis: the decay and the state of the delta rule alone in bfloat16).
"""

from __future__ import annotations

import functools
import json
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from benchmark.reference import kimi_linear as base
from benchmark.reference.kimi_linear import (BF16_KDA, PRECISIONS, ROUTER_BIAS_STD, _is_spec,  # noqa: F401
                                             _short_conv, _swiglu, delta_rule)
from benchmark.reference.llama_dense import CE_BLOCK, INIT_STD, _mm, _rms_norm

ATTN_BLOCK = 512    # query rows of one block of softmax attention
CORE_HEADS = 16     # delta-rule heads a group (`_kda`): all 64 at once do not fit the chip beside the gradients


def sizes(cfg: Dict[str, Any]) -> Dict[str, Any]:
    lin, held = cfg["linear_attn_config"], cfg["experts_held"]
    z = {"C": int(cfg["hidden_size"]), "L": int(cfg["num_hidden_layers"]),
         "H": int(cfg["num_attention_heads"]), "G": int(cfg["num_key_value_heads"]),
         "D": int(cfg["head_dim"]), "V": int(cfg["vocab_size"]),
         "Hk": int(lin["num_heads"]), "d": int(lin["head_dim"]),
         "taps": int(lin["short_conv_kernel_size"]),
         "E": int(cfg["n_routed_experts"]), "K": int(cfg["num_experts_per_tok"]),
         "Fe": int(cfg["moe_intermediate_size"]), "Ns": int(cfg["n_shared_experts"]),
         "first": int(held["first"]), "held": int(held["count"]),
         "beta_scale": 2.0 if cfg["kda_allow_neg_eigval"] else 1.0}
    gqa = [int(l) for l in cfg["gqa_layers"]]
    if not gqa or len(set(gqa)) != len(gqa) or min(gqa) < 0 or max(gqa) >= z["L"]:
        raise ValueError(f"gqa_layers {gqa} (0-based) must name layers of the {z['L']} there are, each once")
    if cfg["use_rope"] or not cfg["use_gqa_gate"] or cfg["kda_use_full_proj"] or int(cfg["first_k_dense_replace"]):
        raise ValueError("this reference is of use_rope false, use_gqa_gate true, kda_use_full_proj false "
                         "and first_k_dense_replace 0")
    z["kinds"] = ["G" if l in gqa else "K" for l in range(z["L"])]
    return z


def param_shapes(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """Tree of (shape, init): a float is normal(0, std), None ones, "a_log" and
    "dt_bias" the decay's own draws (``reference/kimi_linear.py``)."""
    z = sizes(cfg)
    C, H, G, D, Hk, d = z["C"], z["H"], z["G"], z["D"], z["Hk"], z["d"]
    res_std = INIT_STD / (2 * z["L"]) ** 0.5
    w = lambda shape, init=INIT_STD: {"weight": (shape, init)}

    def swiglu(width, lead=()):
        return {"w_gate": w(lead + (C, width)), "w_up": w(lead + (C, width)),
                "w_down": w(lead + (width, C), res_std)}

    def kda():
        return {"wq": w((C, Hk * d)), "wk": w((C, Hk * d)), "wv": w((C, Hk * d)),
                "conv_q": w((Hk * d, z["taps"])), "conv_k": w((Hk * d, z["taps"])),
                "conv_v": w((Hk * d, z["taps"])),
                "f_down": w((C, d)), "f_up": w((d, Hk * d)),
                "A_log": ((Hk,), "a_log"), "dt_bias": ((Hk * d,), "dt_bias"),
                "wb": w((C, Hk)), "g_down": w((C, d)), "g_up": w((d, Hk * d)),
                "o_norm": w((d,), None), "wo": w((Hk * d, C), res_std)}

    def gated():
        return {"wq": w((C, H * D)), "wk": w((C, G * D)), "wv": w((C, G * D)),
                "wg": w((C, H * D)), "wo": w((H * D, C), res_std)}

    def layer(kind):
        ff = {"router": {"weight": ((C, z["E"]), INIT_STD), "bias": ((z["E"],), ROUTER_BIAS_STD)},
              "shared": swiglu(z["Ns"] * z["Fe"]),
              "experts": swiglu(z["Fe"], (z["held"],))}
        mixer = {"kda": kda()} if kind == "K" else {"attention": gated()}
        return {"attention_norm": w((C,), None), **mixer, "ffn_norm": w((C,), None),
                "feed_forward": ff}

    return {"tok_embeddings": w((z["V"], C)),
            "layers": [layer(k) for k in z["kinds"]],
            "norm": w((C,), None),
            "output": w((C, z["V"]))}


def make_params(seed, cfg: Dict[str, Any]):
    """Float32 weights from ``seed`` (a traced or concrete uint32 scalar); each
    leaf draws from the key folded with its index in the flattened tree."""
    leaves, treedef = jax.tree_util.tree_flatten(param_shapes(cfg), is_leaf=_is_spec)
    key = jax.random.PRNGKey(seed)
    out = []
    for i, (shape, init) in enumerate(leaves):
        k = jax.random.fold_in(key, i)
        if init is None:
            a = jnp.ones(shape, jnp.float32)
        elif init == "a_log":
            a = jnp.log(jax.random.uniform(k, shape, jnp.float32, base.A_MIN, base.A_MAX))
        elif init == "dt_bias":
            step = jnp.exp(jax.random.uniform(k, shape, jnp.float32)
                           * (math.log(base.DT_MAX) - math.log(base.DT_MIN)) + math.log(base.DT_MIN))
            a = step + jnp.log(-jnp.expm1(-step))
        else:
            a = jax.random.normal(k, shape, jnp.float32) * init
        out.append(a)
    return jax.tree_util.tree_unflatten(treedef, out)


def init_params(seed: int, cfg: Dict[str, Any], shardings=None):
    fn = jax.jit(functools.partial(make_params, cfg=cfg), out_shardings=shardings)
    return fn(jnp.uint32(seed % (2 ** 32)))


# -- arithmetic -----------------------------------------------------------------
def kda_operands(p, x, cfg, precision):
    """``(q, k, v, g [B, S, H, d], beta [B, S, H])`` of the heads whose weights ``p``
    holds (all of a mixer's, or one group's columns), float32: the write strength is
    ``beta_scale sigmoid(x W_beta)``. Each head-wide operand is its own
    ``jax.checkpoint``, so the backward keeps none of their float32 arrays."""
    z = sizes(cfg)
    B, S, _ = x.shape
    H, d = p["wb"]["weight"].shape[1], z["d"]
    f32 = jnp.float32

    @functools.partial(jax.checkpoint, static_argnums=(3,))
    def short(x, w, conv, scale):
        a = _short_conv(_mm(x, w, "bsc,ce->bse", precision), conv).reshape(B, S, H, d)
        if scale is not None:                                         # q and k: a unit vector a head
            a = a * jax.lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True) + base.L2_EPS) * scale
        return a

    @jax.checkpoint
    def decay(x, down, up, a_log, dt_bias):
        step = _mm(_mm(x, down, "bsc,cr->bsr", precision), up, "bsr,re->bse", precision).astype(f32)
        return -jnp.exp(a_log.astype(f32))[:, None] * jax.nn.softplus(
            step + dt_bias.astype(f32)).reshape(B, S, H, d)

    beta = z["beta_scale"] * jax.nn.sigmoid(_mm(x, p["wb"]["weight"], "bsc,ch->bsh", precision).astype(f32))
    return (short(x, p["wq"]["weight"], p["conv_q"]["weight"], d ** -0.5),
            short(x, p["wk"]["weight"], p["conv_k"]["weight"], 1.0),
            short(x, p["wv"]["weight"], p["conv_v"]["weight"], None),
            decay(x, p["f_down"]["weight"], p["f_up"]["weight"], p["A_log"], p["dt_bias"]),
            beta)


def _kda_heads(p, x, cfg, precision):
    """What the heads whose weights ``p`` holds add to the mixer's output: their
    operands, the recurrence a step at a time, the head norm, the gate's low-rank
    pair and their rows of ``W_o``."""
    o = delta_rule(*kda_operands(p, x, cfg, precision), jnp.bfloat16 if precision == BF16_KDA else None)

    @jax.checkpoint
    def output(p, x, o):
        B, S, H, d = o.shape
        gate = jax.nn.sigmoid(_mm(_mm(x, p["g_down"]["weight"], "bsc,cr->bsr", precision), p["g_up"]["weight"],
                                  "bsr,re->bse", precision).astype(jnp.float32)).reshape(B, S, H, d)
        o = (_rms_norm(o, p["o_norm"]["weight"], float(cfg["rms_norm_eps"])) * gate).astype(x.dtype)
        return _mm(o.reshape(B, S, H * d), p["wo"]["weight"], "bse,ec->bsc", precision)

    return output(p, x, o)


def _by_group(p, names_axes, n):
    """The leaves named, their axis of heads split into ``n`` groups and brought first."""
    out = {}
    for name, axis in names_axes.items():
        leaf = p[name]["weight"] if isinstance(p[name], dict) else p[name]
        shape = leaf.shape[:axis] + (n, leaf.shape[axis] // n) + leaf.shape[axis + 1:]
        out[name] = jnp.moveaxis(leaf.reshape(shape), axis, 0)
    return out


def _sum_over_groups(fn, p, x, names_axes, n):
    """``sum_g fn(p with group g's share of the leaves named, x)``, each group its own
    ``jax.checkpoint``: a mixer's output is a sum over its heads' rows of ``W_o``, so no
    array as wide as all the heads is ever made, and the backward holds one group's."""
    whole = lambda own: dict(p, **{k: ({"weight": a} if isinstance(p[k], dict) else a) for k, a in own.items()})
    add = jax.checkpoint(lambda y, own: y + fn(whole(own), x))
    return jax.lax.scan(lambda y, own: (add(y, own), None), jnp.zeros_like(x), _by_group(p, names_axes, n))[0]


_KDA_BY_HEAD = {"wq": 1, "wk": 1, "wv": 1, "f_up": 1, "wb": 1, "g_up": 1,
                "conv_q": 0, "conv_k": 0, "conv_v": 0, "A_log": 0, "dt_bias": 0, "wo": 0}


def _kda(p, x, cfg, precision):
    """The mixer, ``CORE_HEADS`` heads at a time where it has more. The published 64
    in one pass, as ``reference/kimi_linear.py`` runs its 32, compile to a gradient of
    15.3 GiB at 2 x 8,192 for a described v5e (the compiler's memory-usage report; the
    chip has 15.7), groups of 32 to 14.9, of 16 to 13.8, of 8 to 13.3; a group more is
    a walk of the recurrence more, so 16."""
    H = p["wb"]["weight"].shape[1]
    n = -(-H // CORE_HEADS)
    if n == 1 or H % n:
        return _kda_heads(p, x, cfg, precision)
    return _sum_over_groups(lambda q, x: _kda_heads(q, x, cfg, precision), p, x, _KDA_BY_HEAD, n)


def _attention_group(p, x, cfg, precision):
    """One key/value head and the query heads it serves (``p``'s columns): causal
    softmax on the projections as they come, a block of query rows at a time, gated,
    through their rows of ``W_o``."""
    z = sizes(cfg)
    B, S, _ = x.shape
    D = z["D"]
    R = p["wq"]["weight"].shape[1] // D                            # query heads of this group
    q = _mm(x, p["wq"]["weight"], "bsc,ce->bse", precision).reshape(B, S, R, D)
    k = _mm(x, p["wk"]["weight"], "bsc,cd->bsd", precision)
    v = _mm(x, p["wv"]["weight"], "bsc,cd->bsd", precision)
    gate = jax.nn.sigmoid(_mm(x, p["wg"]["weight"], "bsc,ce->bse", precision).astype(jnp.float32))
    blk = min(ATTN_BLOCK, S)
    if S % blk:
        raise ValueError(f"sequence {S} is not a multiple of {blk}")
    cols = jnp.arange(S)[None, :]

    @jax.checkpoint
    def rows(args):
        qb, row0 = args                                            # [B, blk, R, D]
        seen = cols <= row0 + jnp.arange(blk)[:, None]
        s = _mm(qb, k, "bqhd,bkd->bhqk", precision).astype(jnp.float32) * D ** -0.5
        pr = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), axis=-1).astype(v.dtype)
        return _mm(pr, v, "bhqk,bkd->bqhd", precision)

    n = S // blk
    o = jax.lax.map(rows, (q.reshape(B, n, blk, R, D).swapaxes(0, 1), jnp.arange(n) * blk))
    o = o.swapaxes(0, 1).reshape(B, S, R * D)
    return _mm((o.astype(jnp.float32) * gate).astype(o.dtype), p["wo"]["weight"], "bse,ec->bsc", precision)


_GQA_BY_HEAD = {"wq": 1, "wk": 1, "wv": 1, "wg": 1, "wo": 0}


def _attention(p, x, cfg, precision):
    """Gated grouped-query attention, a key/value head with its query heads at a time."""
    return _sum_over_groups(lambda q, x: _attention_group(q, x, cfg, precision), p, x, _GQA_BY_HEAD,
                            sizes(cfg)["G"])


def route(p, x, cfg, precision):
    """(chosen ids [B, S, K], their weights [B, S, K]), float32: ``kimi_linear.route``
    under this configuration's key names."""
    return base.route(p, x, {"num_experts_per_token": cfg["num_experts_per_tok"],
                             "routed_scaling_factor": cfg["routed_scaling_factor"]}, precision)


def routed_layer(p, x, cfg, precision, first=None, count=None, shared: bool = True):
    """Shared expert (unless ``shared`` is false) + the experts ``first .. first +
    count - 1`` (the configuration's share by default) of ``p["experts"]``, whose
    bank ``j`` is expert ``first + j``: a masked sum, one expert at a time."""
    z = sizes(cfg)
    first = z["first"] if first is None else first
    count = z["held"] if count is None else count
    idx, g = route(p["router"], x, cfg, precision)
    y = _swiglu(p["shared"], x, precision) if shared else jnp.zeros_like(x)

    @jax.checkpoint
    def one(y, bank):
        expert, e = bank
        g_e = jnp.sum(jnp.where(idx == e, g, 0.0), axis=-1)   # 0 where not chosen
        return y + g_e[..., None].astype(x.dtype) * _swiglu(expert, x, precision), None

    banks = jax.tree_util.tree_map(lambda a: a[:count], p["experts"])
    return jax.lax.scan(one, y, (banks, first + jnp.arange(count)))[0]


def _layer(p, x, cfg, precision, kind):
    eps = float(cfg["rms_norm_eps"])
    u = _rms_norm(x, p["attention_norm"]["weight"], eps)
    # a KDA mixer is not checkpointed again inside its layer: every level of that walks the recurrence once more
    h = x + (_kda(p["kda"], u, cfg, precision) if kind == "K" else
             jax.checkpoint(lambda p, u: _attention(p, u, cfg, precision))(p["attention"], u))
    u = _rms_norm(h, p["ffn_norm"]["weight"], eps)
    return h + routed_layer(p["feed_forward"], u, cfg, precision).astype(h.dtype)


def hidden_states(params, tokens, cfg, precision: str = "float32"):
    """tokens [B, S] -> the final-normed state [B, S, C]."""
    if precision not in PRECISIONS:
        raise ValueError(f"unknown precision {precision!r}")
    if precision == "bfloat16":
        params = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), params)
    x = params["tok_embeddings"]["weight"][tokens]
    for p, kind in zip(params["layers"], sizes(cfg)["kinds"]):
        layer = functools.partial(_layer, cfg=cfg, precision=precision, kind=kind)
        # a sequence at a time: the backward holds one sequence's activations of one layer
        x = jax.lax.map(jax.checkpoint(lambda row, p=p, layer=layer: layer(p, row[None])[0]), x)
    return _rms_norm(x, params["norm"]["weight"], float(cfg["rms_norm_eps"]))


def logits_at(params, tokens, cfg, precision: str = "float32"):
    """Float32 logits [B, S, V]."""
    h = hidden_states(params, tokens, cfg, precision)
    return _mm(h, params["output"]["weight"].astype(h.dtype), "bsc,cv->bsv", precision).astype(jnp.float32)


def loss(params, inputs, targets, cfg, precision: str = "float32"):
    """Mean over every position of logsumexp - gold, CE_BLOCK positions at a time."""
    h = hidden_states(params, inputs, cfg, precision)
    w = params["output"]["weight"].astype(h.dtype)
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


@functools.lru_cache(maxsize=None)
def _compiled_loss_and_grads(cfg_json: str, precision: str):
    cfg = json.loads(cfg_json)
    return jax.jit(lambda p, i, t: loss_and_grads(p, i, t, cfg, precision))


def grads_by_sequence(params, inputs, targets, cfg, precision: str = "float32"):
    """:func:`loss_and_grads`, compiled once a (configuration, precision) a process
    (``reference/kimi_linear.py::grads_by_sequence`` says why the name)."""
    return _compiled_loss_and_grads(json.dumps(cfg, sort_keys=True), precision)(params, inputs, targets)
