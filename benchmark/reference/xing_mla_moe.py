"""Plain reference for the latent-attention, sparse-expert decoder with mixed
residual streams and a next-next-token head (``architecture: xing_mla_moe``).

Written from the equations in the configuration file's ``equations`` (the
DeepSeek-V3 report, arXiv:2412.19437, sections 2.1-2.2, and manifold-
constrained hyper-connections, arXiv:2512.24880), in straightforward
``jax.numpy``: no kernels, no sort, no buffers. It imports nothing of the
program and makes its own weights from the seed, in the tree the program
trains (``dense_layers`` and ``layers`` are lists of per-layer dicts).

One routed layer as the configuration cuts it: the router is as wide as
published, the weights are normalised over all chosen experts, and only the
``experts_held`` (first, count) add to the output: each held expert is applied
to every token and weighted by that token's gate for it (zero where it was
not chosen). What absent experts would add is left out, here as in the program.

Departures from a textbook forward, for memory only: attention runs one head
at a time and the cross-entropy one block of positions at a time
(``lax.map`` over ``jax.checkpoint``-ed bodies), every layer is
``jax.checkpoint``-ed, and :func:`grads_by_sequence` takes the gradient one
sequence at a time, added into one donated float32 accumulator: four float32
streams a token a layer are 0.94 GB a layer at 16,384 tokens, which beside
float32 weights and gradients does not fit a 16 GB chip; one sequence's do.

``precision`` as in ``llama_dense.py``: ``float32`` (matmuls at HIGHEST),
``fp8`` (both operands of every matmul rounded through float8_e4m3; the
control), ``bfloat16``, ``float32_default``.
"""

from __future__ import annotations

import functools
import json
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from benchmark.reference.llama_dense import CE_BLOCK, INIT_STD, PRECISIONS, _mm, _rms_norm

HC_ALPHA = 0.5
HC_RES_DIAG = 1.0
ROUTER_BIAS_STD = 0.01


def sizes(cfg: Dict[str, Any]) -> Dict[str, int]:
    held = cfg["experts_held"]
    return {
        "C": int(cfg["hidden_size"]), "F": int(cfg["intermediate_size"]),
        "L": int(cfg["num_hidden_layers"]), "Ld": int(cfg["first_k_dense_replace"]),
        "H": int(cfg["num_attention_heads"]), "V": int(cfg["vocab_size"]),
        "rq": int(cfg["q_lora_rank"]), "rkv": int(cfg["kv_lora_rank"]),
        "dn": int(cfg["qk_nope_head_dim"]), "dr": int(cfg["qk_rope_head_dim"]),
        "dv": int(cfg["v_head_dim"]), "E": int(cfg["n_routed_experts"]),
        "K": int(cfg["num_experts_per_tok"]), "Fe": int(cfg["moe_intermediate_size"]),
        "Ns": int(cfg["n_shared_experts"]), "first": int(held["first"]),
        "held": int(held["count"]), "n": int(cfg["hc_mult"]),
        "mtp": int(cfg["num_nextn_predict_layers"]),
    }


def param_shapes(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """Tree of (shape, init): a float is normal(0, std), None ones, ("const",
    v) a constant, "hc_bias" a mixing map's bias (0, and HC_RES_DIAG on the
    diagonal of its residual part)."""
    z = sizes(cfg)
    C, H, n = z["C"], z["H"], z["n"]
    res_std = INIT_STD / (2 * (z["L"] + z["mtp"])) ** 0.5
    k = 2 * n + n * n
    w = lambda shape, init: {"weight": (shape, init)}
    mix = lambda: {"phi": w((n * C, k), (n * C) ** -0.5), "alpha": ((3,), ("const", HC_ALPHA)),
                   "bias": ((k,), "hc_bias")}

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
            "attn_hc": mix(), "attention_norm": w((C,), None),
            "attention": {
                "wq_a": w((C, z["rq"]), INIT_STD), "q_norm": w((z["rq"],), None),
                "wq_b": w((z["rq"], H * (z["dn"] + z["dr"])), INIT_STD),
                "wkv_a": w((C, z["rkv"] + z["dr"]), INIT_STD), "kv_norm": w((z["rkv"],), None),
                "wkv_b": w((z["rkv"], H * (z["dn"] + z["dv"])), INIT_STD),
                "wo": w((H * z["dv"], C), res_std)},
            "ffn_hc": mix(), "ffn_norm": w((C,), None), "feed_forward": ff,
        }

    tree = {
        "tok_embeddings": w((z["V"], C), INIT_STD),
        "dense_layers": [layer(False) for _ in range(z["Ld"])],
        "layers": [layer(True) for _ in range(z["L"] - z["Ld"])],
        "norm": w((C,), None),
        "output": w((C, z["V"]), INIT_STD),
    }
    if z["mtp"]:
        tree["mtp"] = {"hnorm": w((C,), None), "enorm": w((C,), None),
                       "eh_proj": w((2 * C, C), INIT_STD), "layer": layer(True), "norm": w((C,), None)}
    return tree


def _is_spec(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def make_params(seed, cfg: Dict[str, Any]):
    """Float32 weights from ``seed`` (a traced or concrete uint32 scalar); each
    leaf draws from the key folded with its index in the flattened tree."""
    n = int(cfg["hc_mult"])
    leaves, treedef = jax.tree_util.tree_flatten(param_shapes(cfg), is_leaf=_is_spec)
    key = jax.random.PRNGKey(seed)
    out = []
    for i, (shape, init) in enumerate(leaves):
        if init is None:
            out.append(jnp.ones(shape, jnp.float32))
        elif init == "hc_bias":
            out.append(jnp.concatenate([jnp.zeros((2 * n,), jnp.float32),
                                        HC_RES_DIAG * jnp.eye(n, dtype=jnp.float32).reshape(-1)]))
        elif isinstance(init, tuple):
            out.append(jnp.full(shape, init[1], jnp.float32))
        else:
            out.append(jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32) * init)
    return jax.tree_util.tree_unflatten(treedef, out)


def init_params(seed: int, cfg: Dict[str, Any], shardings=None):
    fn = jax.jit(functools.partial(make_params, cfg=cfg), out_shardings=shardings)
    return fn(jnp.uint32(seed % (2 ** 32)))


# -- arithmetic -----------------------------------------------------------------
def yarn(cfg: Dict[str, Any]):
    """(rotary frequencies [dr/2], factor on cos and sin, softmax scale)."""
    rs = cfg["rope_scaling"]
    dim, base, factor = int(cfg["qk_rope_head_dim"]), float(cfg["rope_theta"]), float(rs["factor"])
    dqk = int(cfg["qk_nope_head_dim"]) + dim
    mscale = lambda m: 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0
    corr = lambda rot: dim * math.log(int(rs["original_max_position_embeddings"])
                                      / (rot * 2 * math.pi)) / (2 * math.log(base))
    low = max(math.floor(corr(float(rs["beta_fast"]))), 0)
    high = min(math.ceil(corr(float(rs["beta_slow"]))), dim - 1)
    high = high if high != low else low + 0.001
    inv = []
    for i in range(dim // 2):
        f = base ** (-2.0 * i / dim)
        keep = 1.0 - min(max((i - low) / (high - low), 0.0), 1.0)  # 1: the original frequency
        inv.append(f / factor * (1.0 - keep) + f * keep)
    return (jnp.asarray(inv, jnp.float32), mscale(float(rs["mscale"])) / mscale(float(rs["mscale_all_dim"])),
            dqk ** -0.5 * mscale(float(rs["mscale_all_dim"])) ** 2)


def _rope(x, cos, sin):
    """Half-split rotation of [B, S, ..., d] by [S, d/2] tables."""
    half = x.shape[-1] // 2
    shape = (1, x.shape[1]) + (1,) * (x.ndim - 3) + (half,)
    c, s = cos.reshape(shape), sin.reshape(shape)
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :half], xf[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1).astype(x.dtype)


def _attention(p, x, cfg, precision):
    z = sizes(cfg)
    B, S, _ = x.shape
    H, dn, dr, dv = z["H"], z["dn"], z["dr"], z["dv"]
    eps = float(cfg["rms_norm_eps"])
    c_q = _rms_norm(_mm(x, p["wq_a"]["weight"], "bsc,cr->bsr", precision), p["q_norm"]["weight"], eps)
    q = _mm(c_q, p["wq_b"]["weight"], "bsr,re->bse", precision).reshape(B, S, H, dn + dr)
    kv_a = _mm(x, p["wkv_a"]["weight"], "bsc,cr->bsr", precision)
    c_kv = _rms_norm(kv_a[..., :z["rkv"]], p["kv_norm"]["weight"], eps)
    kv = _mm(c_kv, p["wkv_b"]["weight"], "bsr,re->bse", precision).reshape(B, S, H, dn + dv)
    inv_freq, cs_scale, scale = yarn(cfg)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv_freq[None]
    cos, sin = jnp.cos(ang) * cs_scale, jnp.sin(ang) * cs_scale
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], cos, sin)], axis=-1)
    k_r = _rope(kv_a[..., z["rkv"]:], cos, sin)                       # [B, S, dr], every head's
    causal = jnp.tril(jnp.ones((S, S), bool))

    @jax.checkpoint
    def head(args):
        qh, kh, vh = args                                             # [B, S, d]
        kh = jnp.concatenate([kh, k_r], axis=-1)
        s = _mm(qh, kh, "bqd,bkd->bqk", precision).astype(jnp.float32) * scale
        pr = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1).astype(vh.dtype)
        return _mm(pr, vh, "bqk,bkd->bqd", precision)

    by_head = lambda a: a.transpose(2, 0, 1, 3)
    o = jax.lax.map(head, (by_head(q), by_head(kv[..., :dn]), by_head(kv[..., dn:])))
    o = o.transpose(1, 2, 0, 3).reshape(B, S, H * dv)
    return _mm(o, p["wo"]["weight"], "bse,ec->bsc", precision)


def _swiglu(p, x, precision, e=None):
    pick = (lambda a: a) if e is None else (lambda a: a[e])
    up = jax.nn.silu(_mm(x, pick(p["w_gate"]["weight"]), "bsc,cf->bsf", precision)) \
        * _mm(x, pick(p["w_up"]["weight"]), "bsc,cf->bsf", precision)
    return _mm(up, pick(p["w_down"]["weight"]), "bsf,fc->bsc", precision)


def route(p, x, cfg, precision):
    """(chosen ids [B, S, K], their weights [B, S, K]), float32."""
    s = jax.nn.sigmoid(_mm(x, p["weight"], "bsc,ce->bse", precision).astype(jnp.float32))
    _, idx = jax.lax.top_k(s + jax.lax.stop_gradient(p["bias"].astype(jnp.float32)),
                           int(cfg["num_experts_per_tok"]))
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    return idx, chosen / (chosen.sum(-1, keepdims=True) + 1e-20) * float(cfg["routed_scaling_factor"])


def routed_layer(p, x, cfg, precision, first=None, count=None):
    """Shared expert + the experts ``first .. first + count - 1`` (the
    configuration's share by default) of ``p["experts"]``, whose bank ``j`` is
    expert ``first + j``."""
    z = sizes(cfg)
    first = z["first"] if first is None else first
    count = z["held"] if count is None else count
    idx, g = route(p["router"], x, cfg, precision)
    y = _swiglu(p["shared"], x, precision)
    for j in range(count):
        g_e = jnp.sum(jnp.where(idx == first + j, g, 0.0), axis=-1)   # 0 where not chosen
        y = y + g_e[..., None].astype(y.dtype) * _swiglu(p["experts"], x, precision, j)
    return y


def mixing_maps(p, X, cfg, precision):
    """X [B, S, n, C] → H_pre [n, B, S], H_post [n, B, S], H_res [n, n, B, S]
    (positions last: a 4 x 4 minor pair would pad every map to a whole tile)."""
    n, eps = int(cfg["hc_mult"]), float(cfg["hc_eps"])
    B, S, _, C = X.shape
    x = X.reshape(B, S, n * C).astype(jnp.float32)
    xh = (x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)).astype(X.dtype)
    a = _mm(xh, p["phi"]["weight"], "bsx,xk->kbs", precision).astype(jnp.float32)
    alpha, b = p["alpha"].astype(jnp.float32), p["bias"].astype(jnp.float32)[:, None, None]
    h_pre = jax.nn.sigmoid(alpha[0] * a[:n] + b[:n])
    h_post = 2.0 * jax.nn.sigmoid(alpha[1] * a[n:2 * n] + b[n:2 * n])
    m = jnp.exp(jnp.clip(alpha[2] * a[2 * n:] + b[2 * n:], float(cfg["mhc_h_res_clamp_min"]),
                         float(cfg["mhc_h_res_clamp_max"]))).reshape(n, n, B, S)

    def sinkhorn(m, _):  # a loop and not 20 copies, for the compile's sake
        m = m / (m.sum(axis=1, keepdims=True) + eps)            # rows: over j of M[i, j]
        return m / (m.sum(axis=0, keepdims=True) + eps), None   # columns

    m, _ = jax.lax.scan(sinkhorn, m, None, length=int(cfg["hc_sinkhorn_iters"]))
    return h_pre, h_post, m


def _mixed(p_hc, p_norm, X, fn, cfg, precision):
    """One sub-layer on the streams: read, RMSNorm, ``fn``, write."""
    h_pre, h_post, h_res = mixing_maps(p_hc, X, cfg, precision)
    dt = X.dtype
    Xf = X.astype(jnp.float32)
    u = jnp.einsum("nbs,bsnc->bsc", h_pre, Xf).astype(dt)
    y = fn(_rms_norm(u, p_norm["weight"], float(cfg["rms_norm_eps"]))).astype(jnp.float32)
    return (jnp.einsum("ijbs,bsjc->bsic", h_res, Xf)
            + jnp.einsum("ibs,bsc->bsic", h_post, y)).astype(dt)


def _layer(p, X, cfg, precision, routed):
    X = _mixed(p["attn_hc"], p["attention_norm"], X,
               lambda h: _attention(p["attention"], h, cfg, precision), cfg, precision)
    ffn = (lambda h: routed_layer(p["feed_forward"], h, cfg, precision)) if routed \
        else (lambda h: _swiglu(p["feed_forward"], h, precision))
    return _mixed(p["ffn_hc"], p["ffn_norm"], X, ffn, cfg, precision)


def _streams(x, n):
    return jnp.broadcast_to(x[:, :, None, :], x.shape[:2] + (n,) + x.shape[2:])


def hidden_states(params, tokens, next_tokens, cfg, precision: str = "float32"):
    """tokens [B, S] → the main model's final-normed state, and the MTP
    module's (None without one), which at position i also sees
    ``next_tokens[i]``."""
    if precision not in PRECISIONS:
        raise ValueError(f"unknown precision {precision!r}")
    if precision == "bfloat16":
        params = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), params)
    n, eps = int(cfg["hc_mult"]), float(cfg["rms_norm_eps"])
    emb = params["tok_embeddings"]["weight"]
    layer = lambda routed: jax.checkpoint(
        functools.partial(_layer, cfg=cfg, precision=precision, routed=routed))
    X = _streams(emb[tokens], n)
    for p in params["dense_layers"]:
        X = layer(False)(p, X)
    for p in params["layers"]:
        X = layer(True)(p, X)
    h = _rms_norm(X.astype(jnp.float32).sum(axis=2).astype(X.dtype), params["norm"]["weight"], eps)
    if "mtp" not in params:
        return h, None
    m = params["mtp"]
    both = jnp.concatenate([_rms_norm(h, m["hnorm"]["weight"], eps),
                            _rms_norm(emb[next_tokens], m["enorm"]["weight"], eps)], axis=-1)
    X = layer(True)(m["layer"], _streams(_mm(both, m["eh_proj"]["weight"], "bsx,xc->bsc", precision), n))
    return h, _rms_norm(X.astype(jnp.float32).sum(axis=2).astype(X.dtype), m["norm"]["weight"], eps)


def logits_at(params, tokens, next_tokens, cfg, precision: str = "float32"):
    """Float32 logits [B, S, V] of the main head and of the MTP head."""
    w = params["output"]["weight"]
    if precision == "bfloat16":
        w = w.astype(jnp.bfloat16)
    return tuple(None if h is None else _mm(h, w, "bsc,cv->bsv", precision).astype(jnp.float32)
                 for h in hidden_states(params, tokens, next_tokens, cfg, precision))


def _ce_sum(h, w, targets, mask, precision):
    """Sum over masked positions of logsumexp - gold, CE_BLOCK positions at a time."""
    B, S, C = h.shape
    blk = min(CE_BLOCK, S)
    if S % blk:
        raise ValueError(f"sequence {S} is not a multiple of {blk}")
    split = lambda a: a.reshape((B, S // blk, blk) + a.shape[2:]).swapaxes(0, 1)

    @jax.checkpoint
    def block(args):
        hh, tt, mm = args
        lg = _mm(hh, w, "bsc,cv->bsv", precision).astype(jnp.float32)
        gold = jnp.take_along_axis(lg, tt[..., None], axis=-1)[..., 0]
        return jnp.sum((jax.nn.logsumexp(lg, axis=-1) - gold) * mm)

    return jnp.sum(jax.lax.map(block, (split(h), split(targets), split(mask))))


def loss_sums(params, inputs, targets, cfg, precision: str = "float32"):
    """(sum of the main head's CE over every position against ``targets[i] =
    t_{i+1}``, sum of the MTP head's over the positions that have a ``t_{i+2}``
    = ``targets[i + 1]``: all but the last)."""
    h, h2 = hidden_states(params, inputs, targets, cfg, precision)
    w = params["output"]["weight"]
    if precision == "bfloat16":
        w = w.astype(jnp.bfloat16)
    ones = jnp.ones(targets.shape, jnp.float32)
    main = _ce_sum(h, w, targets, ones, precision)
    if h2 is None:
        return main, jnp.zeros((), jnp.float32)
    not_last = ones.at[:, -1].set(0.0)
    return main, _ce_sum(h2, w, jnp.roll(targets, -1, axis=1), not_last, precision)


def _total(params, inputs, targets, cfg, precision, n_main, n_mtp):
    main, mtp = loss_sums(params, inputs, targets, cfg, precision)
    main, mtp = main / n_main, mtp / max(n_mtp, 1)
    return main + float(cfg["mtp_loss_weight"]) * mtp, (main, mtp)


def loss_and_grads(params, inputs, targets, cfg, precision: str = "float32"):
    """((L, main, mtp), gradients of L) on the whole batch at once; ``L =
    main + mtp_loss_weight * mtp``, each a mean over its own positions."""
    B, S = targets.shape
    (total, (main, mtp)), grads = jax.value_and_grad(_total, has_aux=True)(
        params, inputs, targets, cfg, precision, B * S, B * (S - 1))
    return (total, main, mtp), grads


@functools.lru_cache(maxsize=8)
def _sequence_adder(cfg_json: str, precision: str, n_main: int, n_mtp: int):
    cfg = json.loads(cfg_json)

    def add(params, grads, terms, inputs, targets):
        (_, (main, mtp)), g = jax.value_and_grad(_total, has_aux=True)(
            params, inputs, targets, cfg, precision, n_main, n_mtp)
        return jax.tree_util.tree_map(jnp.add, grads, g), (terms[0] + main, terms[1] + mtp)

    return jax.jit(add, donate_argnums=(1, 2))


def grads_by_sequence(params, inputs, targets, cfg, precision: str = "float32"):
    """:func:`loss_and_grads`, one sequence at a time into one accumulator (the
    same sums, so the same numbers to float32 rounding)."""
    B, S = targets.shape
    add = _sequence_adder(json.dumps(cfg, sort_keys=True), precision, B * S, B * (S - 1))
    grads = jax.tree_util.tree_map(jnp.zeros_like, params)
    terms = (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32))
    for b in range(B):
        grads, terms = add(params, grads, terms, inputs[b:b + 1], targets[b:b + 1])
    main, mtp = terms
    return (main + float(cfg["mtp_loss_weight"]) * mtp, main, mtp), grads
