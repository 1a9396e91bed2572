"""Plain reference for the hybrid of Kimi Delta Attention and latent attention
over a sigmoid-routed expert layer (``architecture: kimi_linear``;
Kimi-Linear-48B-A3B-Instruct, arXiv:2510.26692 "Kimi Linear: An Expressive,
Efficient Attention Architecture").

Layer equations (ISSUE 51; ``C`` the hidden size, 2,304 published; RMSNorm eps
1e-5; ``x`` a sub-layer's normed input):

- Block, every layer ``l = 1..L``: ``h = x + Mixer_l(RMSNorm(x))``, ``x' = h +
  FFN_l(RMSNorm(h))``; ``x_0 = Emb(t)``; a final RMSNorm; an untied head; mean
  cross-entropy. ``Mixer_l`` is KDA for ``l`` in ``kda_layers`` and latent
  attention for ``l`` in ``full_attn_layers`` (1-based, as published). The
  first ``first_k_dense_replace`` layers' FFN is SwiGLU of ``intermediate_size``;
  the others' is the routed layer.
- **KDA** (``H`` heads of ``d``; ``Hd = H d``): ``q^ = SiLU(conv(x W_q))``,
  likewise ``k^``, ``v`` (a causal depthwise convolution over time,
  ``short_conv_kernel_size`` taps a channel, zeros before ``t = 0``, no bias,
  own weights each); a head: ``q_t = q^_t / sqrt(|q^_t|^2 + 1e-6) d^-1/2``,
  ``k_t = k^_t / sqrt(|k^_t|^2 + 1e-6)``; ``g_t = -exp(A_log_h) softplus(x_t
  W_f^down W_f^up + dt_bias)`` in ``R^{H x d}``; ``beta_t = sigmoid(x_t W_beta)``
  in ``R^H``; state ``S_t [d, d]`` a head, ``S_0 = 0``:
  **``S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T``**,
  ``o_t = S_t^T q_t``; ``y_t = [RMSNorm_head(o_t) * sigmoid(x_t W_g^down
  W_g^up)] W_o`` (the norm over a head's ``d`` with one gain vector).
- **Latent attention** (``H`` heads, ``dn + dr | dv``, rank ``r``; no query
  rank, **no rotation anywhere**): ``q = x W_q`` as ``[H, dn + dr]``; ``a = x
  W_kva`` in ``R^{r + dr}``; ``[k_nope ; v] = RMSNorm(a[:r]) W_kvb`` as ``[H, dn
  + dv]``; ``k = [k_nope ; a[r:]]``, the last ``dr`` the same for all heads;
  causal softmax at ``(dn + dr)^-1/2``; ``W_o``.
- **Routed layer**: a shared SwiGLU expert plus ``s = sigmoid(x W_r)``, the
  top-``k`` of ``s + b``, weights ``scale * s_i / sum_chosen s_j``, and of the
  chosen experts those this chip holds (``experts_held``), as
  ``reference/xing_mla_moe.py`` has it.

Written in straightforward ``jax.numpy``: no kernels, no chunked state. It
imports nothing of the program and makes its own weights from the seed, in the
tree the program trains (``layers`` a list of per-layer dicts).

Departures, each a matter of memory or time and none of the mathematics: a KDA
mixer's core is the recurrence above itself, a step at a time, all of a
sequence's heads in one ``lax.scan`` over time (the steps are bound by latency,
so one wide pass of 8,192 steps is the fastest: 56 s a run of three train steps
where four passes of 8 heads, checkpointed twice more, took 391-489), ``SCAN_BLOCK`` steps at
a time under ``jax.checkpoint`` inside a scan over blocks, so that its backward
holds one block's states (64 x 2 MB at 32 heads) beside the states at block
starts, and not the sequence's (16 GB); the projections either side of it are
``jax.checkpoint``-ed an operand at a time, and the mixer is not checkpointed
again inside its layer (every level of that walks the recurrence once more:
a gradient walks it three times forward and once backward); attention runs one
head at a time, each held expert (a ``lax.scan`` over the banks) and each block of
the cross-entropy is ``jax.checkpoint``-ed, as is every layer, which takes its
batch one sequence at a time (a ``lax.map`` of checkpointed bodies: the
backward holds one sequence's activations of one layer, and the map's carry
that layer's gradients alone). So the whole gradient is one program beside the
float32 weights and their gradients (10.4 GB at 1.30B parameters; 14.6 GiB in
all by the compiler's count): a gradient summed over sequences outside the
program holds a third copy, which does not fit the chip.

``precision`` as in ``llama_dense.py``: ``float32`` (matmuls at HIGHEST),
``fp8`` (both operands of every matmul rounded through float8_e4m3; the
control), ``bfloat16``, ``float32_default``; and one of this file's own,
``float32_bf16_kda``: the reference proper but for what this architecture
states float32 beyond the matmuls' accumulation in its new layer, the decay and
the state of the delta rule, which it keeps in bfloat16 (the state rounded
after every step): a diagnosis of whether the comparison would catch a core
that kept its state in the operands' precision.
"""

from __future__ import annotations

import functools
import json
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from benchmark.reference import llama_dense
from benchmark.reference.llama_dense import CE_BLOCK, INIT_STD, _mm, _rms_norm

ROUTER_BIAS_STD = 0.01
A_MIN, A_MAX = 1.0, 16.0
DT_MIN, DT_MAX = 1e-3, 1e-1
L2_EPS = 1e-6
SCAN_BLOCK = 64     # time steps of one checkpointed block of the recurrence
BF16_KDA = "float32_bf16_kda"   # _mm takes it as float32 at HIGHEST
PRECISIONS = llama_dense.PRECISIONS + (BF16_KDA,)


def sizes(cfg: Dict[str, Any]) -> Dict[str, Any]:
    lin, held = cfg["linear_attn_config"], cfg["experts_held"]
    z = {"C": int(cfg["hidden_size"]), "F": int(cfg["intermediate_size"]),
         "L": int(cfg["num_hidden_layers"]), "Ld": int(cfg["first_k_dense_replace"]),
         "H": int(cfg["num_attention_heads"]), "V": int(cfg["vocab_size"]),
         "rkv": int(cfg["kv_lora_rank"]), "dn": int(cfg["qk_nope_head_dim"]),
         "dr": int(cfg["qk_rope_head_dim"]), "dv": int(cfg["v_head_dim"]),
         "Hk": int(lin["num_heads"]), "d": int(lin["head_dim"]),
         "taps": int(lin["short_conv_kernel_size"]),
         "E": int(cfg["num_experts"]), "K": int(cfg["num_experts_per_token"]),
         "Fe": int(cfg["moe_intermediate_size"]), "Ns": int(cfg["num_shared_experts"]),
         "first": int(held["first"]), "held": int(held["count"])}
    kda = [int(l) for l in lin["kda_layers"]]
    full = [int(l) for l in lin["full_attn_layers"]]
    if sorted(kda + full) != list(range(1, z["L"] + 1)):
        raise ValueError(f"kda_layers {kda} and full_attn_layers {full} must name each of "
                         f"{z['L']} layers once (1-based)")
    z["kinds"] = ["K" if l in kda else "M" for l in range(1, z["L"] + 1)]
    return z


def param_shapes(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """Tree of (shape, init): a float is normal(0, std), None ones, "a_log" the
    log of a draw from U(1, 16), "dt_bias" the inverse softplus of a step drawn
    log-uniformly in [1e-3, 1e-1]."""
    z = sizes(cfg)
    C, H, Hk, d = z["C"], z["H"], z["Hk"], z["d"]
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

    def latent():
        return {"wq": w((C, H * (z["dn"] + z["dr"]))), "wkv_a": w((C, z["rkv"] + z["dr"])),
                "kv_norm": w((z["rkv"],), None),
                "wkv_b": w((z["rkv"], H * (z["dn"] + z["dv"]))),
                "wo": w((H * z["dv"], C), res_std)}

    def layer(i, kind):
        ff = swiglu(z["F"])
        if i >= z["Ld"]:
            ff = {"router": {"weight": ((C, z["E"]), INIT_STD), "bias": ((z["E"],), ROUTER_BIAS_STD)},
                  "shared": swiglu(z["Ns"] * z["Fe"]),
                  "experts": swiglu(z["Fe"], (z["held"],))}
        mixer = {"kda": kda()} if kind == "K" else {"attention": latent()}
        return {"attention_norm": w((C,), None), **mixer, "ffn_norm": w((C,), None),
                "feed_forward": ff}

    return {"tok_embeddings": w((z["V"], C)),
            "layers": [layer(i, k) for i, k in enumerate(z["kinds"])],
            "norm": w((C,), None),
            "output": w((C, z["V"]))}


def _is_spec(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


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
            a = jnp.log(jax.random.uniform(k, shape, jnp.float32, A_MIN, A_MAX))
        elif init == "dt_bias":
            step = jnp.exp(jax.random.uniform(k, shape, jnp.float32)
                           * (math.log(DT_MAX) - math.log(DT_MIN)) + math.log(DT_MIN))
            a = step + jnp.log(-jnp.expm1(-step))
        else:
            a = jax.random.normal(k, shape, jnp.float32) * init
        out.append(a)
    return jax.tree_util.tree_unflatten(treedef, out)


def init_params(seed: int, cfg: Dict[str, Any], shardings=None):
    fn = jax.jit(functools.partial(make_params, cfg=cfg), out_shardings=shardings)
    return fn(jnp.uint32(seed % (2 ** 32)))


# -- arithmetic -----------------------------------------------------------------
def _short_conv(a, w):
    """``a [B, S, D]``, ``w [D, taps]`` -> SiLU of the causal depthwise convolution, float32."""
    taps, S = w.shape[1], a.shape[1]
    padded = jnp.pad(a.astype(jnp.float32), ((0, 0), (taps - 1, 0), (0, 0)))
    return jax.nn.silu(sum(w[:, j].astype(jnp.float32) * padded[:, j:j + S] for j in range(taps)))


def _recurrence(q, k, v, g, beta, low=None):
    """The recurrence on time-major operands ``[S, ..., d]`` (``beta [S, ...]``; any
    axes between time and the channels are independent heads) -> ``o [S, ..., d]``."""
    S, d = q.shape[0], q.shape[-1]
    f32 = jnp.float32
    keep = (lambda a: a.astype(low).astype(f32)) if low is not None else (lambda a: a)
    hi = jax.lax.Precision.HIGHEST

    def step(state, x):                                   # state [..., d_k, d_v]
        q_t, k_t, v_t, g_t, b_t = (a.astype(f32) for a in x)
        state = state * jnp.exp(keep(g_t))[..., None]
        seen = jnp.einsum("...k,...kv->...v", k_t, state, precision=hi)
        state = keep(state + (b_t[..., None] * k_t)[..., None] * (v_t - seen)[..., None, :])
        return state, jnp.einsum("...k,...kv->...v", q_t, state, precision=hi)

    @jax.checkpoint
    def block(state, xs):
        return jax.lax.scan(step, state, xs, unroll=4)   # fewer trips of a loop whose steps are short

    blk = math.gcd(SCAN_BLOCK, S)
    split = lambda a: a.reshape((S // blk, blk) + a.shape[1:])
    _, o = jax.lax.scan(block, jnp.zeros(q.shape[1:] + (d,), f32), tuple(split(a) for a in (q, k, v, g, beta)))
    return o.reshape((S,) + o.shape[2:])


def delta_rule(q, k, v, g, beta, low=None):
    """The recurrence itself, a step at a time: ``q, k, v, g [B, S, H, d]``,
    ``beta [B, S, H]`` -> ``o [B, S, H, d]`` float32. ``low``: a dtype the decay
    is rounded to and the state is kept in (the diagnosis), else float32."""
    time = lambda a: jnp.moveaxis(a, 1, 0)
    return jnp.moveaxis(_recurrence(*(time(a) for a in (q, k, v, g, beta)), low), 0, 1)


def _kda_operands(p, x, cfg, precision):
    """``(q, k, v, g [S, B, H, d], beta [S, B, H])`` of a KDA mixer. Each operand
    is its own ``jax.checkpoint``: the backward recomputes one operand's head-wide
    float32 arrays at a time, and keeps none of them."""
    z = sizes(cfg)
    B, S, _ = x.shape
    H, d = z["Hk"], z["d"]
    f32 = jnp.float32
    heads = lambda a: jnp.moveaxis(a.reshape(B, S, H, -1), 1, 0)      # for the recurrence: time leads

    @functools.partial(jax.checkpoint, static_argnums=(3,))
    def short(x, w, conv, scale):
        a = _short_conv(_mm(x, w, "bsc,ce->bse", precision), conv).reshape(B, S, H, d)
        if scale is not None:                                         # q and k: a unit vector a head
            a = a * jax.lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True) + L2_EPS) * scale
        return heads(a)

    @jax.checkpoint
    def decay(x, down, up, a_log, dt_bias):
        step = _mm(_mm(x, down, "bsc,cr->bsr", precision), up, "bsr,re->bse", precision).astype(f32)
        return heads(-jnp.exp(a_log.astype(f32))[:, None] * jax.nn.softplus(
            step + dt_bias.astype(f32)).reshape(B, S, H, d))

    beta = jax.nn.sigmoid(_mm(x, p["wb"]["weight"], "bsc,ch->bsh", precision).astype(f32))
    return (short(x, p["wq"]["weight"], p["conv_q"]["weight"], d ** -0.5),
            short(x, p["wk"]["weight"], p["conv_k"]["weight"], 1.0),
            short(x, p["wv"]["weight"], p["conv_v"]["weight"], None),
            decay(x, p["f_down"]["weight"], p["f_up"]["weight"], p["A_log"], p["dt_bias"]),
            jnp.moveaxis(beta, 1, 0))


def _kda_output(p, x, o, cfg, precision):
    """``o [B, S, H, d]`` -> the mixer's output (the head norm, the gate, ``W_o``)."""
    B, S, H, d = o.shape
    gate = jax.nn.sigmoid(_mm(_mm(x, p["g_down"]["weight"], "bsc,cr->bsr", precision), p["g_up"]["weight"],
                              "bsr,re->bse", precision).astype(jnp.float32)).reshape(B, S, H, d)
    o = (_rms_norm(o, p["o_norm"]["weight"], float(cfg["rms_norm_eps"])) * gate).astype(x.dtype)
    return _mm(o.reshape(B, S, H * d), p["wo"]["weight"], "bse,ec->bsc", precision)


def _kda(p, x, cfg, precision):
    """The mixer: all of a sequence's heads in one pass of the recurrence (its
    steps are bound by latency, so one wide scan of ``S`` steps is the fastest).
    The projections either side of it are recomputed in the backward (cheap), so
    what a layer keeps is q, k, v, g, beta, o and the states at block starts, not
    every head-wide float32 array between them."""
    low = jnp.bfloat16 if precision == BF16_KDA else None
    o = _recurrence(*_kda_operands(p, x, cfg, precision), low)
    return jax.checkpoint(lambda p, x, o: _kda_output(p, x, jnp.moveaxis(o, 0, 1), cfg, precision))(p, x, o)


def _attention(p, x, cfg, precision):
    z = sizes(cfg)
    B, S, _ = x.shape
    H, dn, dr, dv = z["H"], z["dn"], z["dr"], z["dv"]
    q = _mm(x, p["wq"]["weight"], "bsc,ce->bse", precision).reshape(B, S, H, dn + dr)
    kv_a = _mm(x, p["wkv_a"]["weight"], "bsc,cr->bsr", precision)
    c_kv = _rms_norm(kv_a[..., :z["rkv"]], p["kv_norm"]["weight"], float(cfg["rms_norm_eps"]))
    kv = _mm(c_kv, p["wkv_b"]["weight"], "bsr,re->bse", precision).reshape(B, S, H, dn + dv)
    k_shared = kv_a[..., z["rkv"]:]                                   # [B, S, dr], every head's
    causal = jnp.tril(jnp.ones((S, S), bool))
    scale = (dn + dr) ** -0.5

    @jax.checkpoint
    def head(args):
        qh, kh, vh = args                                             # [B, S, d]
        kh = jnp.concatenate([kh, k_shared], axis=-1)
        s = _mm(qh, kh, "bqd,bkd->bqk", precision).astype(jnp.float32) * scale
        pr = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1).astype(vh.dtype)
        return _mm(pr, vh, "bqk,bkd->bqd", precision)

    by_head = lambda a: a.transpose(2, 0, 1, 3)
    o = jax.lax.map(head, (by_head(q), by_head(kv[..., :dn]), by_head(kv[..., dn:])))
    o = o.transpose(1, 2, 0, 3).reshape(B, S, H * dv)
    return _mm(o, p["wo"]["weight"], "bse,ec->bsc", precision)


def _swiglu(p, x, precision):
    up = jax.nn.silu(_mm(x, p["w_gate"]["weight"], "bsc,cf->bsf", precision)) \
        * _mm(x, p["w_up"]["weight"], "bsc,cf->bsf", precision)
    return _mm(up, p["w_down"]["weight"], "bsf,fc->bsc", precision)


def route(p, x, cfg, precision):
    """(chosen ids [B, S, K], their weights [B, S, K]), float32."""
    s = jax.nn.sigmoid(_mm(x, p["weight"], "bsc,ce->bse", precision).astype(jnp.float32))
    _, idx = jax.lax.top_k(s + jax.lax.stop_gradient(p["bias"].astype(jnp.float32)),
                           int(cfg["num_experts_per_token"]))
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    return idx, chosen / (chosen.sum(-1, keepdims=True) + 1e-20) * float(cfg["routed_scaling_factor"])


def routed_layer(p, x, cfg, precision, first=None, count=None, shared: bool = True):
    """Shared expert (unless ``shared`` is false) + the experts ``first .. first +
    count - 1`` (the configuration's share by default) of ``p["experts"]``, whose
    bank ``j`` is expert ``first + j``."""
    z = sizes(cfg)
    first = z["first"] if first is None else first
    count = z["held"] if count is None else count
    idx, g = route(p["router"], x, cfg, precision)
    y = _swiglu(p["shared"], x, precision) if shared else jnp.zeros_like(x)

    @jax.checkpoint
    def one(y, bank):                                     # one held expert on every token
        expert, e = bank
        g_e = jnp.sum(jnp.where(idx == e, g, 0.0), axis=-1)   # 0 where not chosen
        return y + g_e[..., None].astype(x.dtype) * _swiglu(expert, x, precision), None

    banks = jax.tree_util.tree_map(lambda a: a[:count], p["experts"])
    return jax.lax.scan(one, y, (banks, first + jnp.arange(count)))[0]


def _layer(p, x, cfg, precision, kind, routed):
    eps = float(cfg["rms_norm_eps"])
    u = _rms_norm(x, p["attention_norm"]["weight"], eps)
    # a KDA mixer is not checkpointed again inside its layer: every level of that walks the
    # recurrence once more, and the recurrence is what this reference's time is
    h = x + (_kda(p["kda"], u, cfg, precision) if kind == "K" else
             jax.checkpoint(lambda p, u: _attention(p, u, cfg, precision))(p["attention"], u))
    u = _rms_norm(h, p["ffn_norm"]["weight"], eps)
    ffn = routed_layer(p["feed_forward"], u, cfg, precision) if routed \
        else _swiglu(p["feed_forward"], u, precision)
    return h + ffn.astype(h.dtype)


def hidden_states(params, tokens, cfg, precision: str = "float32"):
    """tokens [B, S] -> the final-normed state [B, S, C]."""
    if precision not in PRECISIONS:
        raise ValueError(f"unknown precision {precision!r}")
    if precision == "bfloat16":
        params = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), params)
    z = sizes(cfg)
    x = params["tok_embeddings"]["weight"][tokens]
    for i, (p, kind) in enumerate(zip(params["layers"], z["kinds"])):
        layer = functools.partial(_layer, cfg=cfg, precision=precision, kind=kind, routed=i >= z["Ld"])
        # a sequence at a time: the backward holds one sequence's activations of one layer, and
        # the map's carry only this layer's gradients
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
    """:func:`loss_and_grads`, compiled once a (configuration, precision) a
    process. ``train_job_arch.reference_steps`` calls a reference's function of
    this name as it is and jits ``loss_and_grads`` anew at every call otherwise,
    which a process that follows several seeds (``control_kda.py``) would pay a
    compilation for each time. The sequences are walked inside the program
    (``hidden_states``), not summed outside it: the module's docstring."""
    return _compiled_loss_and_grads(json.dumps(cfg, sort_keys=True), precision)(params, inputs, targets)
