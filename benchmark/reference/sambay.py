"""Plain reference for the decoder-hybrid-decoder with Mamba layers, gated
memory units and differential attention (``architecture: sambay``;
Phi-4-mini-flash-reasoning, arXiv:2507.06607 "Decoder-Hybrid-Decoder
Architecture for Efficient Reasoning with Long Generation": SambaY with
differential attention).

Layer equations (ISSUE 43; ``d`` the hidden size, 2,560 published; LN =
LayerNorm with weight and bias, eps 1e-5; ``u`` a mixer's input; no positional
encoding anywhere):

- Block, every kind: ``h = x + Mix(LN1(x))``, ``x' = h + W_down(SiLU(g) * v)``,
  ``[g, v] = W_gate_up LN2(h)``, width 10,240, no bias. Input ``x0 = E[ids]``,
  unscaled; output ``logits = LN_f(x_L) E^T``, no bias; mean cross-entropy over
  the vocabulary (200,064).
- **M (Mamba-1)**, ``d_inner`` 5,120, ``N`` 16, conv width 4, ``dt_rank`` =
  ceil(2560 / 16) = 160: ``[a, z] = W_in u`` (no bias); ``c_t = SiLU(sum_{j=0..3}
  w_conv[:, j] * a_{t-3+j} + b_conv)`` (depthwise, causal, zeros before t = 0);
  ``[r, B, C] = W_x c`` with ``r`` 160, ``B``, ``C`` 16 wide (no bias); ``Delta =
  softplus(W_dt r + b_dt)``; ``A = -exp(A_log)``, ``[5120, 16]``; for channel
  ``i``, state ``n``: ``h_t[i, n] = exp(Delta_t[i] A[i, n]) h_{t-1}[i, n] +
  Delta_t[i] B_t[n] c_t[i]``, ``h_{-1} = 0``; ``y_t[i] = sum_n C_t[n] h_t[i, n] +
  D[i] c_t[i]``; ``Mix = W_out(y * SiLU(z))`` (no bias). The scan in float32.
  The **memory** ``m`` is ``y`` (before the gate) of the last M layer before the
  full layer.
- **G (gated memory unit)**: ``Mix = W_2(m * SiLU(W_1 u))``, ``W_1: 2560 -> 5120``,
  ``W_2: 5120 -> 2560``, no bias, ``m`` the memory above, the same tensor for
  every G layer.
- **S / F (differential attention, window / full)**: ``[q, k, v] = W_qkv u +
  b_qkv``: 40 query heads, 20 key and 20 value heads of 64. Pair them: ``q ->
  [20, 2, 64]`` gives ``q1, q2``; ``k -> [10, 2, 64]`` gives ``k1, k2``; ``v ->
  [10, 2, 64]``, the two halves concatenated to ``vbar`` of 128. Query pair ``j``
  reads key/value pair ``j // 2``. ``A_i = softmax(q_i k_i^T / 8 + mask) vbar`` for
  ``i`` = 1, 2; ``lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init``, four
  learned 64-vectors a layer, ``lambda_init = 0.8 - 0.6 exp(-0.3 l)``, ``l`` the
  layer's index in the stack that is run; ``o_j = (1 - lambda_init)
  RMSNorm_128(A_1 - lambda A_2; gain g, eps 1e-5)``; ``Mix = W_o concat_j(o_j) +
  b_o``. Mask S: causal and ``t - s < 512``; mask F: causal.
- **C (cross-attention)**: ``q = W_q u + b_q`` only; ``k``, ``v`` are the F
  layer's, unchanged and the same for every C layer; its own four lambda
  vectors, gain and ``W_o, b_o``; causal mask.
- Published stack, 32 layers: ``(M S) x 8, M F, (G C) x 7``: even layers are M
  below index 18 and G from 18, odd layers are S below 17, F at 17, C from 19;
  ``m`` comes from layer 16, ``K, V`` from layer 17. Which layer is which comes
  from the configuration's ``layer_kinds``, entry by entry.

Written in straightforward ``jax.numpy``: no kernels, no chunked state. It
imports nothing of the program and makes its own weights from the seed, in the
tree the program trains (``layers`` a list of per-layer dicts).

Departures from the published description, each a matter of storage or memory
and none of the mathematics:

- ``W_gate_up`` is held as two matrices ``w_gate``, ``w_up`` (the same numbers,
  the tree of this repository's SwiGLU).
- The scan is a sequential ``lax.scan`` over time, ``SCAN_BLOCK`` steps at a time
  under ``jax.checkpoint``, so that its backward holds one block's states
  (``[256, 5120, 16]`` float32, 84 MB) and not the sequence's (5.4 GB).
- Attention is an explicit masked softmax over whole key rows, one key/value
  pair group and one block of ``ATTN_BLOCK`` query rows at a time; the
  cross-entropy one block of ``CE_BLOCK`` positions at a time (0.41 GB of
  float32 logits at 200,064); every layer, and inside it the mixer and the FFN,
  is ``jax.checkpoint``-ed, so a 16,384-token step fits beside float32 weights
  and gradients on a 16 GB chip.

``precision`` as in ``llama_dense.py``: ``float32`` (matmuls at HIGHEST),
``fp8`` (both operands of every matmul rounded through float8_e4m3; the
control), ``bfloat16`` (weights, activations, the scan's state and the softmax
difference in bfloat16), ``float32_default``; and one of this file's own,
``float32_bf16_scan``: the reference proper but for the two places this
architecture states float32 beyond the matmuls' accumulation, the scan
(operands and state) and the softmax difference, which it computes in
bfloat16: a diagnosis of whether the comparison would catch a program that
did.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from benchmark.reference import llama_dense
from benchmark.reference.llama_dense import CE_BLOCK, INIT_STD, _mm

LAMBDA_STD = 0.1
DT_MIN, DT_MAX = 1e-3, 1e-1
ATTN_BLOCK = 1024   # query rows of one block of explicit scores
SCAN_BLOCK = 256    # time steps of one checkpointed block of the scan
KINDS = ("M", "S", "F", "G", "C")
BF16_SCAN = "float32_bf16_scan"   # _mm takes it as float32 at HIGHEST
PRECISIONS = llama_dense.PRECISIONS + (BF16_SCAN,)


def sizes(cfg: Dict[str, Any]) -> Dict[str, Any]:
    ssm = cfg["ssm"]
    z = {"C": int(cfg["hidden_size"]), "F": int(cfg["intermediate_size"]),
         "L": int(cfg["num_hidden_layers"]), "H": int(cfg["num_attention_heads"]),
         "G": int(cfg["num_key_value_heads"]), "D": int(cfg["head_dim"]),
         "V": int(cfg["vocab_size"]), "W": int(cfg["sliding_window"]),
         "N": int(ssm["d_state"]), "K": int(ssm["d_conv"]),
         "Di": int(ssm["expand"]) * int(cfg["hidden_size"]), "R": int(ssm["dt_rank"]),
         "kinds": [str(k) for k in cfg["layer_kinds"]]}
    if len(z["kinds"]) != z["L"] or set(z["kinds"]) - set(KINDS):
        raise ValueError(f"layer_kinds must name {z['L']} layers of {KINDS}")
    return z


def lambda_init(layer: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def param_shapes(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """Tree of (shape, init): a float is normal(0, std), None ones, "zeros",
    "a_log" (log of 1 .. N along the last axis) or "dt_bias" (the inverse
    softplus of a step drawn log-uniformly in [1e-3, 1e-1])."""
    z = sizes(cfg)
    C, Di, N, R, H, G, D = (z[k] for k in ("C", "Di", "N", "R", "H", "G", "D"))
    dense = lambda shape: {"weight": (shape, INIT_STD)}
    biased = lambda shape: {"weight": (shape, INIT_STD), "bias": (shape[-1:], "zeros")}
    ln = lambda: {"weight": ((C,), None), "bias": ((C,), "zeros")}

    def diff(kind):
        first = {"wq": biased((C, H * D))} if kind == "C" else {"wqkv": biased((C, (H + 2 * G) * D))}
        return {**first, "wo": biased((H * D, C)),
                **{f"lambda_{n}": ((D,), LAMBDA_STD) for n in ("q1", "k1", "q2", "k2")},
                "subln": {"weight": ((2 * D,), None)}}

    mixers = {
        "M": lambda: {"ssm": {
            "in_proj": dense((C, 2 * Di)),
            "conv": {"weight": ((Di, z["K"]), INIT_STD), "bias": ((Di,), "zeros")},
            "x_proj": dense((Di, R + 2 * N)),
            "dt_proj": {"weight": ((R, Di), INIT_STD), "bias": ((Di,), "dt_bias")},
            "A_log": ((Di, N), "a_log"), "D": ((Di,), None),
            "out_proj": dense((Di, C))}},
        "G": lambda: {"gmu": {"w1": dense((C, Di)), "w2": dense((Di, C))}},
    }

    def layer(kind):
        mixer = mixers.get(kind, lambda: {"attention": diff(kind)})()
        return {"attention_norm": ln(), **mixer, "ffn_norm": ln(),
                "feed_forward": {"w_gate": dense((C, z["F"])), "w_up": dense((C, z["F"])),
                                 "w_down": dense((z["F"], C))}}

    return {"tok_embeddings": dense((z["V"], C)),
            "layers": [layer(k) for k in z["kinds"]],
            "norm": ln()}


def _is_spec(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def make_params(seed, cfg: Dict[str, Any]):
    """Float32 weights from ``seed`` (a traced or concrete uint32 scalar); each
    leaf draws from the key folded with its index in the flattened tree."""
    leaves, treedef = jax.tree_util.tree_flatten(param_shapes(cfg), is_leaf=_is_spec)
    key = jax.random.PRNGKey(seed)
    out = []
    for i, (shape, init) in enumerate(leaves):
        if init is None:
            a = jnp.ones(shape, jnp.float32)
        elif init == "zeros":
            a = jnp.zeros(shape, jnp.float32)
        elif init == "a_log":
            a = jnp.log(jnp.broadcast_to(jnp.arange(1, shape[-1] + 1, dtype=jnp.float32), shape))
        elif init == "dt_bias":
            u = jax.random.uniform(jax.random.fold_in(key, i), shape, jnp.float32)
            step = jnp.exp(u * (math.log(DT_MAX) - math.log(DT_MIN)) + math.log(DT_MIN))
            a = step + jnp.log(-jnp.expm1(-step))
        else:
            a = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32) * init
        out.append(a)
    return jax.tree_util.tree_unflatten(treedef, out)


def init_params(seed: int, cfg: Dict[str, Any], shardings=None):
    fn = jax.jit(functools.partial(make_params, cfg=cfg), out_shardings=shardings)
    return fn(jnp.uint32(seed % (2 ** 32)))


# -- arithmetic -----------------------------------------------------------------
def _layer_norm(x, p, eps):
    xf = x.astype(jnp.float32)
    xf = xf - jnp.mean(xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * p["weight"].astype(jnp.float32) + p["bias"].astype(jnp.float32)).astype(x.dtype)


def _linear(x, p, precision):
    y = _mm(x, p["weight"], "bsc,ce->bse", precision)
    return y + p["bias"].astype(y.dtype) if "bias" in p else y


def selective_scan(c, delta, A, B, C, D):
    """The recurrence, one time step after another, in the operands' dtype
    (float32 in the reference proper)."""
    Bt, S, Di = c.shape

    def step(h, s):
        x_t, dt_t, b_t, c_t = s
        h = jnp.exp(dt_t[..., None] * A) * h + (dt_t * x_t)[..., None] * b_t[:, None, :]
        return h, jnp.sum(h * c_t[:, None, :], axis=-1) + D * x_t

    @jax.checkpoint
    def block(h, xs):
        return jax.lax.scan(step, h, xs)

    blk = math.gcd(SCAN_BLOCK, S)
    split = lambda a: a.swapaxes(0, 1).reshape((S // blk, blk, Bt, a.shape[-1]))
    _, ys = jax.lax.scan(block, jnp.zeros((Bt, Di, A.shape[1]), c.dtype),
                         tuple(split(a) for a in (c, delta, B, C)))
    return ys.reshape(S, Bt, Di).swapaxes(0, 1)


def _mamba(p, u, cfg, precision):
    """→ (Mix, y): ``y`` the scan's output, before the gate."""
    z = sizes(cfg)
    S, R, N = u.shape[1], z["R"], z["N"]
    a, gate = jnp.split(_mm(u, p["in_proj"]["weight"], "bsc,ce->bse", precision), 2, axis=-1)
    padded = jnp.pad(a, ((0, 0), (z["K"] - 1, 0), (0, 0)))
    w = p["conv"]["weight"].astype(a.dtype)
    c = jax.nn.silu(sum(w[:, j] * padded[:, j:j + S] for j in range(z["K"]))
                    + p["conv"]["bias"].astype(a.dtype))
    r, b_t, c_t = jnp.split(_mm(c, p["x_proj"]["weight"], "bsd,de->bse", precision),
                            (R, R + N), axis=-1)
    delta = jax.nn.softplus(_mm(r, p["dt_proj"]["weight"], "bsr,rd->bsd", precision)
                            + p["dt_proj"]["bias"].astype(r.dtype))
    low = jnp.bfloat16 if precision == BF16_SCAN else c.dtype
    y = selective_scan(*(a.astype(low) for a in (c, delta, -jnp.exp(p["A_log"]), b_t, c_t, p["D"])))
    y = y.astype(c.dtype)
    return _mm(y * jax.nn.silu(gate), p["out_proj"]["weight"], "bsd,dc->bsc", precision), y


def _gmu(p, u, m, precision):
    gate = jax.nn.silu(_mm(u, p["w1"]["weight"], "bsc,cd->bsd", precision))
    return _mm(m.astype(gate.dtype) * gate, p["w2"]["weight"], "bsd,dc->bsc", precision)


def _softmax_map(q, k, vbar, cfg, precision, window):
    """``q [B, S, P, D]`` (P query pairs' first or second queries), ``k [B, S, Pk,
    D]``, ``vbar [B, S, Pk, 2 D]`` → ``softmax(q k^T / sqrt(D) + mask) vbar`` as
    ``[B, S, P, 2 D]``; query pair ``j`` reads key/value pair ``j // (P / Pk)``."""
    z = sizes(cfg)
    B, S, P, D = q.shape
    Pk = k.shape[2]
    blk = min(ATTN_BLOCK, S)
    if S % blk:
        raise ValueError(f"sequence {S} is not a multiple of {blk}")
    cols = jnp.arange(S)[None, :]

    @jax.checkpoint
    def rows(args):
        qb, kg, vg, row0 = args                                   # [B, blk, P/Pk, D], [B, S, .]
        i = row0 + jnp.arange(blk)[:, None]
        seen = cols <= i
        if window:
            seen = seen & (i - cols < z["W"])
        s = _mm(qb, kg, "bqhd,bkd->bhqk", precision).astype(jnp.float32) * D ** -0.5
        pr = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), axis=-1).astype(vg.dtype)
        return _mm(pr, vg, "bhqk,bkd->bqhd", precision)

    def group(args):
        qg, kg, vg = args
        n = S // blk
        blocks = qg.reshape(B, n, blk, P // Pk, D).swapaxes(0, 1)
        o = jax.lax.map(lambda a: rows((a[0], kg, vg, a[1])), (blocks, jnp.arange(n) * blk))
        return o.swapaxes(0, 1).reshape(B, S, P // Pk, 2 * D)

    by_group = q.reshape(B, S, Pk, P // Pk, D).transpose(2, 0, 1, 3, 4)
    o = jax.lax.map(group, (by_group, k.transpose(2, 0, 1, 3), vbar.transpose(2, 0, 1, 3)))
    return o.transpose(1, 2, 0, 3, 4).reshape(B, S, P, 2 * D)


def _diff_attention(p, u, cfg, precision, kind, layer, kv=None):
    """→ (Mix, (k, v)): ``k, v [B, S, G, D]`` this layer's own (S, F) or the ones
    it was handed (C)."""
    z = sizes(cfg)
    B, S, _ = u.shape
    H, G, D = z["H"], z["G"], z["D"]
    if kind == "C":
        q = _linear(u, p["wq"], precision).reshape(B, S, H, D)
        k, v = kv
    else:
        q, k, v = jnp.split(_linear(u, p["wqkv"], precision), (H * D, (H + G) * D), axis=-1)
        q, k, v = q.reshape(B, S, H, D), k.reshape(B, S, G, D), v.reshape(B, S, G, D)
    qp, kp = q.reshape(B, S, H // 2, 2, D), k.reshape(B, S, G // 2, 2, D)
    vbar = v.reshape(B, S, G // 2, 2 * D)
    a1, a2 = (_softmax_map(qp[:, :, :, i], kp[:, :, :, i], vbar, cfg, precision, kind == "S")
              for i in (0, 1))
    f32 = lambda n: p[n].astype(jnp.float32)
    lam = jnp.exp(jnp.sum(f32("lambda_q1") * f32("lambda_k1"))) \
        - jnp.exp(jnp.sum(f32("lambda_q2") * f32("lambda_k2"))) + lambda_init(layer)
    if precision == BF16_SCAN:
        a1, a2 = a1.astype(jnp.bfloat16), a2.astype(jnp.bfloat16)
    d = a1 - lam.astype(a1.dtype) * a2                             # in the maps' dtype
    df = d.astype(jnp.float32)
    normed = df * jax.lax.rsqrt(jnp.mean(df * df, axis=-1, keepdims=True)
                                + float(cfg["layer_norm_eps"]))
    o = (normed * p["subln"]["weight"].astype(jnp.float32) * (1.0 - lambda_init(layer))).astype(q.dtype)
    return _linear(o.reshape(B, S, H * D), p["wo"], precision), (k, v)


def _ffn(p, x, precision):
    up = jax.nn.silu(_mm(x, p["w_gate"]["weight"], "bsc,cf->bsf", precision)) \
        * _mm(x, p["w_up"]["weight"], "bsc,cf->bsf", precision)
    return _mm(up, p["w_down"]["weight"], "bsf,fc->bsc", precision)


def _layer(p, x, m, kv, cfg, precision, kind, layer):
    """→ (x', what this layer makes for later ones: ``y`` (M), ``(k, v)`` (F), else None)."""
    eps = float(cfg["layer_norm_eps"])

    @jax.checkpoint
    def mixer(p, x, m, kv):
        u = _layer_norm(x, p["attention_norm"], eps)
        if kind == "M":
            y, made = _mamba(p["ssm"], u, cfg, precision)
        elif kind == "G":
            y, made = _gmu(p["gmu"], u, m, precision), None
        else:
            y, made = _diff_attention(p["attention"], u, cfg, precision, kind, layer, kv)
            made = made if kind == "F" else None
        return x + y.astype(x.dtype), made

    @jax.checkpoint
    def ffn(p, h):
        return h + _ffn(p["feed_forward"], _layer_norm(h, p["ffn_norm"], eps), precision)

    h, made = mixer(p, x, m, kv)
    return ffn(p, h), made


def hidden_states(params, tokens, cfg, precision: str = "float32"):
    """tokens [B, S] → the final-normed state [B, S, C]."""
    if precision not in PRECISIONS:
        raise ValueError(f"unknown precision {precision!r}")
    if precision == "bfloat16":
        params = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), params)
    z = sizes(cfg)
    x = params["tok_embeddings"]["weight"][tokens]
    full = z["kinds"].index("F") if "F" in z["kinds"] else 0
    memory_layer = max((i for i, k in enumerate(z["kinds"][:full]) if k == "M"), default=None)
    m = kv = None
    for i, (p, kind) in enumerate(zip(params["layers"], z["kinds"])):
        x, made = jax.checkpoint(functools.partial(
            _layer, cfg=cfg, precision=precision, kind=kind, layer=i))(
                p, x, m if kind == "G" else None, kv if kind == "C" else None)
        if i == memory_layer:
            m = made
        elif kind == "F":
            kv = made
    return _layer_norm(x, params["norm"], float(cfg["layer_norm_eps"]))


def logits_at(params, tokens, cfg, precision: str = "float32"):
    """Float32 logits [B, S, V]: the head is the embedding."""
    h = hidden_states(params, tokens, cfg, precision)
    w = params["tok_embeddings"]["weight"].astype(h.dtype)
    return _mm(h, w, "bsc,vc->bsv", precision).astype(jnp.float32)


def loss(params, inputs, targets, cfg, precision: str = "float32"):
    """Mean over every position of logsumexp - gold, CE_BLOCK positions at a time."""
    h = hidden_states(params, inputs, cfg, precision)
    w = params["tok_embeddings"]["weight"].astype(h.dtype)
    B, S, _ = h.shape
    blk = min(CE_BLOCK, S)
    if S % blk:
        raise ValueError(f"sequence {S} is not a multiple of {blk}")
    split = lambda a: a.reshape((B, S // blk, blk) + a.shape[2:]).swapaxes(0, 1)

    @jax.checkpoint
    def block(args):
        hh, tt = args
        lg = _mm(hh, w, "bsc,vc->bsv", precision).astype(jnp.float32)
        gold = jnp.take_along_axis(lg, tt[..., None], axis=-1)[..., 0]
        return jnp.sum(jax.nn.logsumexp(lg, axis=-1) - gold)

    return jnp.sum(jax.lax.map(block, (split(h), split(targets)))) / (B * S)


def loss_and_grads(params, inputs, targets, cfg, precision: str = "float32"):
    """((L,), gradients of L) on the whole batch at once."""
    value, grads = jax.value_and_grad(loss)(params, inputs, targets, cfg, precision)
    return (value,), grads
