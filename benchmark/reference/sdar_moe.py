"""Plain reference for the softmax-routed expert decoder trained by diffusion
over blocks (``architecture: sdar_moe``).

Written from the equations in ISSUE 48 ("Layer equations"; the configuration
file's ``equations`` repeats where they come from), in straightforward
``jax.numpy``: no kernels, no sort, no buffers, no tile plan. It imports nothing
of the program and makes its own weights from the seed, in the tree the program
trains (``layers`` is a list of per-layer dicts).

**What it is handed.** The noise is the batch's own, never drawn here:
``inputs [B, 2, L]`` int32 holds the noised copy ``x_t`` (row 0) and the clean
copy ``x_0`` (row 1) of every sequence, ``targets [B, L]`` float32 the loss
weights ``m_i / t_b(i)`` (0 where the token was not replaced). A packed row has
no padding, so ``N = B L`` (the traffic kind refuses a batch whose mask is not
all ones).

**Rows and mask.** ``Z_0 = [Emb(x_t) ; Emb(x_0)]``, ``[2L, C]``, positions
``[0..L-1 ; 0..L-1]``. With ``blk(r) = (r mod L) // B'`` query row ``r`` sees key
row ``c`` iff: both noised and ``blk(c) == blk(r)``; ``r`` noised, ``c`` clean and
``blk(c) < blk(r)``; both clean and ``blk(c) <= blk(r)``; ``r`` clean, ``c``
noised: never (:func:`seen`, the four cases written out).

**Layer.** ``H = Z + Attn(RMSNorm(Z))``, ``Z' = H + MoE(RMSNorm(H))``; QK-normed
GQA with half-split rotary on the whole head, no bias, no gate; ``MoE``: softmax
over all experts in float32, top-k, weights renormalised over the chosen, and
only the ``experts_held`` (first, count) add to the output: each held expert is
applied to every row and weighted by that row's gate for it (zero where it was
not chosen). What absent experts would add is left out, here as in the program.

**Head.** Final norm and logits of the noised rows alone; ``loss = (1 / N) sum_i
w_i CE(logits_i, x_0[i])``: position ``i`` predicts the token at ``i``.

Departures from a textbook forward, for memory only: attention is an explicit
masked softmax over whole key rows, taken one key/value group and one block of
``ATTN_BLOCK`` query rows at a time (the ``[2L, 2L]`` scores never stand whole),
the held experts one at a time, the cross-entropy one block of positions at a
time, and every layer is ``jax.checkpoint``-ed.

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

ATTN_BLOCK = 1024  # query rows of one block of explicit scores


def sizes(cfg: Dict[str, Any]) -> Dict[str, int]:
    held = cfg["experts_held"]
    return {
        "C": int(cfg["hidden_size"]), "L": int(cfg["num_hidden_layers"]),
        "H": int(cfg["num_attention_heads"]), "G": int(cfg["num_key_value_heads"]),
        "D": int(cfg["head_dim"]), "V": int(cfg["vocab_size"]), "E": int(cfg["num_experts"]),
        "K": int(cfg["num_experts_per_tok"]), "Fe": int(cfg["moe_intermediate_size"]),
        "Bp": int(cfg["block_length"]),
        "first": int(held["first"]), "held": int(held["count"]),
    }


def param_shapes(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """Tree of (shape, init): a float is normal(0, std), None ones."""
    z = sizes(cfg)
    C, H, G, D, Fe, held = z["C"], z["H"], z["G"], z["D"], z["Fe"], z["held"]
    res_std = INIT_STD / (2 * z["L"]) ** 0.5
    w = lambda shape, init: {"weight": (shape, init)}
    layer = lambda: {
        "attention_norm": w((C,), None),
        "attention": {"wq": w((C, H * D), INIT_STD), "wk": w((C, G * D), INIT_STD),
                      "wv": w((C, G * D), INIT_STD), "q_norm": w((D,), None),
                      "k_norm": w((D,), None), "wo": w((H * D, C), res_std)},
        "ffn_norm": w((C,), None),
        "feed_forward": {"router": w((C, z["E"]), INIT_STD),
                         "experts": {"w_gate": w((held, C, Fe), INIT_STD),
                                     "w_up": w((held, C, Fe), INIT_STD),
                                     "w_down": w((held, Fe, C), res_std)}},
    }
    return {
        "tok_embeddings": w((z["V"], C), INIT_STD),
        "layers": [layer() for _ in range(z["L"])],
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
def seen(r, c, L: int, Bp: int):
    """Whether query row ``r`` sees key row ``c`` of the ``2L`` rows [noised ;
    clean] (integer arrays that broadcast), the four cases one by one."""
    r_clean, c_clean = r >= L, c >= L
    rb, cb = (r % L) // Bp, (c % L) // Bp
    return ((~r_clean & ~c_clean & (cb == rb))      # noised on noised: its own block
            | (~r_clean & c_clean & (cb < rb))      # noised on clean: every earlier block
            | (r_clean & c_clean & (cb <= rb)))     # clean on clean: causal by block
    # clean on noised: never


def _attention(p, x, cfg, precision):
    z = sizes(cfg)
    B, S, _ = x.shape                                            # S = 2L
    H, G, D, L = z["H"], z["G"], z["D"], S // 2
    eps = float(cfg["rms_norm_eps"])
    proj = lambda name, heads: _mm(x, p[name]["weight"], "bsc,ce->bse", precision).reshape(
        B, S, heads, D)
    q = _rms_norm(proj("wq", H), p["q_norm"]["weight"], eps)
    k = _rms_norm(proj("wk", G), p["k_norm"]["weight"], eps)
    v = proj("wv", G)
    positions = jnp.arange(S) % L
    q, k = (_rope(a, positions, float(cfg["rope_theta"])) for a in (q, k))
    blk = min(ATTN_BLOCK, S)
    if S % blk:
        raise ValueError(f"{S} rows are not a multiple of {blk}")
    cols = jnp.arange(S)[None, :]

    @jax.checkpoint
    def rows(args):
        qb, kg, vg, row0 = args                                   # [B, blk, H/G, D], [B, S, D]
        mask = seen(row0 + jnp.arange(blk)[:, None], cols, L, z["Bp"])
        s = _mm(qb, kg, "bqhd,bkd->bhqk", precision).astype(jnp.float32) * D ** -0.5
        pr = jax.nn.softmax(jnp.where(mask[None, None], s, -jnp.inf), axis=-1).astype(vg.dtype)
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
    return _mm(o, p["wo"]["weight"], "bse,ec->bsc", precision)


def _swiglu(p, x, precision):
    up = jax.nn.silu(_mm(x, p["w_gate"]["weight"], "bsc,cf->bsf", precision)) \
        * _mm(x, p["w_up"]["weight"], "bsc,cf->bsf", precision)
    return _mm(up, p["w_down"]["weight"], "bsf,fc->bsc", precision)


def route(p, x, cfg, precision):
    """(chosen ids [B, S, K], their weights [B, S, K]), float32: softmax over
    every expert, the top-k, renormalised over the chosen."""
    probs = jax.nn.softmax(_mm(x, p["weight"], "bsc,ce->bse", precision).astype(jnp.float32), axis=-1)
    chosen, idx = jax.lax.top_k(probs, int(cfg["num_experts_per_tok"]))
    return idx, chosen / (chosen.sum(-1, keepdims=True) + 1e-20)


def routed_layer(p, x, cfg, precision, first=None, count=None):
    """The experts ``first .. first + count - 1`` (the configuration's share by
    default) of ``p["experts"]``, whose bank ``j`` is expert ``first + j``: a
    masked sum, one expert at a time. No shared expert."""
    z = sizes(cfg)
    first = z["first"] if first is None else first
    count = z["held"] if count is None else count
    idx, g = route(p["router"], x, cfg, precision)

    @jax.checkpoint
    def add_expert(y, bank_and_id):
        bank, e = bank_and_id
        g_e = jnp.sum(jnp.where(idx == e, g, 0.0), axis=-1)       # 0 where not chosen
        return y + g_e[..., None].astype(y.dtype) * _swiglu(bank, x, precision), None

    y, _ = jax.lax.scan(add_expert, jnp.zeros_like(x), (p["experts"], first + jnp.arange(count)))
    return y


def _layer(p, x, cfg, precision):
    eps = float(cfg["rms_norm_eps"])
    x = x + _attention(p["attention"], _rms_norm(x, p["attention_norm"]["weight"], eps), cfg, precision)
    return x + routed_layer(p["feed_forward"], _rms_norm(x, p["ffn_norm"]["weight"], eps), cfg,
                            precision)


def hidden_states(params, inputs, cfg, precision: str = "float32"):
    """inputs [B, 2, L] (noised copy, clean copy) → the final-normed state of
    the noised rows [B, L, C]."""
    if precision not in PRECISIONS:
        raise ValueError(f"unknown precision {precision!r}")
    if precision == "bfloat16":
        params = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), params)
    B, two, L = inputs.shape
    if two != 2 or L % sizes(cfg)["Bp"]:
        raise ValueError(f"inputs {inputs.shape}: want [B, 2, L] with L a multiple of the block length")
    x = params["tok_embeddings"]["weight"][inputs.reshape(B, 2 * L)]     # [x_t ; x_0]
    for p in params["layers"]:
        x = jax.checkpoint(functools.partial(_layer, cfg=cfg, precision=precision))(p, x)
    return _rms_norm(x[:, :L], params["norm"]["weight"], float(cfg["rms_norm_eps"]))


def _head_weight(params, precision):
    w = params["output"]["weight"]
    return w.astype(jnp.bfloat16) if precision == "bfloat16" else w


def logits_at(params, inputs, cfg, precision: str = "float32"):
    """Float32 logits of the noised rows [B, L, V]."""
    h = hidden_states(params, inputs, cfg, precision)
    return _mm(h, _head_weight(params, precision), "bsc,cv->bsv", precision).astype(jnp.float32)


def loss(params, inputs, weights, cfg, precision: str = "float32"):
    """``(1 / (B L)) sum_i weights_i (logsumexp_i - logit_i[x_0[i]])``, CE_BLOCK
    positions at a time."""
    h = hidden_states(params, inputs, cfg, precision)
    w = _head_weight(params, precision)
    gold_ids = inputs[:, 1]
    B, S, _ = h.shape
    blk = min(CE_BLOCK, S)
    if S % blk:
        raise ValueError(f"sequence {S} is not a multiple of {blk}")
    split = lambda a: a.reshape((B, S // blk, blk) + a.shape[2:]).swapaxes(0, 1)

    @jax.checkpoint
    def block(args):
        hh, tt, ww = args
        lg = _mm(hh, w, "bsc,cv->bsv", precision).astype(jnp.float32)
        gold = jnp.take_along_axis(lg, tt[..., None], axis=-1)[..., 0]
        return jnp.sum(ww * (jax.nn.logsumexp(lg, axis=-1) - gold))

    return jnp.sum(jax.lax.map(block, (split(h), split(gold_ids),
                                       split(weights.astype(jnp.float32))))) / (B * S)


def loss_and_grads(params, inputs, targets, cfg, precision: str = "float32"):
    """((L,), gradients of L) on the whole batch at once; ``targets`` holds the
    loss weights (the gold tokens are the clean copy in ``inputs``)."""
    value, grads = jax.value_and_grad(loss)(params, inputs, targets, cfg, precision)
    return (value,), grads
