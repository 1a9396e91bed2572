"""Latent-attention, sparse-expert decoder with a multi-stream residual path
(architecture ``xing_mla_moe``; the Xing4.0 / DeepSeek-V3 family).

What differs from ``models/llama.py``, block by block:

- **Residual path.** The state is ``n = hc_mult`` streams ``X [n, B, S, C]``
  (manifold-constrained hyper-connections, arXiv:2512.24880). Around each
  sub-layer ``F`` (attention; FFN or experts), with parameters of its own:
  ``x^ = x / sqrt(mean(x^2) + eps)`` over all ``nC`` features of a token;
  ``[a_pre, a_post, a_res] = x^ Phi``; ``H_pre = sigmoid(alpha_pre a_pre +
  b_pre)``; ``H_post = 2 sigmoid(alpha_post a_post + b_post)``; ``H_res`` =
  ``sinkhorn_iters`` row-then-column normalisations of ``exp(clip(alpha_res
  a_res + b_res))``; ``u = sum_i H_pre[i] X[i]``; ``y = F(RMSNorm(u))``;
  ``X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y``. The model replicates the
  embedding into the streams and sums them before the final norm.
- **Attention.** Multi-head latent attention: q and k/v through low-rank
  down-projections with an RMSNorm on each latent, a decoupled rotary key
  of ``qk_rope_head_dim`` shared by all heads, YaRN frequencies and softmax
  scale; q and k are ``qk_nope + qk_rope`` wide and v ``v_head_dim``, which
  the flash kernels take from the call's shapes.
- **FFN.** ``first_k_dense`` leading dense layers, then routed layers:
  sigmoid scores, a selection bias that is a buffer, weights normalised over
  the chosen and scaled, a shared expert every token visits, and the routed
  experts this chip *holds*: ``experts_held = (first, count)`` of the
  router's ``n_routed_experts``. Selections of experts held elsewhere add
  nothing here (on one chip there is no exchange to fetch them).
- **Loss.** One multi-token-prediction module (depth 1): ``h' = [RMSNorm(h)
  ; RMSNorm(Emb(t_{i+1}))] W``, a routed layer of its own, its own final norm,
  the shared output weight; ``L = CE(main, t_{i+1}) + w CE(mtp, t_{i+2})``.

Training path only: no KV cache, no serving step (the paged pool holds k and
v per head; a latent cache is another layout).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops.attention import attention_core
from . import moe as moe_lib
from . import stack
from .llama import apply_rope, mlp_block, rms_norm
from .registry import Architecture, register

Params = Dict[str, Any]


@dataclass(frozen=True)
class XingArgs:
    vocab_size: int = 259
    hidden_size: int = 128
    intermediate_size: int = 256       # the leading dense layers' FFN
    num_layers: int = 3                # dense + routed, the MTP module's not counted
    num_heads: int = 4
    q_lora_rank: int = 32
    kv_lora_rank: int = 32
    qk_nope_head_dim: int = 16
    qk_rope_head_dim: int = 8
    v_head_dim: int = 16
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    # YaRN (rope.scaling): applied at every length, as the published code does
    yarn_factor: float = 1.0
    yarn_original_max: int = 4096
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale: float = 1.0
    yarn_mscale_all_dim: float = 0.0
    attention_type: str = "simple"     # simple | flash
    # experts
    first_k_dense: int = 1
    n_routed_experts: int = 8          # the router's width
    num_experts_per_tok: int = 2
    moe_intermediate_size: int = 64
    n_shared_experts: int = 1
    routed_scaling_factor: float = 1.0
    experts_held: Tuple[int, int] = (0, 8)   # (first id, count) of the experts this chip holds
    # residual streams
    hc_mult: int = 4
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_res_clamp: Tuple[float, float] = (-30.0, 30.0)
    # multi-token prediction
    mtp_layers: int = 1                # 0 | 1
    mtp_loss_weight: float = 0.3
    tie_word_embeddings: bool = False
    matmul_precision: Optional[str] = None

    # What the trainer asks of any model's args.
    @property
    def is_moe(self) -> bool:
        return True

    @property
    def num_local_experts(self) -> int:   # width of the routing statistics
        return self.n_routed_experts

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @classmethod
    def from_config(cls, model_cfg: Any, vocab_size: int) -> "XingArgs":
        sec = lambda name: dict(getattr(model_cfg, name, None) or {})
        att, rope, norm, moe, mla, hc, mtp = (sec(k) for k in (
            "attention", "rope", "normalization", "moe", "mla", "hyper_connections", "mtp"))
        yarn = rope.get("scaling") or {}
        n_routed = int(moe["n_routed_experts"])
        held = tuple(int(v) for v in (moe.get("experts_held") or (0, n_routed)))
        if held[0] < 0 or held[1] < 1 or held[0] + held[1] > n_routed:
            raise ValueError(f"moe.experts_held {held} is no range of {n_routed} experts")
        first_k = int(moe.get("first_k_dense_replace", 0))
        if not 0 <= first_k < model_cfg.num_layers:
            raise ValueError("moe.first_k_dense_replace must leave a routed layer")
        return cls(
            vocab_size=vocab_size, hidden_size=model_cfg.hidden_size,
            intermediate_size=model_cfg.intermediate_size, num_layers=model_cfg.num_layers,
            num_heads=model_cfg.num_heads,
            q_lora_rank=int(mla["q_lora_rank"]), kv_lora_rank=int(mla["kv_lora_rank"]),
            qk_nope_head_dim=int(mla["qk_nope_head_dim"]),
            qk_rope_head_dim=int(mla["qk_rope_head_dim"]), v_head_dim=int(mla["v_head_dim"]),
            max_position_embeddings=int(att.get("max_position_embeddings") or 4096),
            rms_norm_eps=float(norm.get("rms_norm_eps", 1e-6)),
            rope_theta=float(rope.get("theta", 10000.0)),
            yarn_factor=float(yarn.get("factor", 1.0)),
            yarn_original_max=int(yarn.get("original_max_position_embeddings", 4096)),
            yarn_beta_fast=float(yarn.get("beta_fast", 32.0)),
            yarn_beta_slow=float(yarn.get("beta_slow", 1.0)),
            yarn_mscale=float(yarn.get("mscale", 1.0)),
            yarn_mscale_all_dim=float(yarn.get("mscale_all_dim", 0.0)),
            attention_type=model_cfg.attention_type,
            first_k_dense=first_k, n_routed_experts=n_routed,
            num_experts_per_tok=int(moe["num_experts_per_tok"]),
            moe_intermediate_size=int(moe["moe_intermediate_size"]),
            n_shared_experts=int(moe.get("n_shared_experts", 1)),
            routed_scaling_factor=float(moe.get("routed_scaling_factor", 1.0)),
            experts_held=held,
            hc_mult=int(hc.get("hc_mult", 4)),
            hc_sinkhorn_iters=int(hc.get("hc_sinkhorn_iters", 20)),
            hc_eps=float(hc.get("hc_eps", 1e-6)),
            hc_res_clamp=(float(hc.get("mhc_h_res_clamp_min", -30.0)),
                          float(hc.get("mhc_h_res_clamp_max", 30.0))),
            mtp_layers=int(mtp.get("num_nextn_predict_layers", 0)),
            mtp_loss_weight=float(mtp.get("loss_weight", 0.3)),
            matmul_precision=getattr(model_cfg, "matmul_precision", None),
        )


# -- init ---------------------------------------------------------------------
HC_ALPHA = 0.5      # the three gains of a mixing map at initialisation
HC_RES_DIAG = 1.0   # its residual map's bias: this on the diagonal, 0 elsewhere


def init_params(rng: jax.Array, args: XingArgs, dtype=jnp.float32) -> Params:
    """normal(0.02) projections, residual outputs scaled by 1/sqrt(2 * layers),
    ones for norm gains, the selection bias normal(0.01). A mixing map starts
    with ``Phi`` at normal(1/sqrt(nC)) (unit-variance pre-activations), gains
    ``HC_ALPHA`` and a residual bias that favours a stream's own carry."""
    counter = iter(range(1 << 30))
    key = lambda: jax.random.fold_in(rng, next(counter))
    std = 0.02
    res_std = std / (2 * (args.num_layers + args.mtp_layers)) ** 0.5
    C, H, n = args.hidden_size, args.num_heads, args.hc_mult
    dense = lambda shape, s: {"weight": (jax.random.normal(key(), shape, jnp.float32) * s).astype(dtype)}
    ones = lambda d: {"weight": jnp.ones((d,), dtype)}

    def mix():
        k = 2 * n + n * n
        bias = jnp.concatenate([jnp.zeros((2 * n,)), HC_RES_DIAG * jnp.eye(n).reshape(-1)])
        return {"phi": dense((n * C, k), (n * C) ** -0.5),
                "alpha": jnp.full((3,), HC_ALPHA, dtype), "bias": bias.astype(dtype)}

    def swiglu(width, lead=()):
        return {"w_gate": dense(lead + (C, width), std), "w_up": dense(lead + (C, width), std),
                "w_down": dense(lead + (width, C), res_std)}

    def layer(routed: bool):
        if routed:
            router = dense((C, args.n_routed_experts), std)
            router["bias"] = (jax.random.normal(key(), (args.n_routed_experts,), jnp.float32)
                              * 0.01).astype(dtype)
            ff = {"router": router,
                  "shared": swiglu(args.n_shared_experts * args.moe_intermediate_size),
                  "experts": swiglu(args.moe_intermediate_size, (args.experts_held[1],))}
        else:
            ff = swiglu(args.intermediate_size)
        return {
            "attn_hc": mix(), "attention_norm": ones(C),
            "attention": {
                "wq_a": dense((C, args.q_lora_rank), std), "q_norm": ones(args.q_lora_rank),
                "wq_b": dense((args.q_lora_rank, H * args.qk_head_dim), std),
                "wkv_a": dense((C, args.kv_lora_rank + args.qk_rope_head_dim), std),
                "kv_norm": ones(args.kv_lora_rank),
                "wkv_b": dense((args.kv_lora_rank,
                                H * (args.qk_nope_head_dim + args.v_head_dim)), std),
                "wo": dense((H * args.v_head_dim, C), res_std),
            },
            "ffn_hc": mix(), "ffn_norm": ones(C), "feed_forward": ff,
        }

    params: Params = {
        "tok_embeddings": dense((args.vocab_size, C), std),
        "dense_layers": [layer(False) for _ in range(args.first_k_dense)],
        "layers": [layer(True) for _ in range(args.num_layers - args.first_k_dense)],
        "norm": ones(C),
        "output": dense((C, args.vocab_size), std),
    }
    if args.mtp_layers:
        params["mtp"] = {"hnorm": ones(C), "enorm": ones(C), "eh_proj": dense((2 * C, C), std),
                         "layer": layer(True), "norm": ones(C)}
    return params


# -- rotary frequencies ---------------------------------------------------------
def yarn_inv_freq(args: XingArgs):
    """The ``qk_rope_head_dim / 2`` rotary frequencies after YaRN's blend of
    interpolated and original ones, and the factor its ``mscale`` puts on
    cos and sin; Python floats, fixed at trace time."""
    dim, base, factor = args.qk_rope_head_dim, args.rope_theta, args.yarn_factor
    extra = [base ** (-2.0 * i / dim) for i in range(dim // 2)]
    if factor <= 1.0:
        return extra, 1.0

    def correction_dim(rotations):
        return dim * math.log(args.yarn_original_max / (rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(correction_dim(args.yarn_beta_fast)), 0)
    high = min(math.ceil(correction_dim(args.yarn_beta_slow)), dim - 1)
    span = (high - low) or 0.001
    inv = []
    for i, f in enumerate(extra):
        ramp = min(max((i - low) / span, 0.0), 1.0)  # 0: keep the original frequency
        inv.append(f / factor * ramp + f * (1.0 - ramp))
    return inv, yarn_mscale(factor, args.yarn_mscale) / yarn_mscale(factor, args.yarn_mscale_all_dim)


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def softmax_scale(args: XingArgs) -> float:
    return args.qk_head_dim ** -0.5 * yarn_mscale(args.yarn_factor, args.yarn_mscale_all_dim) ** 2


# -- the mixed residual -----------------------------------------------------------
def hc_read(p: Params, X: jnp.ndarray, args: XingArgs):
    """``X [n, B, S, C]`` → the sub-layer's input ``u [B, S, C]`` and the maps
    that write its output back: ``H_post [n, B, S]``, ``H_res [n, n, B, S]``
    (float32; positions are the minor axes, so a map is whole registers)."""
    n, C = args.hc_mult, X.shape[-1]
    f32 = jnp.float32
    inv = jax.lax.rsqrt(jnp.mean(jnp.square(X.astype(f32)), axis=(0, 3)) + args.hc_eps)
    # x^ Phi = (x Phi) / rms: the matmul reads the streams as they are stored
    a = jnp.einsum("nbsc,nck->kbs", X, p["phi"]["weight"].reshape(n, C, -1),
                   preferred_element_type=f32) * inv
    gain = jnp.concatenate([jnp.broadcast_to(g, (w,)) for g, w in
                            zip(p["alpha"].astype(f32), (n, n, n * n))])
    a = a * gain[:, None, None] + p["bias"].astype(f32)[:, None, None]
    a = a.reshape(a.shape[0], -1)  # [k, B*S]: positions fill the lanes, a map's n the sublanes
    h_pre = jax.nn.sigmoid(a[:n]).reshape((n,) + X.shape[1:3])
    h_post = (2.0 * jax.nn.sigmoid(a[n:2 * n])).reshape((n,) + X.shape[1:3])
    m = jnp.exp(jnp.clip(a[2 * n:], *args.hc_res_clamp)).reshape(n, n, -1)

    @jax.checkpoint  # the loop keeps one map an iteration for the backward, not four
    def sinkhorn(m, _):
        m = m / (m.sum(axis=1, keepdims=True) + args.hc_eps)   # rows
        return m / (m.sum(axis=0, keepdims=True) + args.hc_eps), None   # columns

    # a loop, not twenty copies: the step's compile time is part of every run's set-up
    m, _ = jax.lax.scan(sinkhorn, m, None, length=args.hc_sinkhorn_iters)
    m = m.reshape((n, n) + X.shape[1:3])
    u = sum(h_pre[i][..., None] * X[i].astype(f32) for i in range(n)).astype(X.dtype)
    return u, h_post, m


def hc_write_streams(X, y: jnp.ndarray, h_post: jnp.ndarray, h_res: jnp.ndarray):
    """``X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y``, summed in float32, as a
    list of the ``n`` streams, over any token axes (``X`` the streams stacked
    or one by one ``[..., C]``, ``y [..., C]``, the maps ``[n, ...]`` and
    ``[n, n, ...]``): a token's streams mix among themselves alone."""
    n = len(X)
    Xf = [X[j].astype(jnp.float32) for j in range(n)]
    yf = y.astype(jnp.float32)
    return [(sum(h_res[i, j][..., None] * Xf[j] for j in range(n)) + h_post[i][..., None] * yf
             ).astype(X[0].dtype) for i in range(n)]


def hc_write(X: jnp.ndarray, y: jnp.ndarray, h_post: jnp.ndarray, h_res: jnp.ndarray):
    """:func:`hc_write_streams`, stacked: ``X [n, B, S, C]`` → ``X' [n, B, S, C]``."""
    return jnp.stack(hc_write_streams(X, y, h_post, h_res))


# -- sub-layers ---------------------------------------------------------------------
def latent_kv_projections(p: Params, x: jnp.ndarray, heads: int, width: int, rank: int, eps: float):
    """The key/value side's two projections: ``x [B, S, C]`` -> ``(kv_a [B, S, rank +
    shared], kv [B, S, heads, width])``: the down-projection whole (its last
    channels are the key all heads share) and the up-projection of its normed
    first ``rank``."""
    B, S, _ = x.shape
    kv_a = x @ p["wkv_a"]["weight"]
    c_kv = rms_norm(kv_a[..., :rank], p["kv_norm"]["weight"], eps)
    return kv_a, (c_kv @ p["wkv_b"]["weight"]).reshape(B, S, heads, width)


def latent_keys_values(kv: jnp.ndarray, k_shared: jnp.ndarray, dn: int):
    """``kv [B, S, H, dn + dv]`` and the one shared key ``[B, S, 1, dr]`` (rotated
    or not: the caller's) -> ``(k [B, S, H, dn + dr], v [B, S, H, dv])``."""
    B, S, H, _ = kv.shape
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_shared, (B, S, H, k_shared.shape[-1]))],
                        axis=-1)
    return k, kv[..., dn:]


def latent_attention(p: Params, x: jnp.ndarray, args: XingArgs, positions) -> jnp.ndarray:
    B, S, _ = x.shape
    H, dn, dr, dv = args.num_heads, args.qk_nope_head_dim, args.qk_rope_head_dim, args.v_head_dim
    with jax.named_scope("attn_qkv"):
        c_q = rms_norm(x @ p["wq_a"]["weight"], p["q_norm"]["weight"], args.rms_norm_eps)
        q = (c_q @ p["wq_b"]["weight"]).reshape(B, S, H, dn + dr)
        kv_a, kv = latent_kv_projections(p, x, H, dn + dv, args.kv_lora_rank, args.rms_norm_eps)
        inv_freq, cs_scale = yarn_inv_freq(args)
        angles = positions.astype(jnp.float32)[:, None] * jnp.asarray(inv_freq, jnp.float32)[None]
        cos, sin = jnp.cos(angles) * cs_scale, jnp.sin(angles) * cs_scale
        q = jnp.concatenate([q[..., :dn], apply_rope(q[..., dn:], cos, sin)], axis=-1)
        # one rotated key for all heads
        k, v = latent_keys_values(kv, apply_rope(kv_a[..., None, args.kv_lora_rank:], cos, sin), dn)
    out = attention_core(q, k, v, args.attention_type, scale=softmax_scale(args),
                         precision=args.matmul_precision)
    with jax.named_scope("attn_out"):
        return out.reshape(B, S, H * dv) @ p["wo"]["weight"]


# Rows of a chunk's expert buffer (``moe.sigmoid_routed_ffn``): this model's size is the
# default's, settled by its benchmark cell's compiles.
HELD_CHUNK_ROWS = moe_lib.HELD_CHUNK_ROWS


def routed_ffn(p: Params, x: jnp.ndarray, args: XingArgs, tail=None, operands=()):
    """Shared expert + the held share of the routed experts → ``(y, stats)``;
    with ``tail``, ``y`` is ``tail(y_c, *operands_c)`` of every chunk of tokens
    (``moe.sigmoid_routed_ffn``)."""
    return moe_lib.sigmoid_routed_ffn(p, x, args.num_experts_per_tok, args.routed_scaling_factor,
                                      args.experts_held, args.n_routed_experts, HELD_CHUNK_ROWS,
                                      args.matmul_precision, tail=tail, operands=operands)


def block(p: Params, X: jnp.ndarray, positions, args: XingArgs, routed: bool):
    """One decoder layer on the streams → ``(X', routing stats | None)``.

    A routed layer's tail, the write of ``shared + routed`` back into the
    streams, is handed to the expert layer's chunk loop and runs there on a
    chunk's tokens (``hc_write`` mixes a token's own streams): its gradient to
    ``h_post`` is a row dot with ``shared + routed``, and outside the loop that
    read is what made a rematerialised layer run the held experts' forward a
    third time (``moe.held_share_ffn``). The streams go in and come out one by
    one (``X[j] [B, S, C]``: tokens lead, so a chunk is a slice; the stacked
    ``[n, B, S, C]`` would have to be transposed to ``[chunks, n, T, C]``, and
    XLA makes 235 MB copies of that) and are stacked once after the loop. The
    MTP module's layer is this one."""
    n = args.hc_mult

    def ffn_tail(y, *rest):
        with jax.named_scope("hc_mix"):
            return tuple(hc_write_streams(rest[:n], y, *rest[n:]))

    with jax.named_scope("layer"):
        with jax.named_scope("hc_mix"):
            u, h_post, h_res = hc_read(p["attn_hc"], X, args)
        with jax.named_scope("norm"):
            h = rms_norm(u, p["attention_norm"]["weight"], args.rms_norm_eps)
        y = latent_attention(p["attention"], h, args, positions)
        with jax.named_scope("hc_mix"):
            X = hc_write(X, y, h_post, h_res)
            u, h_post, h_res = hc_read(p["ffn_hc"], X, args)
        with jax.named_scope("norm"):
            h = rms_norm(u, p["ffn_norm"]["weight"], args.rms_norm_eps)
        if routed:
            streams, stats = routed_ffn(
                p["feed_forward"], h, args, tail=ffn_tail,
                operands=tuple((0, X[j]) for j in range(n)) + ((1, h_post), (2, h_res)))
            with jax.named_scope("hc_mix"):
                return jnp.stack(streams), stats
        with jax.named_scope("ffn"):
            y = mlp_block(p["feed_forward"], h)
        with jax.named_scope("hc_mix"):
            return hc_write(X, y, h_post, h_res), None


def _streams(x: jnp.ndarray, n: int) -> jnp.ndarray:
    return jnp.broadcast_to(x[None], (n,) + x.shape)


def _merge_streams(X: jnp.ndarray, gain: jnp.ndarray, args: XingArgs) -> jnp.ndarray:
    """Sum of the streams (in float32), then a stack's final RMSNorm."""
    with jax.named_scope("final_norm"):
        return rms_norm(X.astype(jnp.float32).sum(axis=0).astype(X.dtype), gain, args.rms_norm_eps)


def hidden_states(params: Params, tokens: jnp.ndarray, args: XingArgs, compute_dtype=jnp.float32,
                  remat: Optional[str] = None, scan_layers: bool = False):
    """tokens [B, S] → (final-normed hidden [B, S, C], layer-summed routing stats)."""
    positions = jnp.arange(tokens.shape[1], dtype=jnp.int32)
    with jax.named_scope("embed"):
        X = _streams(params["tok_embeddings"]["weight"].astype(compute_dtype)[tokens], args.hc_mult)
    X, _ = stack.run_layers(lambda p, X, _: block(p, X, positions, args, False), X,
                            params["dense_layers"], compute_dtype, remat)
    X, stats = stack.run_layers(lambda p, X, _: block(p, X, positions, args, True), X,
                                params["layers"], compute_dtype, remat, scan=scan_layers,
                                zero=moe_lib.zero_stats(args.n_routed_experts))
    return _merge_streams(X, params["norm"]["weight"], args), stats


def mtp_hidden(params: Params, hidden: jnp.ndarray, next_tokens: jnp.ndarray, args: XingArgs,
               compute_dtype, remat: Optional[str]):
    """The MTP module's final-normed state: position ``i`` sees the main
    model's ``hidden[i]`` and the embedding of ``next_tokens[i] = t_{i+1}``,
    and predicts ``t_{i+2}``."""
    p = stack.cast_layer({k: v for k, v in params["mtp"].items() if k != "layer"}, compute_dtype)
    positions = jnp.arange(hidden.shape[1], dtype=jnp.int32)
    with jax.named_scope("embed"):
        emb = params["tok_embeddings"]["weight"].astype(compute_dtype)[next_tokens]
    with jax.named_scope("norm"):
        both = jnp.concatenate([
            rms_norm(hidden, p["hnorm"]["weight"], args.rms_norm_eps),
            rms_norm(emb, p["enorm"]["weight"], args.rms_norm_eps)], axis=-1)
    with jax.named_scope("layer"):
        x = both @ p["eh_proj"]["weight"]
    X, stats = stack.own_layer(lambda l, X: block(l, X, positions, args, True), compute_dtype, remat)(
        params["mtp"]["layer"], _streams(x, args.hc_mult))
    return _merge_streams(X, p["norm"]["weight"], args), stats


def mtp_targets(targets: jnp.ndarray, mask: jnp.ndarray):
    """For next-token ``targets [B, S]`` (``targets[i] = t_{i+1}``): the MTP
    head's targets ``t_{i+2}`` and their mask, the last position left out."""
    last = jnp.arange(targets.shape[1]) == targets.shape[1] - 1
    nxt = lambda a: jnp.roll(a, -1, axis=1)
    return nxt(targets), jnp.where(last, 0, mask * nxt(mask)).astype(mask.dtype)


def forward(params: Params, tokens: jnp.ndarray, args: XingArgs, cache=None, start_pos: Any = 0,
            compute_dtype=jnp.float32, remat: Optional[str] = None, scan_layers: bool = False,
            **_unused):
    """tokens [B, S] → (main-head logits [B, S, V] float32, None)."""
    if cache is not None:
        raise NotImplementedError("xing_mla_moe has no cached decode: a latent KV cache is not built")
    h, _ = hidden_states(params, tokens, args, compute_dtype, remat, scan_layers)
    return stack.head_logits(h, params["output"]["weight"], 1, compute_dtype), None


def loss_fn(params: Params, batch: Dict[str, jnp.ndarray], args: XingArgs,
            compute_dtype=jnp.float32, remat: Optional[str] = None, remat_ratio: float = 1.0,
            include_aux: bool = True, ce_chunk: int = -1, scan_layers: bool = False,
            z_loss_weight: float = 0.0, with_moe_stats: bool = False, overlap: bool = False):
    """``(loss, token_count)``: masked mean CE of the main head, plus (training,
    ``include_aux``) ``mtp_loss_weight`` times the MTP head's over the positions
    that have a ``t_{i+2}``. ``with_moe_stats`` returns ``(loss, (count,
    stats))`` with the routing statistics summed over every routed layer (the
    MTP module's too) and the two loss terms. Both heads run the fused CE on
    one output weight; the routed layers are always rematerialised whole
    (``remat_ratio`` is not split here)."""
    del remat_ratio, overlap  # overlap: the llama stack's fsdp schedule
    targets, mask = batch["targets"], batch["mask"].astype(jnp.float32)
    ce_chunk = stack.ce_chunk_rows(ce_chunk, *targets.shape, args.vocab_size)
    h, stats = hidden_states(params, batch["inputs"], args, compute_dtype, remat, scan_layers)
    w_vd = stack.head_weight(params["output"]["weight"], 1, compute_dtype)
    head = lambda hidden, tgt, weights, w_vd=w_vd: stack.head_ce(
        hidden, w_vd, tgt, weights, ce_chunk, z_loss_weight)
    mean_of = stack.mean_weights
    if not (args.mtp_layers and include_aux):
        main = loss = head(h, targets, mean_of(mask))
        mtp = jnp.zeros((), jnp.float32)
    else:
        with jax.named_scope("mtp"):
            h2, stats2 = mtp_hidden(params, h, targets, args, compute_dtype, remat)
            targets2, mask2 = mtp_targets(targets, mask)
        stats = {k: stats[k] + stats2[k] for k in stats}
        # One walk over both heads' rows, each row weighted by its share of the loss: one
        # float32 gradient of the output weight in the step's memory and not one a head.
        loss = head(jnp.concatenate([h, h2]), jnp.concatenate([targets, targets2]),
                    jnp.concatenate([mean_of(mask), args.mtp_loss_weight * mean_of(mask2)]))
        main = mtp = None
        if with_moe_stats:  # the terms apart, for the log: the main head's walked again, forward only
            main = head(*jax.lax.stop_gradient((h, targets, mean_of(mask), w_vd)))
            mtp = (jax.lax.stop_gradient(loss) - main) / (args.mtp_loss_weight or 1.0)
    if with_moe_stats:
        stats = dict(stats, main_loss=jax.lax.stop_gradient(main),
                     mtp_loss=jax.lax.stop_gradient(mtp))
        return loss, (mask.sum(), stats)
    return loss, mask.sum()


def matmul_params_per_token(args: XingArgs) -> int:
    """Weights a token is multiplied by in the main model and the MTP module,
    a uniform router assumed for the held share (``top_k * held / routed``
    experts a token); no input table, no norm gains."""
    C, H, n = args.hidden_size, args.num_heads, args.hc_mult
    attn = (C * args.q_lora_rank + args.q_lora_rank * H * args.qk_head_dim
            + C * (args.kv_lora_rank + args.qk_rope_head_dim)
            + args.kv_lora_rank * H * (args.qk_nope_head_dim + args.v_head_dim)
            + H * args.v_head_dim * C)
    mix = 2 * n * C * (2 * n + n * n)
    expert = 3 * C * args.moe_intermediate_size
    held = args.num_experts_per_tok * args.experts_held[1] / args.n_routed_experts
    routed = attn + mix + C * args.n_routed_experts + (args.n_shared_experts + held) * expert
    dense = attn + mix + 3 * C * args.intermediate_size
    n_routed = args.num_layers - args.first_k_dense
    total = args.first_k_dense * dense + n_routed * routed + C * args.vocab_size
    if args.mtp_layers:
        total += routed + 2 * C * C + C * args.vocab_size
    return int(total)


def flops_per_token(args: XingArgs, seq_len: int) -> float:
    """Training FLOPs a token requires: 6 a multiplied weight, and causal
    attention's ``3 S H (d_qk + d_v)`` a layer (forward plus twice backward)."""
    layers = args.num_layers + args.mtp_layers
    return 6.0 * matmul_params_per_token(args) \
        + 3.0 * layers * seq_len * args.num_heads * (args.qk_head_dim + args.v_head_dim)


register(Architecture("xing_mla_moe", XingArgs, init_params, forward, loss_fn,
                      flops_per_token=flops_per_token))
