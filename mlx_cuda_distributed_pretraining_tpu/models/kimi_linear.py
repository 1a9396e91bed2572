"""Hybrid of Kimi Delta Attention and latent attention over a sigmoid-routed
expert layer (architecture ``kimi_linear``; Kimi Linear, arXiv:2510.26692).

Every layer is ``h = x + Mixer(RMSNorm(x))``, ``x' = h + FFN(RMSNorm(h))``.
What differs from ``models/llama.py``, block by block:

- **Two kinds of mixer in one stack**, by the published 1-based lists
  ``kda_layers`` and ``full_attn_layers`` (period ``K K K M``). Their
  parameter trees differ (twelve leaves against five), so there is no one tree
  to stack: the stack is a Python loop over per-layer trees, each layer its
  own rematerialised function (``stack.own_layer``), as ``models/sambay.py``
  has it, and ``scan_layers`` has nothing to scan. A flag and a ``lax.cond``
  (``models/afmoe.py``) needs one tree for both branches; a scan over whole
  periods needs every period alike, and the first holds the dense layer (and a
  cut stack need not end on a period).
- **KDA mixer** (``kda``): ``q, k, v`` each a projection, a causal depthwise
  convolution of ``conv_size`` taps and a SiLU; ``q`` and ``k`` L2-normed over
  a head, ``q`` scaled by ``d^-1/2`` (one pass over the projection,
  ``ops/short_conv.py``: a kernel pair on a TPU); a
  log decay for every key channel ``g = -exp(A_log) softplus(x W_f^down W_f^up
  + dt_bias)`` and a write strength ``beta = sigmoid(x W_beta)`` a head; the
  gated delta rule ``ops/kda.py``; then ``RMSNorm_head(o) * sigmoid(x W_g^down
  W_g^up)`` and ``W_o``. No positions: the state orders the tokens. The mixer
  is shared: :func:`kda_mixer` and :func:`kda_params` take any args with
  ``hidden_size``, ``kda_heads``, ``kda_head_dim``, ``conv_size`` and
  ``rms_norm_eps``, and the write strength's factor is an argument, 1 here and
  2 in ``models/solar_open2.py`` (``beta`` in (0, 2): negative eigenvalues).
- **Latent attention** (``attention``) as ``models/xing.py`` has it, without a
  query rank and **without any rotation** (``mla_use_nope``): ``q = x W_q``
  straight to ``H x (nope + rope)``; the ``rope`` channels of the shared key
  are the down-projection's last ones as they come.
- **FFN.** ``first_k_dense`` leading SwiGLU layers, then routed layers as
  ``models/xing.py`` has them: sigmoid scores, a selection bias that is a
  buffer, weights normalised over the chosen and scaled, a shared expert, and
  the routed experts this chip *holds* (``experts_held = (first, count)``).

Training path only: serving these layers needs a cache of the recurrent state
beside the latent one (ROADMAP R-M6).
"""

from __future__ import annotations

import collections
import math
import threading
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops import attention as attention_ops
from ..ops import kda as kda_ops
from ..ops import short_conv as conv_ops
from . import moe as moe_lib
from . import stack
from .llama import mlp_block, rms_norm
from .registry import Architecture, register
from .xing import latent_keys_values, latent_kv_projections

Params = Dict[str, Any]


@dataclass(frozen=True)
class KimiLinearArgs:
    vocab_size: int = 259
    hidden_size: int = 64
    intermediate_size: int = 128       # the leading dense layers' FFN
    num_layers: int = 4
    kda_layers: Tuple[int, ...] = (1, 2, 3)      # 1-based, as published
    full_attn_layers: Tuple[int, ...] = (4,)
    # KDA
    kda_heads: int = 2
    kda_head_dim: int = 32
    conv_size: int = 4
    # latent attention
    num_heads: int = 2
    kv_lora_rank: int = 32
    qk_nope_head_dim: int = 16
    qk_rope_head_dim: int = 8
    v_head_dim: int = 16
    rms_norm_eps: float = 1e-5
    attention_type: str = "simple"     # simple | flash
    # experts
    first_k_dense: int = 1
    n_routed_experts: int = 8          # the router's width
    num_experts_per_tok: int = 2
    moe_intermediate_size: int = 32
    n_shared_experts: int = 1
    routed_scaling_factor: float = 1.0
    experts_held: Tuple[int, int] = (0, 8)   # (first id, count) of the experts this chip holds
    held_chunk_rows: int = moe_lib.HELD_CHUNK_ROWS
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

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        """One letter a layer, in the stack's order: ``K`` | ``M``."""
        return tuple("K" if l in self.kda_layers else "M" for l in range(1, self.num_layers + 1))

    @classmethod
    def from_config(cls, model_cfg: Any, vocab_size: int) -> "KimiLinearArgs":
        sec = lambda name: dict(getattr(model_cfg, name, None) or {})
        norm, moe, mla, lin = (sec(k) for k in ("normalization", "moe", "mla", "linear_attn"))
        n = model_cfg.num_layers
        kda = tuple(int(l) for l in lin.get("kda_layers") or ())
        full = tuple(int(l) for l in lin.get("full_attn_layers") or ())
        if sorted(kda + full) != list(range(1, n + 1)):
            raise ValueError(f"linear_attn.kda_layers {kda} and full_attn_layers {full} (1-based) "
                             f"must name each of {n} layers once")
        n_routed = int(moe["num_experts"])
        held = tuple(int(v) for v in (moe.get("experts_held") or (0, n_routed)))
        if held[0] < 0 or held[1] < 1 or held[0] + held[1] > n_routed:
            raise ValueError(f"moe.experts_held {held} is no range of {n_routed} experts")
        first_k = int(moe.get("first_k_dense_replace", 0))
        if not 0 <= first_k < n:
            raise ValueError("moe.first_k_dense_replace must leave a routed layer")
        if mla.get("q_lora_rank") is not None:
            raise ValueError("kimi_linear's latent attention has no query rank (q_lora_rank: null)")
        return cls(
            vocab_size=vocab_size, hidden_size=model_cfg.hidden_size,
            intermediate_size=model_cfg.intermediate_size, num_layers=n,
            kda_layers=kda, full_attn_layers=full,
            kda_heads=int(lin["num_heads"]), kda_head_dim=int(lin["head_dim"]),
            conv_size=int(lin.get("short_conv_kernel_size", 4)),
            num_heads=model_cfg.num_heads, kv_lora_rank=int(mla["kv_lora_rank"]),
            qk_nope_head_dim=int(mla["qk_nope_head_dim"]),
            qk_rope_head_dim=int(mla["qk_rope_head_dim"]), v_head_dim=int(mla["v_head_dim"]),
            rms_norm_eps=float(norm.get("rms_norm_eps", 1e-5)),
            attention_type=model_cfg.attention_type,
            first_k_dense=first_k, n_routed_experts=n_routed,
            num_experts_per_tok=int(moe["num_experts_per_token"]),
            moe_intermediate_size=int(moe["moe_intermediate_size"]),
            n_shared_experts=int(moe.get("num_shared_experts", 1)),
            routed_scaling_factor=float(moe.get("routed_scaling_factor", 1.0)),
            experts_held=held,
            held_chunk_rows=int(moe.get("held_chunk_rows") or moe_lib.HELD_CHUNK_ROWS),
            matmul_precision=getattr(model_cfg, "matmul_precision", None),
        )


# -- what was traced --------------------------------------------------------------
_layer_counts: Dict[str, int] = collections.Counter()
_plan_counts_lock = threading.Lock()


_SOLVE_KEY = "solve_" + kda_ops.SOLVE_FORM


def count_layer(*keys: str) -> None:
    with _plan_counts_lock:
        _layer_counts.update(keys)


def kda_plan_counts(layers=("kda_layers", "latent_layers")) -> Dict[str, int]:
    """The layers traced by kind (``layers``: this module's two; ``solar_open2``
    names its own), then the delta-rule cores by
    form and chunk (``ops/kda.plan_counts``: ``kernel``, ``xla``), of the cores a
    mixer traced ``neg_eig_cores``, those whose write strength was doubled (0 in a
    run that lost ``kda_allow_neg_eigval``), and ``solve_<form>``, those by the
    form of the chunk's triangular solve (``ops/kda.SOLVE_FORM``; a tally without
    the key is an executable from before PR 56, whose solve lost two digits at a
    write strength near 2), and the q, k, v prologues by form
    (``ops/short_conv.plan_counts``: ``conv_kernel``, ``conv_xla``)."""
    with _plan_counts_lock:
        own = {k: _layer_counts[k] for k in layers}
        cores = {k: _layer_counts[k] for k in ("neg_eig_cores", _SOLVE_KEY)}
    return {**own, **kda_ops.plan_counts(), **cores, **conv_ops.plan_counts()}


# -- init ---------------------------------------------------------------------
A_RANGE = (1.0, 16.0)       # exp(A_log) ~ U(1, 16) a head
DT_RANGE = (1e-3, 1e-1)     # softplus(dt_bias) log-uniform


def kda_params(key, args, dtype, std: float, res_std: float) -> Params:
    """A KDA mixer's fifteen leaves, ``key()`` a fresh key a draw (the module's
    docstring says what ``args`` holds)."""
    C, Hk, d = args.hidden_size, args.kda_heads, args.kda_head_dim
    dense = lambda shape, s=std: {"weight": (jax.random.normal(key(), shape, jnp.float32) * s).astype(dtype)}
    u = jax.random.uniform(key(), (Hk,), jnp.float32, *A_RANGE)
    step = jnp.exp(jax.random.uniform(key(), (Hk * d,), jnp.float32)
                   * (math.log(DT_RANGE[1]) - math.log(DT_RANGE[0])) + math.log(DT_RANGE[0]))
    return {"wq": dense((C, Hk * d)), "wk": dense((C, Hk * d)), "wv": dense((C, Hk * d)),
            "conv_q": dense((Hk * d, args.conv_size)), "conv_k": dense((Hk * d, args.conv_size)),
            "conv_v": dense((Hk * d, args.conv_size)),
            "f_down": dense((C, d)), "f_up": dense((d, Hk * d)),
            "A_log": jnp.log(u).astype(dtype),
            "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(dtype),
            "wb": dense((C, Hk)),
            "g_down": dense((C, d)), "g_up": dense((d, Hk * d)),
            "o_norm": {"weight": jnp.ones((d,), dtype)}, "wo": dense((Hk * d, C), res_std)}


def init_params(rng: jax.Array, args: KimiLinearArgs, dtype=jnp.float32) -> Params:
    """normal(0.02) projections and convolution taps, residual outputs scaled by
    ``1/sqrt(2 * layers)``, ones for gains, the selection bias normal(0.01);
    the decay's own: ``A_log = log u``, ``u ~ U(1, 16)`` a head, ``dt_bias`` the
    inverse softplus of a log-uniform step in [1e-3, 1e-1]."""
    counter = iter(range(1 << 30))
    key = lambda: jax.random.fold_in(rng, next(counter))
    std = 0.02
    res_std = std / (2 * args.num_layers) ** 0.5
    C, H = args.hidden_size, args.num_heads
    dense = lambda shape, s=std: {"weight": (jax.random.normal(key(), shape, jnp.float32) * s).astype(dtype)}
    ones = lambda n: {"weight": jnp.ones((n,), dtype)}

    def swiglu(width, lead=()):
        return {"w_gate": dense(lead + (C, width)), "w_up": dense(lead + (C, width)),
                "w_down": dense(lead + (width, C), res_std)}

    def latent():
        return {"wq": dense((C, H * args.qk_head_dim)),
                "wkv_a": dense((C, args.kv_lora_rank + args.qk_rope_head_dim)),
                "kv_norm": ones(args.kv_lora_rank),
                "wkv_b": dense((args.kv_lora_rank, H * (args.qk_nope_head_dim + args.v_head_dim))),
                "wo": dense((H * args.v_head_dim, C), res_std)}

    def layer(i: int, kind: str):
        if i < args.first_k_dense:
            ff = swiglu(args.intermediate_size)
        else:
            router = dense((C, args.n_routed_experts))
            router["bias"] = (jax.random.normal(key(), (args.n_routed_experts,), jnp.float32)
                              * 0.01).astype(dtype)
            ff = {"router": router,
                  "shared": swiglu(args.n_shared_experts * args.moe_intermediate_size),
                  "experts": swiglu(args.moe_intermediate_size, (args.experts_held[1],))}
        mixer = {"kda": kda_params(key, args, dtype, std, res_std)} if kind == "K" else {"attention": latent()}
        return {"attention_norm": ones(C), **mixer, "ffn_norm": ones(C), "feed_forward": ff}

    return {"tok_embeddings": dense((args.vocab_size, C)),
            "layers": [layer(i, k) for i, k in enumerate(args.layer_kinds)],
            "norm": ones(C),
            "output": dense((C, args.vocab_size))}


# -- sub-layers ---------------------------------------------------------------------
def kda_mixer(p: Params, x: jnp.ndarray, args, beta_scale: float = 1.0) -> jnp.ndarray:
    """``x [B, S, C]`` (normed) -> ``[B, S, C]``. The convolutions, the norms of
    ``q`` and ``k``, the decay, ``beta`` and the head norm are float32; the
    projections and the core's matmuls take operands in ``x``'s dtype.
    ``beta_scale`` (static) is the write strength's factor: ``beta = beta_scale
    sigmoid(x W_beta)``."""
    B, S, _ = x.shape
    H, d = args.kda_heads, args.kda_head_dim
    f32 = jnp.float32
    with jax.named_scope("kda"):
        with jax.named_scope("kda_proj"):
            short = lambda w, conv, **norm: conv_ops.short_conv(
                x @ p[w]["weight"], p[conv]["weight"], out_dtype=x.dtype, **norm).reshape(B, S, H, d)
            q = short("wq", "conv_q", heads=H, scale=d ** -0.5)
            k = short("wk", "conv_k", heads=H)
            v = short("wv", "conv_v")
            low = lambda down, up: jnp.einsum(
                "bsr,re->bse", x @ p[down]["weight"], p[up]["weight"], preferred_element_type=f32)
            step = jax.nn.softplus(low("f_down", "f_up") + p["dt_bias"].astype(f32))
            g = -jnp.exp(p["A_log"].astype(f32))[:, None] * step.reshape(B, S, H, d)
            beta = jax.nn.sigmoid(jnp.einsum("bsc,ch->bsh", x, p["wb"]["weight"],
                                             preferred_element_type=f32))
            if beta_scale != 1.0:
                beta = beta_scale * beta
        count_layer(_SOLVE_KEY, *(("neg_eig_cores",) if beta_scale > 1.0 else ()))
        with jax.named_scope("kda_core"):
            o = kda_ops.kda(q, k, v, g, beta)
        with jax.named_scope("kda_out"):
            gate = jax.nn.sigmoid(low("g_down", "g_up")).reshape(B, S, H, d)
            o = rms_norm(o.astype(f32), p["o_norm"]["weight"], args.rms_norm_eps) * gate
            return o.astype(x.dtype).reshape(B, S, H * d) @ p["wo"]["weight"]


def latent_attention(p: Params, x: jnp.ndarray, args: KimiLinearArgs) -> jnp.ndarray:
    """Latent attention with no query rank and no rotation; causal softmax at
    ``(nope + rope)^-1/2``."""
    B, S, _ = x.shape
    H, dn, dv = args.num_heads, args.qk_nope_head_dim, args.v_head_dim
    with jax.named_scope("attn_qkv"):
        q = (x @ p["wq"]["weight"]).reshape(B, S, H, args.qk_head_dim)
        kv_a, kv = latent_kv_projections(p, x, H, dn + dv, args.kv_lora_rank, args.rms_norm_eps)
        k, v = latent_keys_values(kv, kv_a[..., None, args.kv_lora_rank:], dn)
    out = attention_ops.attention_core(q, k, v, args.attention_type, scale=args.qk_head_dim ** -0.5,
                                       precision=args.matmul_precision)
    with jax.named_scope("attn_out"):
        return out.reshape(B, S, H * dv) @ p["wo"]["weight"]


def routed_ffn(p: Params, x: jnp.ndarray, args: KimiLinearArgs):
    """Shared expert + the held share of the routed experts -> ``(y, stats)``. No
    tail goes into the chunk loop: what follows is the residual add, whose
    backward reads no value (``moe.held_share_ffn``)."""
    return moe_lib.sigmoid_routed_ffn(p, x, args.num_experts_per_tok, args.routed_scaling_factor,
                                      args.experts_held, args.n_routed_experts,
                                      args.held_chunk_rows, args.matmul_precision)


def block(p: Params, x: jnp.ndarray, args: KimiLinearArgs, kind: str, routed: bool):
    """One decoder layer -> ``(x', routing stats | None)``."""
    with jax.named_scope("layer"):
        with jax.named_scope("norm"):
            h = rms_norm(x, p["attention_norm"]["weight"], args.rms_norm_eps)
        y = kda_mixer(p["kda"], h, args) if kind == "K" else latent_attention(p["attention"], h, args)
        with jax.named_scope("norm"):
            x = x + y
            h = rms_norm(x, p["ffn_norm"]["weight"], args.rms_norm_eps)
        if routed:
            y, stats = routed_ffn(p["feed_forward"], h, args)
        else:
            with jax.named_scope("ffn"):
                y, stats = mlp_block(p["feed_forward"], h), None
        with jax.named_scope("norm"):
            return x + y, stats


def hidden_states(params: Params, tokens: jnp.ndarray, args: KimiLinearArgs,
                  compute_dtype=jnp.float32, remat: Optional[str] = None, scan_layers: bool = False):
    """tokens [B, S] -> (final-normed hidden [B, S, C], layer-summed routing stats).

    ``scan_layers`` is accepted and changes nothing (the module's docstring says
    why): every layer is its own rematerialised function that casts its weights
    inside, so the step holds one layer's bf16 copies at a time."""
    del scan_layers
    with jax.named_scope("embed"):
        x = params["tok_embeddings"]["weight"][tokens].astype(compute_dtype)
    stats = moe_lib.zero_stats(args.n_routed_experts)
    for i, (p, kind) in enumerate(zip(params["layers"], args.layer_kinds)):
        count_layer("kda_layers" if kind == "K" else "latent_layers")
        routed = i >= args.first_k_dense
        x, out = stack.own_layer(lambda p, x, kind=kind, routed=routed: block(p, x, args, kind, routed),
                                 compute_dtype, remat)(p, x)
        if routed:
            stats = {k: stats[k] + out[k] for k in stats}
    with jax.named_scope("final_norm"):
        return rms_norm(x, params["norm"]["weight"], args.rms_norm_eps), stats


def forward(params: Params, tokens: jnp.ndarray, args: KimiLinearArgs, cache=None, start_pos: Any = 0,
            compute_dtype=jnp.float32, remat: Optional[str] = None, scan_layers: bool = False,
            **_unused):
    """tokens [B, S] -> (logits [B, S, V] float32, None)."""
    if cache is not None:
        raise NotImplementedError("kimi_linear has no cached decode: no recurrent-state cache "
                                  "beside a latent one")
    h, _ = hidden_states(params, tokens, args, compute_dtype, remat, scan_layers)
    return stack.head_logits(h, params["output"]["weight"], 1, compute_dtype), None


def loss_fn(params: Params, batch: Dict[str, jnp.ndarray], args: KimiLinearArgs,
            compute_dtype=jnp.float32, remat: Optional[str] = None, remat_ratio: float = 1.0,
            include_aux: bool = True, ce_chunk: int = -1, scan_layers: bool = False,
            z_loss_weight: float = 0.0, with_moe_stats: bool = False, overlap: bool = False):
    """``(loss, token_count)``: masked mean cross-entropy through the fused CE;
    no auxiliary term. ``with_moe_stats`` returns ``(loss, (count, stats))``
    with the routing statistics summed over the routed layers."""
    del remat_ratio, include_aux, overlap  # no aux term; overlap: the llama stack's fsdp schedule
    h, stats = hidden_states(params, batch["inputs"], args, compute_dtype, remat, scan_layers)
    loss, count = stack.masked_ce(h, params["output"]["weight"], 1, batch, args.vocab_size,
                                  ce_chunk or -1, z_loss_weight, compute_dtype)
    return (loss, (count, stats)) if with_moe_stats else (loss, count)


def kda_matmul_params(C: int, Hk: int, d: int) -> int:
    """A KDA mixer's weights a token is multiplied by: four head-wide projections,
    two low-rank pairs, ``W_beta``."""
    return 4 * C * Hk * d + 2 * (C * d + d * Hk * d) + C * Hk


def kda_core_flops_per_token(Hk: int, d: int, c: int = 64) -> float:
    """The delta rule's chunked matmuls a token, forward and backward: ``10 c d + 6
    d^2 + c^2`` a head forward at ``c`` = 64, three times that with the backward."""
    return 3.0 * Hk * (10 * c * d + 6 * d * d + c * c)


def matmul_params_per_token(args: KimiLinearArgs) -> int:
    """Weights a token is multiplied by (a uniform router assumed for the held
    share: ``top_k * held / routed`` experts a token); no input table, no
    gains, not the depthwise convolutions."""
    C, H = args.hidden_size, args.num_heads
    mixer = {"K": kda_matmul_params(C, args.kda_heads, args.kda_head_dim),
             "M": C * H * args.qk_head_dim + C * (args.kv_lora_rank + args.qk_rope_head_dim)
             + args.kv_lora_rank * H * (args.qk_nope_head_dim + args.v_head_dim)
             + H * args.v_head_dim * C}
    held = args.num_experts_per_tok * args.experts_held[1] / args.n_routed_experts
    routed = C * args.n_routed_experts + (args.n_shared_experts + held) * 3 * C * args.moe_intermediate_size
    total = C * args.vocab_size
    for i, kind in enumerate(args.layer_kinds):
        total += mixer[kind] + (3 * C * args.intermediate_size if i < args.first_k_dense else routed)
    return int(total)


def flops_per_token(args: KimiLinearArgs, seq_len: int) -> float:
    """Training FLOPs a token requires: 6 a multiplied weight, causal attention's
    ``3 S H (d_qk + d_v)`` a latent layer, and the delta rule's chunked matmuls a
    KDA layer (``10 c d + 6 d^2 + c^2`` a head forward at ``c`` = 64, three times
    that with the backward)."""
    core = kda_core_flops_per_token(args.kda_heads, args.kda_head_dim)
    latent = 3.0 * seq_len * args.num_heads * (args.qk_head_dim + args.v_head_dim)
    kinds = args.layer_kinds
    return 6.0 * matmul_params_per_token(args) + kinds.count("K") * core + kinds.count("M") * latent


register(Architecture("kimi_linear", KimiLinearArgs, init_params, forward, loss_fn,
                      flops_per_token=flops_per_token,
                      plans={"kda_plan": ("delta-rule layers (traced; cores by form and chunk)",
                                          kda_plan_counts)}))
