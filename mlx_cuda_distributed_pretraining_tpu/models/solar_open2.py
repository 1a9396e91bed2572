"""Hybrid of delta-rule layers whose write strength reaches 2 and gated
grouped-query attention without positions, over a sigmoid-routed expert layer
(architecture ``solar_open2``; the Solar Open 2 family's published config).

Every layer is ``h = x + Mixer(RMSNorm(x))``, ``x' = h + MoE(RMSNorm(h))``; there
is no dense FFN (``first_k_dense_replace`` 0). What differs from
``models/kimi_linear.py``, whose stack this is (a Python loop over per-layer
trees of two kinds, each layer its own rematerialised function, ``scan_layers``
with nothing to scan):

- **Which layers are which**: ``gqa_layers`` names the softmax layers, 0-based
  as published (``G K K K``: the softmax layer first in its period); every other
  layer is a delta-rule layer.
- **KDA mixer** (``kda``): ``kimi_linear.kda_mixer`` itself, with the write
  strength doubled: ``beta = 2 sigmoid(x W_beta)`` in (0, 2)
  (``kda_allow_neg_eigval``), so a step's transition ``I - beta k k^T`` has the
  eigenvalue ``1 - beta`` in (-1, 1) along ``k``. The low-rank pairs of the
  decay and the gate are the only form (``kda_use_full_proj: false``). Here the
  mixer is twice as wide as the residual stream (64 heads of 128 over 4,096).
- **Gated attention** (``attention``): ``models/afmoe.py``'s output gate on the
  concatenated heads (``sigmoid(x W_g)`` before ``W_o``; ``use_gqa_gate``, the
  scope ``attn_gate``) around a causal grouped-query core with **no rotation
  and no head norms** (``use_rope: false``), at ``head_dim^-1/2``.
- **MoE** in every layer, as ``kimi_linear`` has it in its routed layers:
  sigmoid scores, a selection bias that is a buffer, weights normalised over
  the chosen (``norm_topk_prob``) and scaled, a shared expert, and the routed
  experts this chip *holds* (``experts_held = (first, count)``).

Training path only: serving these layers needs a cache of the recurrent state
beside keys and values (ROADMAP R-M6).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops import attention as attention_ops
from . import kimi_linear as kimi
from . import moe as moe_lib
from . import stack
from .llama import rms_norm
from .registry import Architecture, register

Params = Dict[str, Any]


@dataclass(frozen=True)
class SolarOpen2Args:
    vocab_size: int = 259
    hidden_size: int = 64
    num_layers: int = 4
    gqa_layers: Tuple[int, ...] = (0,)       # 0-based, as published
    # KDA
    kda_heads: int = 4
    kda_head_dim: int = 32
    conv_size: int = 4
    kda_beta_scale: float = 2.0              # kda_allow_neg_eigval: beta in (0, 2)
    # gated grouped-query attention
    num_heads: int = 4
    num_kv_heads: int = 2
    head_dim: int = 32
    rms_norm_eps: float = 1e-5
    attention_type: str = "simple"           # simple | flash
    # experts
    n_routed_experts: int = 8                # the router's width
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
    def layer_kinds(self) -> Tuple[str, ...]:
        """One letter a layer, in the stack's order: ``G`` | ``K``."""
        return tuple("G" if l in self.gqa_layers else "K" for l in range(self.num_layers))

    @classmethod
    def from_config(cls, model_cfg: Any, vocab_size: int) -> "SolarOpen2Args":
        """The published keys under their own names: ``attention`` holds ``gqa_layers``,
        ``use_gqa_gate``, ``use_rope`` beside the heads; ``linear_attn`` the published
        ``linear_attn_config`` with ``kda_allow_neg_eigval`` and ``kda_use_full_proj``;
        ``moe`` the router's. What this module cannot run is refused by name."""
        sec = lambda name: dict(getattr(model_cfg, name, None) or {})
        att, norm, moe, lin = (sec(k) for k in ("attention", "normalization", "moe", "linear_attn"))
        n = model_cfg.num_layers
        if att.get("use_rope", False):
            raise ValueError("solar_open2 rotates nothing: attention.use_rope must be false")
        if not att.get("use_gqa_gate", True):
            raise ValueError("solar_open2's softmax layers are gated: attention.use_gqa_gate must be true")
        if lin.get("kda_use_full_proj", False):
            raise ValueError("solar_open2's decay and gate are low-rank pairs: "
                             "linear_attn.kda_use_full_proj must be false")
        gqa = tuple(int(l) for l in att.get("gqa_layers") or ())
        if not gqa or len(set(gqa)) != len(gqa) or min(gqa) < 0 or max(gqa) >= n:
            raise ValueError(f"attention.gqa_layers {gqa} (0-based) must name layers of the {n} there are, "
                             f"each once")
        if int(moe.get("first_k_dense_replace", 0)) != 0:
            raise ValueError("solar_open2 has no dense layer: moe.first_k_dense_replace must be 0")
        if not moe.get("norm_topk_prob", True):
            raise ValueError("solar_open2's gate weights are normalised over the chosen: "
                             "moe.norm_topk_prob must be true")
        n_routed = int(moe["n_routed_experts"])
        held = tuple(int(v) for v in (moe.get("experts_held") or (0, n_routed)))
        if held[0] < 0 or held[1] < 1 or held[0] + held[1] > n_routed:
            raise ValueError(f"moe.experts_held {held} is no range of {n_routed} experts")
        kda_heads = lin.get("num_kv_heads") or lin["num_heads"]
        if int(kda_heads) != int(lin["num_heads"]):
            raise ValueError("solar_open2's delta-rule heads are not grouped: linear_attn.num_kv_heads "
                             "must be null or num_heads")
        return cls(
            vocab_size=vocab_size, hidden_size=model_cfg.hidden_size, num_layers=n, gqa_layers=gqa,
            kda_heads=int(lin["num_heads"]), kda_head_dim=int(lin["head_dim"]),
            conv_size=int(lin.get("short_conv_kernel_size", 4)),
            kda_beta_scale=2.0 if lin.get("kda_allow_neg_eigval", True) else 1.0,
            num_heads=model_cfg.num_heads, num_kv_heads=model_cfg.num_kv_heads,
            head_dim=model_cfg.head_dim,
            rms_norm_eps=float(norm.get("rms_norm_eps", 1e-5)),
            attention_type=model_cfg.attention_type,
            n_routed_experts=n_routed,
            num_experts_per_tok=int(moe["num_experts_per_tok"]),
            moe_intermediate_size=int(moe["moe_intermediate_size"]),
            n_shared_experts=int(moe.get("n_shared_experts", 1)),
            routed_scaling_factor=float(moe.get("routed_scaling_factor", 1.0)),
            experts_held=held,
            held_chunk_rows=int(moe.get("held_chunk_rows") or moe_lib.HELD_CHUNK_ROWS),
            matmul_precision=getattr(model_cfg, "matmul_precision", None),
        )


# -- init ---------------------------------------------------------------------
def init_params(rng: jax.Array, args: SolarOpen2Args, dtype=jnp.float32) -> Params:
    """As ``kimi_linear.init_params``: normal(0.02) projections and taps, residual
    outputs scaled by ``1/sqrt(2 * layers)``, ones for gains, the selection bias
    normal(0.01), the decay's own initialisation (``kimi_linear.kda_params``)."""
    counter = iter(range(1 << 30))
    key = lambda: jax.random.fold_in(rng, next(counter))
    std = 0.02
    res_std = std / (2 * args.num_layers) ** 0.5
    C, H, G, D = args.hidden_size, args.num_heads, args.num_kv_heads, args.head_dim
    dense = lambda shape, s=std: {"weight": (jax.random.normal(key(), shape, jnp.float32) * s).astype(dtype)}
    ones = lambda n: {"weight": jnp.ones((n,), dtype)}

    def swiglu(width, lead=()):
        return {"w_gate": dense(lead + (C, width)), "w_up": dense(lead + (C, width)),
                "w_down": dense(lead + (width, C), res_std)}

    def gated():
        return {"wq": dense((C, H * D)), "wk": dense((C, G * D)), "wv": dense((C, G * D)),
                "wg": dense((C, H * D)), "wo": dense((H * D, C), res_std)}

    def layer(kind: str):
        router = dense((C, args.n_routed_experts))
        router["bias"] = (jax.random.normal(key(), (args.n_routed_experts,), jnp.float32) * 0.01).astype(dtype)
        ff = {"router": router,
              "shared": swiglu(args.n_shared_experts * args.moe_intermediate_size),
              "experts": swiglu(args.moe_intermediate_size, (args.experts_held[1],))}
        mixer = {"kda": kimi.kda_params(key, args, dtype, std, res_std)} if kind == "K" \
            else {"attention": gated()}
        return {"attention_norm": ones(C), **mixer, "ffn_norm": ones(C), "feed_forward": ff}

    return {"tok_embeddings": dense((args.vocab_size, C)),
            "layers": [layer(k) for k in args.layer_kinds],
            "norm": ones(C),
            "output": dense((C, args.vocab_size))}


# -- sub-layers ---------------------------------------------------------------------
def gated_attention(p: Params, x: jnp.ndarray, args: SolarOpen2Args) -> jnp.ndarray:
    """``x [B, S, C]`` (normed) -> ``[B, S, C]``: causal grouped-query softmax on
    ``q, k, v`` as projected (no rotation, no head norm), the heads gated by
    ``sigmoid(x W_g)`` before ``W_o``."""
    B, S, _ = x.shape
    H, G, D = args.num_heads, args.num_kv_heads, args.head_dim
    with jax.named_scope("attn_qkv"):
        q = (x @ p["wq"]["weight"]).reshape(B, S, H, D)
        k = (x @ p["wk"]["weight"]).reshape(B, S, G, D)
        v = (x @ p["wv"]["weight"]).reshape(B, S, G, D)
        with jax.named_scope("attn_gate"):
            z = x @ p["wg"]["weight"]
    out = attention_ops.attention_core(q, k, v, args.attention_type, scale=D ** -0.5,
                                       precision=args.matmul_precision)
    with jax.named_scope("attn_out"):
        with jax.named_scope("attn_gate"):
            out = out.reshape(B, S, H * D) * jax.nn.sigmoid(z.astype(jnp.float32)).astype(out.dtype)
        return out @ p["wo"]["weight"]


routed_ffn = kimi.routed_ffn   # shared expert + the held share of the routed experts -> (y, stats): the same args' fields


def block(p: Params, x: jnp.ndarray, args: SolarOpen2Args, kind: str):
    """One decoder layer -> ``(x', routing stats)``."""
    with jax.named_scope("layer"):
        with jax.named_scope("norm"):
            h = rms_norm(x, p["attention_norm"]["weight"], args.rms_norm_eps)
        y = kimi.kda_mixer(p["kda"], h, args, args.kda_beta_scale) if kind == "K" \
            else gated_attention(p["attention"], h, args)
        with jax.named_scope("norm"):
            x = x + y
            h = rms_norm(x, p["ffn_norm"]["weight"], args.rms_norm_eps)
        y, stats = routed_ffn(p["feed_forward"], h, args)
        with jax.named_scope("norm"):
            return x + y, stats


def hidden_states(params: Params, tokens: jnp.ndarray, args: SolarOpen2Args,
                  compute_dtype=jnp.float32, remat: Optional[str] = None, scan_layers: bool = False):
    """tokens [B, S] -> (final-normed hidden [B, S, C], layer-summed routing stats).
    ``scan_layers`` is accepted and changes nothing, as in ``kimi_linear``."""
    del scan_layers
    with jax.named_scope("embed"):
        x = params["tok_embeddings"]["weight"][tokens].astype(compute_dtype)
    stats = moe_lib.zero_stats(args.n_routed_experts)
    for p, kind in zip(params["layers"], args.layer_kinds):
        kimi.count_layer("kda_layers" if kind == "K" else "gqa_layers")
        x, out = stack.own_layer(lambda p, x, kind=kind: block(p, x, args, kind), compute_dtype, remat)(p, x)
        stats = {k: stats[k] + out[k] for k in stats}
    with jax.named_scope("final_norm"):
        return rms_norm(x, params["norm"]["weight"], args.rms_norm_eps), stats


def forward(params: Params, tokens: jnp.ndarray, args: SolarOpen2Args, cache=None, start_pos: Any = 0,
            compute_dtype=jnp.float32, remat: Optional[str] = None, scan_layers: bool = False,
            **_unused):
    """tokens [B, S] -> (logits [B, S, V] float32, None)."""
    if cache is not None:
        raise NotImplementedError("solar_open2 has no cached decode: no recurrent-state cache "
                                  "beside keys and values")
    h, _ = hidden_states(params, tokens, args, compute_dtype, remat, scan_layers)
    return stack.head_logits(h, params["output"]["weight"], 1, compute_dtype), None


def loss_fn(params: Params, batch: Dict[str, jnp.ndarray], args: SolarOpen2Args,
            compute_dtype=jnp.float32, remat: Optional[str] = None, remat_ratio: float = 1.0,
            include_aux: bool = True, ce_chunk: int = -1, scan_layers: bool = False,
            z_loss_weight: float = 0.0, with_moe_stats: bool = False, overlap: bool = False):
    """``(loss, token_count)``: masked mean cross-entropy through the fused CE;
    no auxiliary term. ``with_moe_stats`` returns ``(loss, (count, stats))``
    with the routing statistics summed over the layers."""
    del remat_ratio, include_aux, overlap  # no aux term; overlap: the llama stack's fsdp schedule
    h, stats = hidden_states(params, batch["inputs"], args, compute_dtype, remat, scan_layers)
    loss, count = stack.masked_ce(h, params["output"]["weight"], 1, batch, args.vocab_size,
                                  ce_chunk or -1, z_loss_weight, compute_dtype)
    return (loss, (count, stats)) if with_moe_stats else (loss, count)


def matmul_params_per_token(args: SolarOpen2Args) -> int:
    """Weights a token is multiplied by (a uniform router assumed for the held
    share: ``top_k * held / routed`` experts a token); no input table, no
    gains, not the depthwise convolutions."""
    C, H, G, D = args.hidden_size, args.num_heads, args.num_kv_heads, args.head_dim
    mixer = {"K": kimi.kda_matmul_params(C, args.kda_heads, args.kda_head_dim),
             "G": C * D * (3 * H + 2 * G)}
    held = args.num_experts_per_tok * args.experts_held[1] / args.n_routed_experts
    routed = C * args.n_routed_experts + (args.n_shared_experts + held) * 3 * C * args.moe_intermediate_size
    return int(C * args.vocab_size + sum(mixer[kind] + routed for kind in args.layer_kinds))


def flops_per_token(args: SolarOpen2Args, seq_len: int) -> float:
    """Training FLOPs a token requires: 6 a multiplied weight, causal attention's
    ``6 S H D`` a softmax layer, and ``kimi_linear``'s count of the delta rule's
    chunked matmuls a KDA layer."""
    kinds = args.layer_kinds
    return 6.0 * matmul_params_per_token(args) \
        + kinds.count("K") * kimi.kda_core_flops_per_token(args.kda_heads, args.kda_head_dim) \
        + kinds.count("G") * 6.0 * seq_len * args.num_heads * args.head_dim


def plan_counts() -> Dict[str, int]:
    """``kimi_linear.kda_plan_counts`` over this module's two kinds of layer."""
    return kimi.kda_plan_counts(("gqa_layers", "kda_layers"))


register(Architecture("solar_open2", SolarOpen2Args, init_params, forward, loss_fn,
                      flops_per_token=flops_per_token,
                      plans={"kda_plan": ("delta-rule layers (traced; cores by form and chunk)",
                                          plan_counts)}))
