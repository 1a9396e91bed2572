"""Decoder whose layers differ in attention mask and position encoding, with
gated QK-normed attention and a sigmoid-routed expert layer (architecture
``afmoe``; the Arcee Trinity family).

What differs from ``models/llama.py``, block by block:

- **Two kinds of layer in one stack**, by ``layer_types`` (one entry a layer,
  as the published config lists them): ``sliding_attention`` attends causally
  to the last ``sliding_window`` positions and rotates q and k (RoPE);
  ``full_attention`` attends causally to everything and has no position
  encoding. Under ``scan_layers`` the routed layers are one ``lax.scan`` whose
  per-layer flag, scanned beside the weights, picks the attention core by
  ``lax.cond``: each branch is one static call of the kernels under its own
  mask, a layer runs one of them, and everything else in the layer is shared.
  A scan over one period of the pattern was not taken: the cut stack starts
  in the middle of a published period, and the flag serves any pattern.
- **Attention.** Grouped-query; RMSNorm over the head dimension on q and k
  (one gain vector each, shared by the heads); an output gate
  ``sigmoid(h Wg)`` on the concatenated heads before ``wo``.
- **Norms.** Four a layer: before and after each sub-layer
  (``x + RMS(F(RMS(x)))``).
- **FFN.** ``num_dense_layers`` leading dense layers, then routed layers as
  ``models/xing.py`` has them: sigmoid scores, a selection bias that is a
  buffer, weights normalised over the chosen and scaled, a shared expert, and
  the routed experts this chip *holds* (``experts_held = (first, count)``).
- **Embedding** scaled by ``sqrt(hidden)`` (``mup_enabled``).

Training path only: no KV cache (a cache for window and full layers side by
side is another allocator).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops import attention as attention_ops
from . import moe as moe_lib
from . import stack
from .llama import apply_rope, mlp_block, rms_norm, rope_cos_sin
from .registry import Architecture, register

Params = Dict[str, Any]
SLIDING, FULL = "sliding_attention", "full_attention"
_KIND = {SLIDING: "window", FULL: "global"}  # a layer type's name in scopes and counters


@dataclass(frozen=True)
class AfmoeArgs:
    vocab_size: int = 259
    hidden_size: int = 128
    intermediate_size: int = 256       # the leading dense layers' FFN
    num_layers: int = 3                # dense + routed
    num_heads: int = 4
    num_kv_heads: int = 2
    head_dim: int = 32
    layer_types: Tuple[str, ...] = (SLIDING, FULL, SLIDING)
    sliding_window: int = 64
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    attention_type: str = "simple"     # simple | flash
    mup_enabled: bool = True
    # experts
    num_dense_layers: int = 1
    n_routed_experts: int = 8          # the router's width
    num_experts_per_tok: int = 2
    moe_intermediate_size: int = 64
    n_shared_experts: int = 1
    route_scale: float = 1.0
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

    @classmethod
    def from_config(cls, model_cfg: Any, vocab_size: int) -> "AfmoeArgs":
        sec = lambda name: dict(getattr(model_cfg, name, None) or {})
        att, rope, norm, moe, misc = (sec(k) for k in (
            "attention", "rope", "normalization", "moe", "misc"))
        types = tuple(str(t) for t in att.get("layer_types") or ())
        if len(types) != model_cfg.num_layers or set(types) - {SLIDING, FULL}:
            raise ValueError(f"attention.layer_types must name {model_cfg.num_layers} layers, each "
                             f"{SLIDING!r} or {FULL!r}; got {types}")
        n_routed = int(moe["num_experts"])
        held = tuple(int(v) for v in (moe.get("experts_held") or (0, n_routed)))
        if held[0] < 0 or held[1] < 1 or held[0] + held[1] > n_routed:
            raise ValueError(f"moe.experts_held {held} is no range of {n_routed} experts")
        n_dense = int(moe.get("num_dense_layers", 0))
        if not 0 <= n_dense < model_cfg.num_layers:
            raise ValueError("moe.num_dense_layers must leave a routed layer")
        return cls(
            vocab_size=vocab_size, hidden_size=model_cfg.hidden_size,
            intermediate_size=model_cfg.intermediate_size, num_layers=model_cfg.num_layers,
            num_heads=model_cfg.num_heads, num_kv_heads=model_cfg.num_kv_heads,
            head_dim=model_cfg.head_dim, layer_types=types,
            sliding_window=int(att["sliding_window"]),
            rms_norm_eps=float(norm.get("rms_norm_eps", 1e-5)),
            rope_theta=float(rope.get("theta", 10000.0)),
            attention_type=model_cfg.attention_type,
            mup_enabled=bool(misc.get("mup_enabled", True)),
            num_dense_layers=n_dense, n_routed_experts=n_routed,
            num_experts_per_tok=int(moe["num_experts_per_tok"]),
            moe_intermediate_size=int(moe["moe_intermediate_size"]),
            n_shared_experts=int(moe.get("num_shared_experts", 1)),
            route_scale=float(moe.get("route_scale", 1.0)),
            experts_held=held,
            held_chunk_rows=int(moe.get("held_chunk_rows") or moe_lib.HELD_CHUNK_ROWS),
            matmul_precision=getattr(model_cfg, "matmul_precision", None),
        )


# -- init ---------------------------------------------------------------------
def init_params(rng: jax.Array, args: AfmoeArgs, dtype=jnp.float32) -> Params:
    """normal(0.02) projections, residual outputs scaled by 1/sqrt(2 * layers),
    ones for norm gains, the selection bias normal(0.01)."""
    counter = iter(range(1 << 30))
    key = lambda: jax.random.fold_in(rng, next(counter))
    std = 0.02
    res_std = std / (2 * args.num_layers) ** 0.5
    C, H, G, D = args.hidden_size, args.num_heads, args.num_kv_heads, args.head_dim
    dense = lambda shape, s: {"weight": (jax.random.normal(key(), shape, jnp.float32) * s).astype(dtype)}
    ones = lambda d: {"weight": jnp.ones((d,), dtype)}

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
            "attention_norm": ones(C),
            "attention": {
                "wq": dense((C, H * D), std), "wk": dense((C, G * D), std),
                "wv": dense((C, G * D), std), "wg": dense((C, H * D), std),
                "q_norm": ones(D), "k_norm": ones(D), "wo": dense((H * D, C), res_std),
            },
            "post_attention_norm": ones(C), "ffn_norm": ones(C), "feed_forward": ff,
            "post_ffn_norm": ones(C),
        }

    return {
        "tok_embeddings": dense((args.vocab_size, C), std),
        "dense_layers": [layer(False) for _ in range(args.num_dense_layers)],
        "layers": [layer(True) for _ in range(args.num_layers - args.num_dense_layers)],
        "norm": ones(C),
        "output": dense((C, args.vocab_size), std),
    }


# -- sub-layers ---------------------------------------------------------------------
def attention_core(q, k, v, positions, args: AfmoeArgs, layer_type: str):
    """One kind's core on head-normed ``q [B, S, H, D]``, ``k``, ``v [B, S, G,
    D]``: a sliding layer rotates q and k and attends inside its window, a full
    layer attends causally as they are."""
    mask = {}
    if layer_type == SLIDING:
        mask = dict(mask_type="sliding_window", window_size=args.sliding_window)
        with jax.named_scope("attn_qkv"):
            cos, sin = rope_cos_sin(positions, args.head_dim, args.rope_theta)
            q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
    return attention_ops.attention_core(q, k, v, args.attention_type, kind=_KIND[layer_type],
                                        precision=args.matmul_precision, **mask)


def gated_attention(p: Params, x: jnp.ndarray, args: AfmoeArgs, positions, sliding) -> jnp.ndarray:
    """``sliding``: a Python bool, or (a scanned stack of both kinds) a traced
    one, on which ``lax.cond`` runs one kind's core."""
    B, S, _ = x.shape
    H, G, D = args.num_heads, args.num_kv_heads, args.head_dim
    with jax.named_scope("attn_qkv"):
        q = rms_norm((x @ p["wq"]["weight"]).reshape(B, S, H, D), p["q_norm"]["weight"],
                     args.rms_norm_eps)
        k = rms_norm((x @ p["wk"]["weight"]).reshape(B, S, G, D), p["k_norm"]["weight"],
                     args.rms_norm_eps)
        v = (x @ p["wv"]["weight"]).reshape(B, S, G, D)
        with jax.named_scope("attn_gate"):
            z = x @ p["wg"]["weight"]
    core = lambda layer_type: lambda q, k, v: attention_core(q, k, v, positions, args, layer_type)
    if isinstance(sliding, bool):
        out = core(SLIDING if sliding else FULL)(q, k, v)
    else:
        out = jax.lax.cond(sliding, core(SLIDING), core(FULL), q, k, v)
    with jax.named_scope("attn_out"):
        with jax.named_scope("attn_gate"):
            out = out.reshape(B, S, H * D) * jax.nn.sigmoid(z.astype(jnp.float32)).astype(out.dtype)
        return out @ p["wo"]["weight"]


def routed_ffn(p: Params, x: jnp.ndarray, args: AfmoeArgs, tail=None):
    """Shared expert + the held share of the routed experts → ``(y, stats)``;
    with ``tail``, ``y`` is ``tail(y_c)`` of every chunk of tokens
    (``moe.sigmoid_routed_ffn``)."""
    return moe_lib.sigmoid_routed_ffn(p, x, args.num_experts_per_tok, args.route_scale,
                                      args.experts_held, args.n_routed_experts,
                                      args.held_chunk_rows, args.matmul_precision, tail=tail)


def block(p: Params, x: jnp.ndarray, positions, args: AfmoeArgs, routed: bool, sliding):
    """One decoder layer → ``(x', routing stats | None)``.

    A routed layer's post-norm, ``rms_norm(shared + routed, post_ffn_norm)``, is
    handed to the expert layer's chunk loop and runs there, a chunk of tokens
    at a time (a norm over a token's own channels): its backward reads
    ``shared + routed``, and outside the loop that read is what made a
    rematerialised layer run the held experts' forward a third time
    (``moe.held_share_ffn``). The residual add stays here: its backward reads
    no value, and inside the loop it would be one more ``[B, S, C]`` operand
    and one more stacked cotangent (0.10 GiB of the cell's step)."""
    eps = args.rms_norm_eps

    def post_norm(y):
        with jax.named_scope("norm"):
            return rms_norm(y, p["post_ffn_norm"]["weight"], eps)

    with jax.named_scope("layer"):
        with jax.named_scope("norm"):
            h = rms_norm(x, p["attention_norm"]["weight"], eps)
        y = gated_attention(p["attention"], h, args, positions, sliding)
        with jax.named_scope("norm"):
            x = x + rms_norm(y, p["post_attention_norm"]["weight"], eps)
            h = rms_norm(x, p["ffn_norm"]["weight"], eps)
        if routed:
            y, stats = routed_ffn(p["feed_forward"], h, args, tail=post_norm)
        else:
            with jax.named_scope("ffn"):
                y = mlp_block(p["feed_forward"], h)
            y, stats = post_norm(y), None
        with jax.named_scope("norm"):
            return x + y, stats


def hidden_states(params: Params, tokens: jnp.ndarray, args: AfmoeArgs, compute_dtype=jnp.float32,
                  remat: Optional[str] = None, scan_layers: bool = False):
    """tokens [B, S] → (final-normed hidden [B, S, C], layer-summed routing stats)."""
    positions = jnp.arange(tokens.shape[1], dtype=jnp.int32)
    sliding = [t == SLIDING for t in args.layer_types]
    n_dense = args.num_dense_layers
    with jax.named_scope("embed"):
        x = params["tok_embeddings"]["weight"][tokens]
        if args.mup_enabled:
            x = x * math.sqrt(args.hidden_size)
        x = x.astype(compute_dtype)
    x, _ = stack.run_layers(lambda p, x, s: block(p, x, positions, args, False, s), x,
                            params["dense_layers"], compute_dtype, remat, flags=sliding[:n_dense])
    x, stats = stack.run_layers(lambda p, x, s: block(p, x, positions, args, True, s), x,
                                params["layers"], compute_dtype, remat, scan=scan_layers,
                                flags=sliding[n_dense:],
                                zero=moe_lib.zero_stats(args.n_routed_experts))
    with jax.named_scope("final_norm"):
        return rms_norm(x, params["norm"]["weight"], args.rms_norm_eps), stats


def forward(params: Params, tokens: jnp.ndarray, args: AfmoeArgs, cache=None, start_pos: Any = 0,
            compute_dtype=jnp.float32, remat: Optional[str] = None, scan_layers: bool = False,
            **_unused):
    """tokens [B, S] → (logits [B, S, V] float32, None)."""
    if cache is not None:
        raise NotImplementedError("afmoe has no cached decode: no cache for window and full layers")
    h, _ = hidden_states(params, tokens, args, compute_dtype, remat, scan_layers)
    return stack.head_logits(h, params["output"]["weight"], 1, compute_dtype), None


def loss_fn(params: Params, batch: Dict[str, jnp.ndarray], args: AfmoeArgs,
            compute_dtype=jnp.float32, remat: Optional[str] = None, remat_ratio: float = 1.0,
            include_aux: bool = True, ce_chunk: int = -1, scan_layers: bool = False,
            z_loss_weight: float = 0.0, with_moe_stats: bool = False, overlap: bool = False):
    """``(loss, token_count)``: masked mean cross-entropy through the fused CE;
    no auxiliary term (the published ``load_balance_coeff`` belongs to the
    bias's update rule). ``with_moe_stats`` returns ``(loss, (count, stats))``
    with the routing statistics summed over the routed layers, which are
    always rematerialised whole (``remat_ratio`` is not split here)."""
    del remat_ratio, include_aux, overlap  # overlap: the llama stack's fsdp schedule
    h, stats = hidden_states(params, batch["inputs"], args, compute_dtype, remat, scan_layers)
    loss, count = stack.masked_ce(h, params["output"]["weight"], 1, batch, args.vocab_size,
                                  ce_chunk, z_loss_weight, compute_dtype)
    return loss, ((count, stats) if with_moe_stats else count)


def matmul_params_per_token(args: AfmoeArgs) -> int:
    """Weights a token is multiplied by, a uniform router assumed for the held
    share (``top_k * held / routed`` experts a token); no input table, no gains."""
    C, H, G, D = args.hidden_size, args.num_heads, args.num_kv_heads, args.head_dim
    attn = C * D * (3 * H + 2 * G)
    expert = 3 * C * args.moe_intermediate_size
    held = args.num_experts_per_tok * args.experts_held[1] / args.n_routed_experts
    routed = attn + C * args.n_routed_experts + (args.n_shared_experts + held) * expert
    dense = attn + 3 * C * args.intermediate_size
    n_routed = args.num_layers - args.num_dense_layers
    return int(args.num_dense_layers * dense + n_routed * routed + C * args.vocab_size)


def flops_per_token(args: AfmoeArgs, seq_len: int) -> float:
    """Training FLOPs a token requires: 6 a multiplied weight, and each layer's
    attention under its own mask: ``12 H D`` a (query, key) pair, forward plus
    twice backward, over the pairs its mask admits."""
    pairs = sum(stack.band_positions(seq_len, args.sliding_window) if t == SLIDING
                else seq_len * (seq_len + 1) // 2 for t in args.layer_types)
    return 6.0 * matmul_params_per_token(args) \
        + 12.0 * args.num_heads * args.head_dim * pairs / seq_len


register(Architecture("afmoe", AfmoeArgs, init_params, forward, loss_fn,
                      flops_per_token=flops_per_token,
                      plans={"attn_plan": ("attention layers (traced, by kind and kernel path)",
                                           attention_ops.core_counts)}))
