"""Pure-pytree Llama decoder.

Capability parity with the reference model (reference: models/llama.py:
ModelArgs :17-41, RMSNorm :44-56, RoPE :59-139, MLP :141-160, attention
dispatch :181-209, TransformerBlock :298-319, Model :322-477) designed
TPU-first:

- params are a nested dict of ``jnp.ndarray`` (no module framework) so
  sharding rules, optimizer partitions and checkpoints address leaves by
  path;
- ``forward`` is a pure function — jit/grad/shard_map compose directly;
- attention dispatch simple/flash/flex selects the Pallas kernel at trace
  time; masks/score-mods are traceable index functions (ops/masks.py);
- canonical SwiGLU (``silu(gate) * up``) instead of the reference's
  nonstandard ``gate * sigmoid(up) * 2`` (models/llama.py:151) — documented
  behavioral divergence (SURVEY.md §7.3);
- RMSNorm computes in fp32 regardless of compute dtype; logits are fp32.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..ops import fused_ce
from ..ops import masks as masks_lib
from ..ops.attention import attention_core, named_mask_mod, reference_attention
from .stack import cast_layer, layer_checkpoint, normalize_remat

Params = Dict[str, Any]

@dataclass(frozen=True)
class LlamaArgs:
    vocab_size: int = 259
    hidden_size: int = 128
    intermediate_size: int = 256
    num_layers: int = 4
    num_heads: int = 8
    num_kv_heads: int = 8
    head_dim: int = 16
    max_position_embeddings: int = 1024
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    rope_traditional: bool = False
    rope_scaling_factor: Optional[float] = None
    attention_bias: bool = False
    mlp_bias: bool = False
    tie_word_embeddings: bool = True
    logit_scale: Optional[float] = None
    attention_type: str = "simple"  # simple | flash | flex
    # flex-attention mask program (traceable builders in ops/masks.py)
    mask_type: str = "causal"  # causal | sliding_window | prefix_lm
    window_size: int = 512
    prefix_len: int = 0
    score_mod_type: Optional[str] = None  # None | alibi | soft_cap
    soft_cap: float = 50.0
    # MoE (reference declares these fields but never uses them:
    # models/llama.py:40-41; here they drive a real block — models/moe.py).
    num_local_experts: int = 0
    num_experts_per_tok: int = 0
    moe_capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01
    router_z_weight: float = 0.0
    moe_group_size: int = 256
    # Dispatch implementation: "grouped" (sort-based dropless, grouped
    # GEMMs — ops/grouped_matmul.py) or "einsum" (GShard dispatch tensors,
    # capacity drops — kept as the parity oracle). models/moe.py.
    moe_impl: str = "grouped"
    # Static per-destination send slots for the ep all-to-all, as a
    # fraction of local selections: <= 0 means worst-case (dropless).
    moe_ep_capacity_factor: float = 0.0
    # Opt-in low-precision training matmuls (model.matmul_precision):
    # None/fp32 | bf16 | int8 — threaded into ops/flash_attention.py and
    # ops/grouped_matmul.py (amax/scale-tracked int8 forward, fp backward;
    # loss-parity gated vs bf16 in the test suite).
    matmul_precision: Optional[str] = None

    @property
    def is_moe(self) -> bool:
        return self.num_local_experts > 0 and self.num_experts_per_tok > 0

    @classmethod
    def from_config(cls, model_cfg: Any, vocab_size: int) -> "LlamaArgs":
        att = dict(getattr(model_cfg, "attention", None) or {})
        rope = dict(getattr(model_cfg, "rope", None) or {})
        misc = dict(getattr(model_cfg, "misc", None) or {})
        norm = dict(getattr(model_cfg, "normalization", None) or {})
        moe = dict(getattr(model_cfg, "moe", None) or {})
        scaling = rope.get("scaling") or {}
        scale_factor = scaling.get("factor") if isinstance(scaling, dict) else None
        return cls(
            vocab_size=vocab_size,
            hidden_size=model_cfg.hidden_size,
            intermediate_size=model_cfg.intermediate_size,
            num_layers=model_cfg.num_layers,
            num_heads=model_cfg.num_heads,
            num_kv_heads=model_cfg.num_kv_heads,
            head_dim=model_cfg.head_dim,
            max_position_embeddings=int(att.get("max_position_embeddings") or 0)
            or 4096,
            rms_norm_eps=float(norm.get("rms_norm_eps", 1e-5)),
            rope_theta=float(rope.get("theta", 10000.0)),
            rope_traditional=bool(rope.get("traditional", False)),
            rope_scaling_factor=float(scale_factor) if scale_factor else None,
            attention_bias=bool(misc.get("attention_bias", False)),
            mlp_bias=bool(misc.get("mlp_bias", False)),
            tie_word_embeddings=bool(misc.get("tie_word_embeddings", True)),
            logit_scale=misc.get("logit_scale"),
            attention_type=model_cfg.attention_type,
            mask_type=str(att.get("mask_type", "causal")),
            window_size=int(att.get("window_size", 512)),
            prefix_len=int(att.get("prefix_len", 0)),
            score_mod_type=att.get("score_mod"),
            soft_cap=float(att.get("soft_cap", 50.0)),
            num_local_experts=int(moe.get("num_local_experts", 0) or 0),
            num_experts_per_tok=int(moe.get("num_experts_per_tok", 0) or 0),
            moe_capacity_factor=float(moe.get("capacity_factor", 1.25) or 1.25),
            moe_aux_weight=float(moe.get("aux_loss_weight", 0.01) or 0.0),
            router_z_weight=float(moe.get("router_z_weight", 0.0) or 0.0),
            moe_group_size=int(moe.get("group_size", 256) or 256),
            moe_impl=str(moe.get("impl", "grouped") or "grouped"),
            moe_ep_capacity_factor=float(moe.get("ep_capacity_factor", 0.0) or 0.0),
            matmul_precision=getattr(model_cfg, "matmul_precision", None),
        )


# -- init -------------------------------------------------------------------
def init_params(rng: jax.Array, args: LlamaArgs, dtype=jnp.float32) -> Params:
    """Initialize parameters: normal(0.02) embeddings/projections, residual
    output projections scaled by 1/sqrt(2*num_layers) (GPT-2 style), ones for
    norms."""
    per_layer = 8 if args.is_moe else 7
    n_streams = per_layer * args.num_layers + 2
    keys = iter(jax.random.split(rng, n_streams))
    std = 0.02
    res_std = std / (2 * args.num_layers) ** 0.5
    D, Dh = args.hidden_size, args.head_dim
    Hq, Hkv, I = args.num_heads, args.num_kv_heads, args.intermediate_size

    def dense(key, shape, s):
        return (jax.random.normal(key, shape, jnp.float32) * s).astype(dtype)

    layers = []
    for _ in range(args.num_layers):
        layer = {
            "attention_norm": {"weight": jnp.ones((D,), dtype)},
            "attention": {
                "wq": {"weight": dense(next(keys), (D, Hq * Dh), std)},
                "wk": {"weight": dense(next(keys), (D, Hkv * Dh), std)},
                "wv": {"weight": dense(next(keys), (D, Hkv * Dh), std)},
                "wo": {"weight": dense(next(keys), (Hq * Dh, D), res_std)},
            },
            "ffn_norm": {"weight": jnp.ones((D,), dtype)},
        }
        if args.is_moe:
            from . import moe as moe_lib

            layer["feed_forward"] = moe_lib.init_moe_params(keys, args, dtype)
        else:
            layer["feed_forward"] = {
                "w_gate": {"weight": dense(next(keys), (D, I), std)},
                "w_up": {"weight": dense(next(keys), (D, I), std)},
                "w_down": {"weight": dense(next(keys), (I, D), res_std)},
            }
        if args.attention_bias:
            for name, fan_out in (("wq", Hq * Dh), ("wk", Hkv * Dh), ("wv", Hkv * Dh), ("wo", D)):
                layer["attention"][name]["bias"] = jnp.zeros((fan_out,), dtype)
        if args.mlp_bias:
            if args.is_moe:
                raise ValueError("mlp_bias is not supported with MoE (experts are bias-free)")
            for name, fan_out in (("w_gate", I), ("w_up", I), ("w_down", D)):
                layer["feed_forward"][name]["bias"] = jnp.zeros((fan_out,), dtype)
        layers.append(layer)

    params: Params = {
        "tok_embeddings": {"weight": dense(next(keys), (args.vocab_size, D), std)},
        "layers": layers,
        "norm": {"weight": jnp.ones((D,), dtype)},
    }
    if not args.tie_word_embeddings:
        params["output"] = {"weight": dense(next(keys), (D, args.vocab_size), std)}
    return params


def num_params(params: Params) -> int:
    return sum(int(x.size) for x in jax.tree_util.tree_leaves(params))


# -- building blocks --------------------------------------------------------
def rms_norm(x: jnp.ndarray, weight: jnp.ndarray, eps: float) -> jnp.ndarray:
    """fp32-internal RMSNorm (reference: models/llama.py:44-56)."""
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * weight.astype(jnp.float32)).astype(dtype)


def _linear(x: jnp.ndarray, p: Params) -> jnp.ndarray:
    if "weight_q4" in p:
        # int4 weight-only quantization (models/quantize.py): two values
        # per byte along the contraction dim. The nibble unpack is two
        # arithmetic shifts XLA fuses into the matmul's operand read, and
        # the per-output-channel scale lands in the epilogue — the weight
        # crosses HBM at 0.5 byte/elem, no fp copy is materialized.
        from .quantize import unpack_int4

        w = unpack_int4(p["weight_q4"])
        y = (x @ w.astype(x.dtype)) * p["weight_s"].astype(x.dtype)
    elif "weight_q" in p:
        # int8 weight-only quantization (quantize_params_int8): the
        # per-output-channel scale factors OUT of the contraction, so
        # dequant happens after the matmul on the [.., out] result — the
        # weight crosses HBM at 1 byte/elem.
        y = (x @ p["weight_q"].astype(x.dtype)) * p["weight_s"].astype(x.dtype)
    else:
        y = x @ p["weight"]
    if "bias" in p:
        y = y + p["bias"]
    return y


def quantize_params_int8(params: Params) -> Params:
    """Weight-only int8 quantization for inference (per-output-channel
    symmetric scales on every layer linear: wq/wk/wv/wo, the dense MLP
    and MoE expert banks). Embeddings, the output head, norms, biases
    and MoE routers stay full precision (they set logit quality).
    Composes with the int8 KV cache: weights AND cache both cross HBM
    at 1 byte/elem. Thin wrapper over models/quantize.py, which also
    implements packed int4 and the quantize-on-load checkpoint path.

    The reference has no weight quantization (its only quant surface is
    the optional KV cache quant, core/generation_lite.py:75-89)."""
    from .quantize import quantize_weights

    return quantize_weights(params, "int8")


def rope_cos_sin(
    positions: jnp.ndarray, head_dim: int, theta: float, scaling_factor: Optional[float] = None
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """cos/sin tables for given positions [S] -> [S, head_dim//2], fp32.

    Linear position scaling divides positions by the factor (reference:
    models/llama.py:59-139 supports the same "linear" scaling)."""
    pos = positions.astype(jnp.float32)
    if scaling_factor:
        pos = pos / scaling_factor
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    angles = pos[:, None] * inv_freq[None, :]
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray, traditional: bool = False) -> jnp.ndarray:
    """Rotate [B, S, H, D]. ``traditional`` = interleaved pairs; default =
    half-split (llama) convention."""
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    c = cos[None, :, None, :]
    s = sin[None, :, None, :]
    if traditional:
        x1 = xf[..., 0::2]
        x2 = xf[..., 1::2]
        r1 = x1 * c - x2 * s
        r2 = x2 * c + x1 * s
        out = jnp.stack([r1, r2], axis=-1).reshape(x.shape)
    else:
        half = x.shape[-1] // 2
        x1 = xf[..., :half]
        x2 = xf[..., half:]
        out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return out.astype(dtype)


def build_mask_mod(args: LlamaArgs) -> masks_lib.MaskMod:
    return named_mask_mod(args.mask_type, args.window_size, args.prefix_len)


def build_score_mod(args: LlamaArgs, head: Optional[int] = None):
    """Score mod for the whole head dim (vectorized over heads where needed)."""
    if args.score_mod_type == "alibi":
        slopes = jnp.asarray(masks_lib.alibi_slopes(args.num_heads), jnp.float32)

        def mod(scores, q_idx, k_idx):
            # scores [B, Hkv, G, Sq, Skv]; recover absolute head index.
            B, Hkv, G = scores.shape[0], scores.shape[1], scores.shape[2]
            head_ids = jnp.arange(Hkv * G).reshape(Hkv, G)
            slope = slopes[head_ids][None, :, :, None, None]
            return scores - slope * jnp.abs(q_idx - k_idx)[None, None, None]

        return mod
    if args.score_mod_type == "soft_cap":
        return lambda s, q, k: args.soft_cap * jnp.tanh(s / args.soft_cap)
    return None


def attention_block(
    p: Params,
    x: jnp.ndarray,
    args: LlamaArgs,
    positions: jnp.ndarray,
    cache: Optional[Dict[str, jnp.ndarray]] = None,
    attn_impl: Optional[str] = None,
    attend_len: Optional[int] = None,
) -> Tuple[jnp.ndarray, Optional[Dict[str, jnp.ndarray]]]:
    """Self-attention with RoPE, GQA and optional KV cache.

    cache = {"k": [B, T, Hkv, Dh], "v": ..., "pos": scalar} with T =
    max_position_embeddings; decode writes at ``pos`` via dynamic slice and
    attends under a positional validity mask. ``attend_len`` (static)
    restricts attention to the first ``attend_len`` cache slots — the
    generation loop passes a power-of-two bucket >= pos+S, so decode cost
    is O(bucket), not O(T) (the reference's per-token decode is O(cache)
    from step 1: core/generation_lite.py:158-175)."""
    B, S, _ = x.shape
    Hq, Hkv, Dh = args.num_heads, args.num_kv_heads, args.head_dim

    with jax.named_scope("attn_qkv"):
        q = checkpoint_name(_linear(x, p["wq"]), "qkv").reshape(B, S, Hq, Dh)
        k = checkpoint_name(_linear(x, p["wk"]), "qkv").reshape(B, S, Hkv, Dh)
        v = checkpoint_name(_linear(x, p["wv"]), "qkv").reshape(B, S, Hkv, Dh)

        cos, sin = rope_cos_sin(positions, Dh, args.rope_theta, args.rope_scaling_factor)
        q = apply_rope(q, cos, sin, args.rope_traditional)
        k = apply_rope(k, cos, sin, args.rope_traditional)

    impl = attn_impl or args.attention_type
    new_cache = None
    if cache is None and args.score_mod_type is None and impl not in ("ring", "flex"):
        out = attention_core(q, k, v, impl, mask_type=args.mask_type, window_size=args.window_size,
                             prefix_len=args.prefix_len,
                             precision=getattr(args, "matmul_precision", None))
    else:
        with jax.named_scope("attn_core"):
            out, new_cache = _attend(q, k, v, args, positions, cache, impl, attend_len)
    with jax.named_scope("attn_out"):
        out = checkpoint_name(out.reshape(B, S, Hq * Dh), "attn_out")
        return _linear(out, p["wo"]), new_cache


def _attend(q, k, v, args, positions, cache, impl, attend_len):
    """The attention proper on rotated q/k/v where ``attention_core`` does not
    run it: cached decode (fp or int8 buffers), ring, flex, or the reference
    under a score program. Returns ``(out [B, S, Hq, Dh], new_cache | None)``."""
    S = q.shape[1]
    new_cache = None
    if cache is not None and "k_q" in cache:
        # int8-quantized cache (reference: generation_lite.py:75-89 optional
        # KV quantization): per-(position, head) symmetric scales; int8
        # buffers cut decode's HBM cache reads ~4x, dequant fuses into the
        # attention matmul.
        pos = cache["pos"]
        kq, ks = _quantize_kv(k)
        vq, vs = _quantize_kv(v)
        ck_q = jax.lax.dynamic_update_slice(cache["k_q"], kq, (0, pos, 0, 0))
        ck_s = jax.lax.dynamic_update_slice(cache["k_s"], ks, (0, pos, 0, 0))
        cv_q = jax.lax.dynamic_update_slice(cache["v_q"], vq, (0, pos, 0, 0))
        cv_s = jax.lax.dynamic_update_slice(cache["v_s"], vs, (0, pos, 0, 0))
        new_cache = {"k_q": ck_q, "k_s": ck_s, "v_q": cv_q, "v_s": cv_s, "pos": pos + S}
        L = attend_len or ck_q.shape[1]
        k = ck_q[:, :L].astype(jnp.float32) * ck_s[:, :L]
        v = cv_q[:, :L].astype(jnp.float32) * cv_s[:, :L]
        out = _cached_attention(q, k, v, positions, pos, S)
    elif cache is not None:
        pos = cache["pos"]
        ck = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype), (0, pos, 0, 0))
        cv = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype), (0, pos, 0, 0))
        new_cache = {"k": ck, "v": cv, "pos": pos + S}
        L = attend_len or ck.shape[1]
        out = _cached_attention(q, ck[:, :L], cv[:, :L], positions, pos, S)
    else:
        mask_mod = build_mask_mod(args)
        if impl == "ring":
            # Sequence/context parallelism: exact causal attention with KV
            # shards rotating over the sp mesh axis (ops/ring_attention.py).
            from ..ops.ring_attention import make_ring_attention
            from ..parallel.context import current_mesh

            mesh = current_mesh()
            if mesh is None or "sp" not in mesh.axis_names or mesh.shape["sp"] == 1:
                out = reference_attention(q, k, v, mask_mod=mask_mod)
            else:
                out = make_ring_attention(mesh, mask_mod=mask_mod)(q, k, v)
        elif impl == "flex":
            from ..ops.flex_attention import flex_attention, kernel_score_mod

            out = flex_attention(
                q, k, v, mask_mod=mask_mod,
                score_mod=kernel_score_mod(args.score_mod_type, args.num_heads, args.soft_cap),
            )
        else:
            out = reference_attention(q, k, v, mask_mod=mask_mod, score_mod=build_score_mod(args))
    return out, new_cache


def _quantize_kv(x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Symmetric int8 per-(batch, position, head) quantization of [B, S, H, D]
    → (int8 values, fp32 scales [B, S, H, 1])."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.maximum(amax / 127.0, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127).astype(jnp.int8)
    return q, scale


def _cached_attention(q, k, v, positions, pos, S):
    """Decode attention over a full static cache buffer under a positional
    validity mask (keys at or before each query, and already written)."""
    T = k.shape[1]
    k_idx = jnp.arange(T, dtype=jnp.int32)
    explicit = (k_idx[None, :] <= positions[:, None]) & (k_idx[None, :] < pos + S)
    return reference_attention(q, k, v, explicit_mask=explicit)


def mlp_block(p: Params, x: jnp.ndarray) -> jnp.ndarray:
    """Canonical SwiGLU: ``down(silu(gate(x)) * up(x))``."""
    up = checkpoint_name(
        jax.nn.silu(_linear(x, p["w_gate"])) * _linear(x, p["w_up"]), "ffn_up")
    return checkpoint_name(_linear(up, p["w_down"]), "ffn_down")


def transformer_block(
    p: Params,
    x: jnp.ndarray,
    args: LlamaArgs,
    positions: jnp.ndarray,
    cache: Optional[Dict[str, jnp.ndarray]] = None,
    attn_impl: Optional[str] = None,
    attend_len: Optional[int] = None,
) -> Tuple[jnp.ndarray, Optional[Dict[str, jnp.ndarray]], jnp.ndarray]:
    """Pre-norm residual block (reference: models/llama.py:298-319).

    Returns ``(x, new_cache, aux_loss)`` — aux is the MoE load-balancing
    loss (0 for dense layers). When a routing-stats tap is active
    (models/moe.py — training with an MoE model), a fourth element carries
    this layer's routing stats: the stats are re-emitted as RETURN VALUES
    here, inside any ``jax.checkpoint`` wrapping this block, so they cross
    the remat/scan boundary instead of leaking out of its trace."""
    with jax.named_scope("layer"):
        with jax.named_scope("norm"):
            normed = rms_norm(x, p["attention_norm"]["weight"], args.rms_norm_eps)
        h, new_cache = attention_block(
            p["attention"], normed, args, positions, cache, attn_impl, attend_len)
        x = x + h
        with jax.named_scope("norm"):
            normed = rms_norm(x, p["ffn_norm"]["weight"], args.rms_norm_eps)
        if args.is_moe:
            from . import moe as moe_lib

            if moe_lib.stats_tap_active():
                with moe_lib.routing_stats_tap() as tap:
                    ff, aux = moe_lib.moe_block(p["feed_forward"], normed, args)
                x = x + ff
                return x, new_cache, aux, moe_lib.merge_stats(
                    tap, args.num_local_experts)
            ff, aux = moe_lib.moe_block(p["feed_forward"], normed, args)
        else:
            with jax.named_scope("ffn"):
                ff = mlp_block(p["feed_forward"], normed)
            aux = jnp.zeros((), jnp.float32)
        x = x + ff
        return x, new_cache, aux


# -- full model -------------------------------------------------------------
def forward(
    params: Params,
    tokens: jnp.ndarray,
    args: LlamaArgs,
    cache: Optional[list] = None,
    start_pos: Any = 0,
    compute_dtype: jnp.dtype = jnp.float32,
    remat: Optional[str] = None,
    remat_ratio: float = 1.0,
    return_aux: bool = False,
    attend_len: Optional[int] = None,
    return_hidden: bool = False,
    scan_layers: bool = False,
    overlap: bool = False,
) -> Tuple[jnp.ndarray, Optional[list]]:
    """tokens [B, S] int32 → (logits [B, S, V] fp32, new_cache | None).

    ``remat``: None | "none" | "full" | "dots" | "save_attn" — per-layer
    ``jax.checkpoint`` with the named policy (see ``stack.REMAT_POLICIES``);
    ``remat_ratio`` checkpoints only the first fraction
    of layers (reference: system.gradient_checkpointing_ratio).
    ``return_aux=True`` appends the summed MoE aux loss:
    ``(logits, cache, aux)``. ``attend_len`` (static) bounds cached decode
    attention to a bucket of the cache — see :func:`attention_block`.
    ``return_hidden=True`` skips the output projection and returns the
    final normed hidden states [B, S, D] in compute dtype instead of
    logits (the fused-CE loss folds the projection into the loss —
    ops/fused_ce.py).
    ``scan_layers=True`` runs the (uniform) layer stack as one
    ``lax.scan`` body over in-jit-stacked params instead of a Python
    loop: XLA traces/compiles ONE layer instead of num_layers copies,
    cutting program size and (remote-)compile wall time ~num_layers x at
    the 400M-1B scales; the stack itself is one extra pass over the
    already-casted params, negligible next to a training step. Training
    path only (ignored under KV cache). ``remat_ratio < 1`` runs as TWO
    scans — the checkpointed prefix and the plain suffix.
    ``overlap=True`` routes the layer stack through the manual
    shard_map overlap schedule (parallel/overlap.py: per-layer bucketed
    fsdp param all-gather prefetched one layer ahead, gradient
    reduce-scatter draining per layer behind the backward) when the
    current mesh qualifies (pure dp×fsdp, dense, no int8); otherwise
    this flag is a no-op and GSPMD schedules the collectives.
    """
    B, S = tokens.shape
    with jax.named_scope("embed"):
        x = params["tok_embeddings"]["weight"].astype(compute_dtype)[tokens]
    positions = jnp.arange(S, dtype=jnp.int32) + start_pos

    remat = normalize_remat(remat)
    block = layer_checkpoint(remat, static_argnums=(2, 5, 6))(transformer_block)
    cast = lambda layer: cast_layer(layer, compute_dtype)

    new_cache = [] if cache is not None else None
    n_remat = int(round(args.num_layers * remat_ratio))
    aux_total = jnp.zeros((), jnp.float32)
    if args.is_moe:
        from . import moe as moe_lib
        collect_stats = moe_lib.stats_tap_active()
    else:
        collect_stats = False
    stats_total = moe_lib.zero_stats(args.num_local_experts) if collect_stats else None
    use_overlap = False
    if overlap and cache is None and not args.is_moe and not collect_stats:
        from ..parallel import overlap as overlap_lib
        from ..parallel.context import current_mesh

        overlap_mesh = current_mesh()
        layers_cast = [cast(l) for l in params["layers"]]
        use_overlap = overlap_lib.can_overlap(overlap_mesh, layers_cast, B)
    if use_overlap:
        # Manual overlap schedule (parallel/overlap.py): one bucketed
        # all-gather per layer over the fsdp axis, prefetched one layer
        # ahead on the non-checkpointed segment; the gather's transpose
        # drains the gradient reduce-scatter per layer in the backward.
        def overlap_body(layer, h, pos):
            h, _, aux = transformer_block(
                layer, h, args, pos, None, None, attend_len)
            return h, aux

        # Wrapped WITHOUT static_argnums: overlap closes over the static
        # config and checkpoints (gather ∘ block) together so the backward
        # re-gathers shards instead of saving full per-layer params as
        # residuals.
        policy_wrap = layer_checkpoint(remat) if remat else None
        x, aux = overlap_lib.overlapped_layer_scan(
            overlap_body, x, layers_cast, overlap_mesh,
            consts=(positions,), wrap=policy_wrap,
            n_wrapped=(n_remat if remat else 0),
        )
        aux_total = aux_total + aux
    elif scan_layers and cache is None:
        # Segmented scan: the checkpointed prefix (remat_ratio) and the
        # plain suffix each scan over their own stacked params — at most
        # two compiled layer bodies, any ratio.
        layers = [cast(l) for l in params["layers"]]
        segments = ([(layers[:n_remat], block),
                     (layers[n_remat:], transformer_block)]
                    if remat else [(layers, transformer_block)])
        for seg, blk in segments:
            if not seg:
                continue
            def body(h, layer, blk=blk):
                # transformer_block grows a stats element under an active
                # tap; routing it through the scan ys keeps the traced
                # stats inside the scan body's trace.
                out = blk(layer, h, args, positions, None, None, attend_len)
                if collect_stats:
                    h, _, aux, stats = out
                    return h, (aux, stats)
                h, _, aux = out
                return h, aux

            with jax.named_scope("layer"):  # the scan's stacking and slicing too
                stacked = jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *seg)
                x, ys = jax.lax.scan(body, x, stacked)
            if collect_stats:
                auxs, stats = ys
                stats_total = {k: stats_total[k] + stats[k].sum(axis=0)
                               for k in stats_total}
            else:
                auxs = ys
            aux_total = aux_total + auxs.sum()
    else:
        for i, layer in enumerate(params["layers"]):
            blk = block if (remat and i < n_remat) else transformer_block
            layer_cache = cache[i] if cache is not None else None
            out = blk(cast(layer), x, args, positions, layer_cache, None,
                      attend_len)
            if collect_stats:
                x, c, aux, stats = out
                stats_total = {k: stats_total[k] + stats[k] for k in stats_total}
            else:
                x, c, aux = out
            aux_total = aux_total + aux
            if new_cache is not None:
                new_cache.append(c)
    if collect_stats:
        moe_lib.record_stats(stats_total)

    with jax.named_scope("final_norm"):
        x = rms_norm(x, params["norm"]["weight"], args.rms_norm_eps)
    if return_hidden:
        if return_aux:
            return x, new_cache, aux_total
        return x, new_cache
    with jax.named_scope("lm_head_ce"):
        logits = _project_logits(params, x, args, compute_dtype)
    if return_aux:
        return logits, new_cache, aux_total
    return logits, new_cache


def _project_logits(params: Params, x: jnp.ndarray, args: LlamaArgs,
                    compute_dtype) -> jnp.ndarray:
    # Output projection accumulates in fp32 (preferred_element_type) so the
    # logits never round through bf16 — bit-identical to the fused-CE path
    # (ops/fused_ce.py) under any compute dtype.
    if args.tie_word_embeddings or "output" not in params:
        logits = jax.lax.dot_general(
            x, params["tok_embeddings"]["weight"].astype(compute_dtype),
            (((2,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        )
    else:
        logits = jax.lax.dot_general(
            x, params["output"]["weight"].astype(compute_dtype),
            (((2,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        )
        if "bias" in params["output"]:
            # Raw fp32 bias (not rounded through compute_dtype) — keeps this
            # path bit-identical to fused_cross_entropy's bias handling.
            logits = logits + params["output"]["bias"].astype(jnp.float32)
    if args.logit_scale:
        logits = logits * args.logit_scale
    return logits


def init_cache(
    args: LlamaArgs,
    batch_size: int,
    max_len: Optional[int] = None,
    dtype=jnp.float32,
    quantize: bool = False,
) -> list:
    """KV cache buffers. ``quantize=True`` allocates int8 value buffers plus
    per-(position, head) fp32 scales (reference: generation_lite.py:75-89's
    optional KV-cache quantization, here int8-symmetric)."""
    T = max_len or args.max_position_embeddings
    B, H, D = batch_size, args.num_kv_heads, args.head_dim
    if quantize:
        return [
            {
                "k_q": jnp.zeros((B, T, H, D), jnp.int8),
                "k_s": jnp.zeros((B, T, H, 1), jnp.float32),
                "v_q": jnp.zeros((B, T, H, D), jnp.int8),
                "v_s": jnp.zeros((B, T, H, 1), jnp.float32),
                "pos": jnp.asarray(0, jnp.int32),
            }
            for _ in range(args.num_layers)
        ]
    return [
        {
            "k": jnp.zeros((B, T, H, D), dtype),
            "v": jnp.zeros((B, T, H, D), dtype),
            "pos": jnp.asarray(0, jnp.int32),
        }
        for _ in range(args.num_layers)
    ]


def init_paged_cache(
    args: LlamaArgs,
    num_blocks: int,
    block_size: int,
    dtype=jnp.float32,
    quantize: bool = False,
) -> list:
    """Paged KV arena (vLLM-style): per layer a global pool of fixed-size
    blocks ``[num_blocks, block_size, Hkv, Dh]`` addressed through per-
    sequence block tables instead of a per-sequence row. Same value layout
    as :func:`init_cache` (fp buffers, or the int8 quartet with per-
    (position, head) scales) — only the leading dims change, so the
    quantize/dequantize path is shared. No ``pos``: positions are
    per-sequence host state in the serving pool."""
    N, T, H, D = num_blocks, block_size, args.num_kv_heads, args.head_dim
    if quantize:
        return [
            {
                "k_q": jnp.zeros((N, T, H, D), jnp.int8),
                "k_s": jnp.zeros((N, T, H, 1), jnp.float32),
                "v_q": jnp.zeros((N, T, H, D), jnp.int8),
                "v_s": jnp.zeros((N, T, H, 1), jnp.float32),
            }
            for _ in range(args.num_layers)
        ]
    return [
        {
            "k": jnp.zeros((N, T, H, D), dtype),
            "v": jnp.zeros((N, T, H, D), dtype),
        }
        for _ in range(args.num_layers)
    ]


def loss_fn(
    params: Params,
    batch: Dict[str, jnp.ndarray],
    args: LlamaArgs,
    compute_dtype: jnp.dtype = jnp.float32,
    remat: Optional[str] = None,
    remat_ratio: float = 1.0,
    include_aux: bool = True,
    ce_chunk: int = -1,
    scan_layers: bool = False,
    z_loss_weight: float = 0.0,
    with_moe_stats: bool = False,
    overlap: bool = False,
) -> Tuple[jnp.ndarray, Any]:
    """Masked mean cross-entropy in fp32 (reference: core/training.py
    compute_loss :1195-1260). Returns (loss, token_count). MoE models add
    the pre-scaled router aux losses when ``include_aux`` (training); eval
    passes ``include_aux=False`` so val loss/ppl stay pure LM cross-entropy,
    comparable with dense baselines.

    ``ce_chunk``: rows per fused-CE chunk (ops/fused_ce.py — folds the
    output projection into a chunked loss, never materializing [B,S,V]
    logits; differentiated, it computes the head's gradients in the same
    chunk walk, three matmuls a chunk as the full-logits path has). 0
    disables; -1 (default) auto-enables when the logits tensor would be
    HBM-significant. Both paths run the projection with fp32 accumulation
    and reduce in fp32, so toggling ce_chunk changes memory behavior only,
    not the computed loss. The fused path is a ``jax.custom_vjp``:
    forward-mode differentiation (``jax.jvp``, ``jacfwd``) through this
    loss is unsupported while it is on.

    ``with_moe_stats=True`` (MoE training step) opens a routing-stats tap
    around the forward pass and returns ``(loss, (token_count, stats))``
    where stats is the layer-summed dict from models/moe.py — the shape
    ``value_and_grad(has_aux=True)`` needs to carry traced routing stats
    out of the differentiated region."""
    if with_moe_stats and args.is_moe:
        from . import moe as moe_lib

        with moe_lib.routing_stats_tap() as tap:
            loss, count = loss_fn(
                params, batch, args, compute_dtype=compute_dtype,
                remat=remat, remat_ratio=remat_ratio, include_aux=include_aux,
                ce_chunk=ce_chunk, scan_layers=scan_layers,
                z_loss_weight=z_loss_weight, overlap=overlap,
            )
        return loss, (count, moe_lib.merge_stats(tap, args.num_local_experts))
    targets = batch["targets"]
    mask = batch["mask"].astype(jnp.float32)
    count = jnp.maximum(mask.sum(), 1.0)

    B, S = batch["inputs"].shape
    if ce_chunk < 0:
        ce_chunk = fused_ce.auto_chunk(B, S, args.vocab_size)
    untied = not args.tie_word_embeddings and "output" in params
    if ce_chunk > 0:
        hidden, _, aux = forward(
            params, batch["inputs"], args, compute_dtype=compute_dtype,
            remat=remat, remat_ratio=remat_ratio, return_aux=True,
            return_hidden=True, scan_layers=scan_layers, overlap=overlap,
        )
        with jax.named_scope("lm_head_ce"):
            if untied:
                w_vd = params["output"]["weight"].astype(compute_dtype).T
                bias = params["output"].get("bias")
            else:
                w_vd = params["tok_embeddings"]["weight"].astype(compute_dtype)
                bias = None
        from ..parallel.context import current_mesh

        mesh = current_mesh()
        with jax.named_scope("lm_head_ce"):
            if (mesh is not None and mesh.shape.get("sp", 1) > 1
                    and mesh.shape.get("tp", 1) == 1):
                # Sequence-sharded: shard_map keeps the chunked CE local to
                # each sp shard (ops/fused_ce.py::fused_cross_entropy_sp).
                out = fused_ce.fused_cross_entropy_sp(
                    hidden, w_vd, targets, mask, mesh, bias_v=bias,
                    logit_scale=args.logit_scale, chunk=ce_chunk,
                    z_weight=z_loss_weight,
                )
            else:
                out = fused_ce.fused_cross_entropy(
                    hidden, w_vd, targets, mask, bias_v=bias,
                    logit_scale=args.logit_scale, chunk=ce_chunk,
                    z_weight=z_loss_weight,
                )
            loss = out / count
    else:
        logits, _, aux = forward(
            params, batch["inputs"], args, compute_dtype=compute_dtype,
            remat=remat, remat_ratio=remat_ratio, return_aux=True,
            scan_layers=scan_layers, overlap=overlap,
        )
        with jax.named_scope("lm_head_ce"):
            logz = jax.nn.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
            nll = (logz - gold) * mask
            loss = nll.sum() / count
            if z_loss_weight > 0.0:
                loss = loss + z_loss_weight * jnp.sum(jnp.square(logz) * mask) / count
    if args.is_moe and include_aux:
        loss = loss + aux  # pre-scaled inside moe_block
    return loss, mask.sum()
