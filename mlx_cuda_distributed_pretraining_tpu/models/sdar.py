"""Softmax-routed expert decoder trained by diffusion over blocks (architecture
``sdar_moe``; the SDAR family, arXiv:2510.06303, trained by the block-diffusion
recipe of BD3-LM, arXiv:2503.09573).

**Data.** A packed row of ``L`` tokens ``x_0``; block length ``B'``; block of
position ``i``: ``b(i) = i // B'``. Noise, a block at a time
(``data/block_diffusion.py``): ``u_b ~ U[0, 1)``, ``t_b = eps + (1 - eps)
u_b``; ``m_i ~ Bernoulli(t_b(i))``; ``x_t[i] = MASK if m_i else x_0[i]``.

**Rows.** ``Z_0 = [Emb(x_t) ; Emb(x_0)]``, ``[2L, C]``; positions ``p =
[0..L-1 ; 0..L-1]``. With ``blk(r) = (r mod L) // B'``, query row ``r`` sees key
row ``c`` iff (``ops/masks.py::block_diffusion``)

- ``r`` noised, ``c`` noised: ``blk(c) == blk(r)`` (its own block, both directions);
- ``r`` noised, ``c`` clean: ``blk(c) < blk(r)`` (every earlier block, clean);
- ``r`` clean, ``c`` clean: ``blk(c) <= blk(r)`` (causal by block);
- ``r`` clean, ``c`` noised: never.

**Layer** (all alike): ``H = Z + Attn(RMSNorm(Z))``, ``Z' = H + MoE(RMSNorm(H))``.
``Attn(x)``: ``q = RMSNorm_D(x W_q)`` (``H`` heads of ``D``, the norm over a
head's channels with a learned gain), ``k = RMSNorm_D(x W_k)`` (``G`` heads),
``v = x W_v``; rotary on every channel of ``q`` and ``k`` at positions ``p``,
half-split convention; scores ``q k^T / sqrt(D)`` under the mask above, softmax
in float32; ``W_o``. No bias, no gate.
``MoE(x)``: ``P = softmax(x W_r)`` over all experts, float32; ``S = topk(P)``;
``g_e = P_e / sum_{e' in S} P_e'``; ``y = sum_{e in S, e held} g_e W_down,e
(silu(x W_gate,e) * (x W_up,e))``. No shared expert, no selection bias, no
scaling factor; selections of experts held elsewhere add nothing
(``experts_held = (first, count)``: one expert-parallel rank's share).

**Head.** ``h = RMSNorm(Z_last)``; logits ``h_i W_out`` for the noised rows ``i
< L`` only; ``loss = (1 / N) sum_i mask_i m_i (1 / t_b(i)) CE(logits_i,
x_0[i])``, ``N = sum_i mask_i``. Position ``i`` predicts the token *at* ``i``:
no shift. The batch brings ``noised_inputs``, ``targets`` (``x_0``) and
``loss_weights`` (``mask_i m_i / t_b(i)``).

Training path only: decoding by diffusion over blocks (several denoising steps a
block, a cache of the finished blocks) is a scheduler's matter (ROADMAP R-M7).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops import attention as attention_ops
from ..ops import flash_attention as flash_ops
from . import moe as moe_lib
from . import stack
from .llama import apply_rope, rms_norm, rope_cos_sin
from .registry import Architecture, register

Params = Dict[str, Any]


@dataclass(frozen=True)
class SdarArgs:
    vocab_size: int = 259
    hidden_size: int = 128
    num_layers: int = 2
    num_heads: int = 4
    num_kv_heads: int = 2
    head_dim: int = 32
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e6
    attention_type: str = "simple"     # simple | flash
    # experts
    n_routed_experts: int = 8          # the router's width
    num_experts_per_tok: int = 2
    moe_intermediate_size: int = 64
    experts_held: Tuple[int, int] = (0, 8)   # (first id, count) of the experts this chip holds
    held_chunk_rows: int = moe_lib.HELD_CHUNK_ROWS
    # the objective's noise, drawn by the loader (data/block_diffusion.py)
    block_length: int = 4
    noise_eps: float = 1e-3
    mask_id: int = 258
    matmul_precision: Optional[str] = None

    # What the trainer asks of any model's args.
    @property
    def is_moe(self) -> bool:
        return True

    @property
    def num_local_experts(self) -> int:   # width of the routing statistics
        return self.n_routed_experts

    @property
    def diffusion(self) -> Dict[str, Any]:  # how the trainer's loader noises a batch
        return {"block_length": self.block_length, "eps": self.noise_eps, "mask_id": self.mask_id}

    @classmethod
    def from_config(cls, model_cfg: Any, vocab_size: int) -> "SdarArgs":
        sec = lambda name: dict(getattr(model_cfg, name, None) or {})
        rope, norm, moe, diffusion = (sec(k) for k in ("rope", "normalization", "moe", "diffusion"))
        n_routed = int(moe["num_experts"])
        held = tuple(int(v) for v in (moe.get("experts_held") or (0, n_routed)))
        if held[0] < 0 or held[1] < 1 or held[0] + held[1] > n_routed:
            raise ValueError(f"moe.experts_held {held} is no range of {n_routed} experts")
        mask_id = int(diffusion.get("mask_id", vocab_size - 1))
        if not 0 <= mask_id < vocab_size:
            raise ValueError(f"diffusion.mask_id {mask_id} is no id of a vocabulary of {vocab_size}")
        return cls(
            vocab_size=vocab_size, hidden_size=model_cfg.hidden_size,
            num_layers=model_cfg.num_layers, num_heads=model_cfg.num_heads,
            num_kv_heads=model_cfg.num_kv_heads, head_dim=model_cfg.head_dim,
            rms_norm_eps=float(norm.get("rms_norm_eps", 1e-6)),
            rope_theta=float(rope.get("theta", 1e6)),
            attention_type=model_cfg.attention_type,
            n_routed_experts=n_routed,
            num_experts_per_tok=int(moe["num_experts_per_tok"]),
            moe_intermediate_size=int(moe["moe_intermediate_size"]),
            experts_held=held,
            held_chunk_rows=int(moe.get("held_chunk_rows") or moe_lib.HELD_CHUNK_ROWS),
            block_length=int(diffusion.get("block_length", 4)),
            noise_eps=float(diffusion.get("eps", 1e-3)), mask_id=mask_id,
            matmul_precision=getattr(model_cfg, "matmul_precision", None),
        )


# -- init ---------------------------------------------------------------------
def init_params(rng: jax.Array, args: SdarArgs, dtype=jnp.float32) -> Params:
    """normal(0.02) projections, residual outputs scaled by 1/sqrt(2 * layers),
    ones for norm gains."""
    counter = iter(range(1 << 30))
    key = lambda: jax.random.fold_in(rng, next(counter))
    std = 0.02
    res_std = std / (2 * args.num_layers) ** 0.5
    C, H, G, D = args.hidden_size, args.num_heads, args.num_kv_heads, args.head_dim
    F, held = args.moe_intermediate_size, args.experts_held[1]
    dense = lambda shape, s: {"weight": (jax.random.normal(key(), shape, jnp.float32) * s).astype(dtype)}
    ones = lambda d: {"weight": jnp.ones((d,), dtype)}

    def layer():
        return {
            "attention_norm": ones(C),
            "attention": {"wq": dense((C, H * D), std), "wk": dense((C, G * D), std),
                          "wv": dense((C, G * D), std), "q_norm": ones(D), "k_norm": ones(D),
                          "wo": dense((H * D, C), res_std)},
            "ffn_norm": ones(C),
            "feed_forward": {"router": dense((C, args.n_routed_experts), std),
                             "experts": {"w_gate": dense((held, C, F), std),
                                         "w_up": dense((held, C, F), std),
                                         "w_down": dense((held, F, C), res_std)}},
        }

    return {
        "tok_embeddings": dense((args.vocab_size, C), std),
        "layers": [layer() for _ in range(args.num_layers)],
        "norm": ones(C),
        "output": dense((C, args.vocab_size), std),
    }


# -- sub-layers ---------------------------------------------------------------------
def attention(p: Params, x: jnp.ndarray, args: SdarArgs, positions) -> jnp.ndarray:
    """QK-normed grouped-query attention over the doubled rows ``x [B, 2L, C]``
    under the block-diffusion mask."""
    B, S, _ = x.shape
    H, G, D = args.num_heads, args.num_kv_heads, args.head_dim
    with jax.named_scope("attn_qkv"):
        q = rms_norm((x @ p["wq"]["weight"]).reshape(B, S, H, D), p["q_norm"]["weight"],
                     args.rms_norm_eps)
        k = rms_norm((x @ p["wk"]["weight"]).reshape(B, S, G, D), p["k_norm"]["weight"],
                     args.rms_norm_eps)
        v = (x @ p["wv"]["weight"]).reshape(B, S, G, D)
        cos, sin = rope_cos_sin(positions, D, args.rope_theta)
        q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
    out = attention_ops.attention_core(q, k, v, args.attention_type, kind="blockdiff",
                                       precision=args.matmul_precision,
                                       mask_type="block_diffusion", window_size=args.block_length)
    with jax.named_scope("attn_out"):
        return out.reshape(B, S, H * D) @ p["wo"]["weight"]


def routed_ffn(p: Params, x: jnp.ndarray, args: SdarArgs):
    """The held share of the softmax-routed experts → ``(y, stats)``. No tail
    goes into the chunk loop: what follows the experts is the residual add,
    whose backward reads no value (``moe.held_share_ffn``)."""
    return moe_lib.routed_share_ffn(
        p, x, lambda h, router: moe_lib.softmax_route(h, router, args.num_experts_per_tok),
        args.experts_held, args.n_routed_experts, args.held_chunk_rows, args.matmul_precision)


def block(p: Params, x: jnp.ndarray, positions, args: SdarArgs):
    """One decoder layer → ``(x', routing stats)``."""
    with jax.named_scope("layer"):
        with jax.named_scope("norm"):
            h = rms_norm(x, p["attention_norm"]["weight"], args.rms_norm_eps)
        y = attention(p["attention"], h, args, positions)
        with jax.named_scope("norm"):
            x = x + y
            h = rms_norm(x, p["ffn_norm"]["weight"], args.rms_norm_eps)
        y, stats = routed_ffn(p["feed_forward"], h, args)
        with jax.named_scope("norm"):
            return x + y, stats


def hidden_states(params: Params, noised: jnp.ndarray, clean: jnp.ndarray, args: SdarArgs,
                  compute_dtype=jnp.float32, remat: Optional[str] = None,
                  scan_layers: bool = False):
    """``noised``, ``clean`` ``[B, L]`` → (final-normed state of the *noised*
    rows ``[B, L, C]``, layer-summed routing stats)."""
    L = noised.shape[1]
    if L % args.block_length:
        raise ValueError(f"block length {args.block_length} does not divide a row of {L}")
    with jax.named_scope("bd_rows"):  # two copies of a row side by side, positions twice
        tokens = jnp.concatenate([noised, clean], axis=1)
        positions = jnp.tile(jnp.arange(L, dtype=jnp.int32), 2)
    with jax.named_scope("embed"):
        x = params["tok_embeddings"]["weight"][tokens].astype(compute_dtype)
    x, stats = stack.run_layers(lambda p, x, _: block(p, x, positions, args), x, params["layers"],
                                compute_dtype, remat, scan=scan_layers,
                                zero=moe_lib.zero_stats(args.n_routed_experts))
    with jax.named_scope("bd_rows"):  # the head reads the noised copy alone
        x = x[:, :L]
    with jax.named_scope("final_norm"):
        return rms_norm(x, params["norm"]["weight"], args.rms_norm_eps), stats


def forward(params: Params, tokens: jnp.ndarray, args: SdarArgs, cache=None, start_pos: Any = 0,
            compute_dtype=jnp.float32, remat: Optional[str] = None, scan_layers: bool = False,
            noised_tokens: Optional[jnp.ndarray] = None, **_unused):
    """tokens ``[B, L]`` (the clean copy) and ``noised_tokens`` (the same where
    none is given) → (logits of the noised rows ``[B, L, V]`` float32, None)."""
    if cache is not None:
        raise NotImplementedError("sdar_moe has no cached decode: generation by diffusion over "
                                  "blocks is a scheduler's matter")
    noised = tokens if noised_tokens is None else noised_tokens
    h, _ = hidden_states(params, noised, tokens, args, compute_dtype, remat, scan_layers)
    return stack.head_logits(h, params["output"]["weight"], 1, compute_dtype), None


def loss_fn(params: Params, batch: Dict[str, jnp.ndarray], args: SdarArgs,
            compute_dtype=jnp.float32, remat: Optional[str] = None, remat_ratio: float = 1.0,
            include_aux: bool = True, ce_chunk: int = -1, scan_layers: bool = False,
            z_loss_weight: float = 0.0, with_moe_stats: bool = False, overlap: bool = False):
    """``(loss, token_count)``: the block-diffusion loss of the module's
    docstring through the fused CE over the ``L`` noised rows, on a batch of
    ``data/block_diffusion.py``; no auxiliary term. ``with_moe_stats`` returns
    ``(loss, (count, stats))``: the routing statistics summed over the layers,
    the positions whose loss counted (``bd_loss_rows``) and what the attention
    plan traced visits of its tile grid (``bd_tiles_live`` of ``bd_tiles_grid``,
    a head and forward call, ``bd_tiles_masked`` of them under a mask over the
    whole tile and ``bd_tiles_narrow`` in narrower squares; all 0 without the
    kernels)."""
    del remat_ratio, include_aux, overlap  # no aux term; overlap: the llama stack's fsdp schedule
    if "noised_inputs" not in batch:
        raise KeyError("sdar_moe trains on a block-diffusion batch (noised_inputs, loss_weights): "
                       "wrap the loader in data.block_diffusion.BlockDiffusionBatches")
    h, stats = hidden_states(params, batch["noised_inputs"], batch["inputs"], args, compute_dtype,
                             remat, scan_layers)
    targets, count = batch["targets"], batch["mask"].astype(jnp.float32).sum()
    w_vd = stack.head_weight(params["output"]["weight"], 1, compute_dtype)
    with jax.named_scope("lm_head_ce"):
        weights = batch["loss_weights"].astype(jnp.float32) / jnp.maximum(count, 1.0)
    loss = stack.head_ce(h, w_vd, targets, weights,
                         stack.ce_chunk_rows(ce_chunk, *targets.shape, args.vocab_size),
                         z_loss_weight)
    if not with_moe_stats:
        return loss, count
    tiles = flash_ops.bd_tiles_traced() if args.attention_type == "flash" else {}
    stats = dict(stats, bd_loss_rows=(batch["loss_weights"] > 0).sum().astype(jnp.float32),
                 **{f"bd_tiles_{k}": jnp.float32(tiles.get(k, 0))
                    for k in ("live", "grid", "masked", "narrow")})
    return loss, (count, stats)


def matmul_params_per_token(args: SdarArgs) -> int:
    """Weights a *data* token is multiplied by: both of its rows go through
    every layer (a uniform router assumed for the held share: ``top_k * held /
    routed`` experts a row), its noised row alone through the head; no input
    table, no gains."""
    C, H, G, D = args.hidden_size, args.num_heads, args.num_kv_heads, args.head_dim
    held = args.num_experts_per_tok * args.experts_held[1] / args.n_routed_experts
    layer = C * D * (2 * H + 2 * G) + C * args.n_routed_experts \
        + held * 3 * C * args.moe_intermediate_size
    return int(2 * args.num_layers * layer + C * args.vocab_size)


def flops_per_token(args: SdarArgs, seq_len: int) -> float:
    """Training FLOPs a data token requires: 6 a multiplied weight, and each
    layer's attention ``12 H D`` a (query, key) pair over the ``L^2 + L B'``
    pairs the mask admits over the two copies of a row of ``L``."""
    pairs = seq_len * seq_len + seq_len * args.block_length
    return 6.0 * matmul_params_per_token(args) \
        + 12.0 * args.num_heads * args.head_dim * args.num_layers * pairs / seq_len


register(Architecture("sdar_moe", SdarArgs, init_params, forward, loss_fn,
                      flops_per_token=flops_per_token,
                      plans={"attn_plan": ("attention layers (traced, by kind and kernel path)",
                                           attention_ops.core_counts)}))
