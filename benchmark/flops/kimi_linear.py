"""Parameters, and operations a training step *requires* per token, of the
``kimi_linear`` decoder as its configuration file cuts it (``experts_held`` of
``num_experts``, the two 1-based layer lists entry by entry), or as published
(``total_params(cfg, published=True)``: the file's ``published`` depth, lists,
experts and vocabulary: 49,122,681,728, the published "48B-A3B").

Matmuls forward and backward are 6 FLOPs per weight a token is multiplied by: a
KDA mixer's four head-wide projections, its two low-rank pairs and ``W_beta``; a
latent mixer's four; the router, the shared expert, the dense layer's FFN, the
output head; not the input table (a lookup), not norm gains, not the depthwise
convolutions (4 multiply-adds a channel: VPU work). The routed experts count by
the rows they were sent: ``held_experts_per_token`` is the mean number of *held*
experts a token chose in a routed layer, measured from the program's
``moe_rows_held`` counter in the run's window; a uniform router sends
``num_experts_per_token * held / num_experts`` (0.5 at 8 * 16 / 256). A latent
layer's attention is ``6 (d_qk + d_v)`` a (query, key) pair over ``S (S + 1) /
2`` pairs a sequence (``flops/flash_mla.py``'s count without the recomputed
products); a KDA layer's core is ``flops/kda_chunk.py``'s chunked matmuls,
forward and twice that backward. No recomputation, no dead rows.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from benchmark.flops import kda_chunk


def _sizes(cfg: Dict[str, Any], published: bool = False) -> Dict[str, Any]:
    pub = cfg.get("published", {}) if published else {}
    lin = {**cfg["linear_attn_config"], **pub.get("linear_attn_config", {})}
    z = {k: int(pub.get(k, cfg[k])) for k in (
        "hidden_size", "intermediate_size", "num_hidden_layers", "first_k_dense_replace",
        "num_attention_heads", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
        "vocab_size", "num_experts", "num_experts_per_token", "moe_intermediate_size",
        "num_shared_experts")}
    z["held"] = z["num_experts"] if published else int(cfg["experts_held"]["count"])
    z["kda_heads"], z["kda_dim"] = int(lin["num_heads"]), int(lin["head_dim"])
    z["taps"] = int(lin["short_conv_kernel_size"])
    kda = {int(l) for l in lin["kda_layers"]}
    z["kinds"] = ["K" if l in kda else "M" for l in range(1, z["num_hidden_layers"] + 1)]
    return z


def kda_matmul_params(z) -> int:
    C, Hd, d = z["hidden_size"], z["kda_heads"] * z["kda_dim"], z["kda_dim"]
    return 4 * C * Hd + 2 * (C * d + d * Hd) + C * z["kda_heads"]


def kda_mixer_params(z) -> int:
    """The matmul weights and: three convolutions' taps, ``A_log``, ``dt_bias``, the head norm's gain."""
    Hd = z["kda_heads"] * z["kda_dim"]
    return kda_matmul_params(z) + 3 * Hd * z["taps"] + z["kda_heads"] + Hd + z["kda_dim"]


def latent_matmul_params(z) -> int:
    C, H = z["hidden_size"], z["num_attention_heads"]
    return (C * H * (z["qk_nope_head_dim"] + z["qk_rope_head_dim"])
            + C * (z["kv_lora_rank"] + z["qk_rope_head_dim"])
            + z["kv_lora_rank"] * H * (z["qk_nope_head_dim"] + z["v_head_dim"])
            + H * z["v_head_dim"] * C)


def latent_mixer_params(z) -> int:
    return latent_matmul_params(z) + z["kv_lora_rank"]


def expert_params(z) -> int:
    return 3 * z["hidden_size"] * z["moe_intermediate_size"]


def uniform_held_experts_per_token(cfg: Dict[str, Any]) -> float:
    z = _sizes(cfg)
    return z["num_experts_per_token"] * z["held"] / z["num_experts"]


def routed_layers(cfg: Dict[str, Any]) -> int:
    z = _sizes(cfg)
    return z["num_hidden_layers"] - z["first_k_dense_replace"]


def total_params(cfg: Dict[str, Any], published: bool = False) -> int:
    """Every number the program trains, the selection bias included; with
    ``published`` the whole model at its published depth, experts and vocabulary."""
    z = _sizes(cfg, published)
    C = z["hidden_size"]
    mixer = {"K": kda_mixer_params(z), "M": latent_mixer_params(z)}
    routed = C * z["num_experts"] + z["num_experts"] \
        + (z["num_shared_experts"] + z["held"]) * expert_params(z)
    total = 2 * C * z["vocab_size"] + C
    for i, kind in enumerate(z["kinds"]):
        total += mixer[kind] + 2 * C \
            + (3 * C * z["intermediate_size"] if i < z["first_k_dense_replace"] else routed)
    return total


def matmul_params(cfg: Dict[str, Any], held_experts_per_token: Optional[float] = None) -> float:
    z = _sizes(cfg)
    C = z["hidden_size"]
    held = uniform_held_experts_per_token(cfg) if held_experts_per_token is None \
        else float(held_experts_per_token)
    mixer = {"K": kda_matmul_params(z), "M": latent_matmul_params(z)}
    routed = C * z["num_experts"] + (z["num_shared_experts"] + held) * expert_params(z)
    total = float(C * z["vocab_size"])
    for i, kind in enumerate(z["kinds"]):
        total += mixer[kind] + (3 * C * z["intermediate_size"] if i < z["first_k_dense_replace"]
                                else routed)
    return total


def core_flops_per_token(cfg: Dict[str, Any], seq_len: int) -> float:
    """Forward operations a token of the mixers' cores, every layer summed: the
    delta rule's chunked matmuls a KDA layer, ``2 (d_qk + d_v)`` a pair over
    ``(S + 1) / 2`` keys a query a latent layer."""
    z = _sizes(cfg)
    kda = kda_chunk.fwd_flops(1, 1, z["kda_heads"], z["kda_dim"])
    latent = (int(seq_len) + 1) * z["num_attention_heads"] \
        * (z["qk_nope_head_dim"] + z["qk_rope_head_dim"] + z["v_head_dim"])
    return z["kinds"].count("K") * kda + z["kinds"].count("M") * latent


def train_flops_per_token(cfg: Dict[str, Any], seq_len: int,
                          held_experts_per_token: Optional[float] = None) -> float:
    return 6.0 * matmul_params(cfg, held_experts_per_token) + 3.0 * core_flops_per_token(cfg, seq_len)
