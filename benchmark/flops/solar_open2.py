"""Parameters, and operations a training step *requires* per token, of the
``solar_open2`` decoder as its configuration file cuts it (``experts_held`` of
``n_routed_experts``, ``gqa_layers`` entry by entry, 0-based), or as published
(``total_params(cfg, published=True)``: the file's ``published`` depth, list,
experts and vocabulary: 250,287,810,304, the published "250B-A15B").

Matmuls forward and backward are 6 FLOPs per weight a token is multiplied by: a
KDA mixer's four head-wide projections, its two low-rank pairs and ``W_beta``; a
gated grouped-query mixer's five (``W_q``, ``W_k``, ``W_v``, the gate's ``W_g``,
``W_o``); the router, the shared expert, the output head; not the input table (a
lookup), not norm gains, not the depthwise convolutions (4 multiply-adds a
channel: VPU work). The routed experts count by the rows they were sent:
``held_experts_per_token`` is the mean number of *held* experts a token chose in
a layer, measured from the program's ``moe_rows_held`` counter in the run's
window; a uniform router sends ``num_experts_per_tok * held / n_routed_experts``
(0.2 at 8 * 8 / 320). A softmax layer's attention is ``12 H D`` a (query, key)
pair, forward plus twice backward, over ``S (S + 1) / 2`` pairs a sequence
(``flops/flash_attention.py``'s count without the recomputed products); a KDA
layer's core is ``flops/kda_chunk.py``'s chunked matmuls, forward and twice that
backward. No recomputation, no dead rows.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from benchmark.flops import kda_chunk
from benchmark.flops.kimi_linear import expert_params, kda_matmul_params, kda_mixer_params  # noqa: F401  (the same mixer, the same expert)


def _sizes(cfg: Dict[str, Any], published: bool = False) -> Dict[str, Any]:
    pub = cfg.get("published", {}) if published else {}
    lin = cfg["linear_attn_config"]
    z = {k: int(pub.get(k, cfg[k])) for k in (
        "hidden_size", "num_hidden_layers", "num_attention_heads", "num_key_value_heads", "head_dim",
        "vocab_size", "n_routed_experts", "num_experts_per_tok", "moe_intermediate_size",
        "n_shared_experts")}
    z["held"] = z["n_routed_experts"] if published else int(cfg["experts_held"]["count"])
    z["kda_heads"], z["kda_dim"] = int(lin["num_heads"]), int(lin["head_dim"])
    z["taps"] = int(lin["short_conv_kernel_size"])
    gqa = {int(l) for l in pub.get("gqa_layers", cfg["gqa_layers"])}
    z["kinds"] = ["G" if l in gqa else "K" for l in range(z["num_hidden_layers"])]
    return z


def gqa_mixer_params(z) -> int:
    """``W_q``, ``W_g``, ``W_o`` over the query heads, ``W_k``, ``W_v`` over the key/value heads; no gain."""
    return z["hidden_size"] * z["head_dim"] * (3 * z["num_attention_heads"] + 2 * z["num_key_value_heads"])


def uniform_held_experts_per_token(cfg: Dict[str, Any]) -> float:
    z = _sizes(cfg)
    return z["num_experts_per_tok"] * z["held"] / z["n_routed_experts"]


def routed_layers(cfg: Dict[str, Any]) -> int:
    return _sizes(cfg)["num_hidden_layers"]     # every layer routes (first_k_dense_replace 0)


def total_params(cfg: Dict[str, Any], published: bool = False) -> int:
    """Every number the program trains, the selection bias included; with
    ``published`` the whole model at its published depth, experts and vocabulary."""
    z = _sizes(cfg, published)
    C = z["hidden_size"]
    mixer = {"K": kda_mixer_params(z), "G": gqa_mixer_params(z)}
    routed = C * z["n_routed_experts"] + z["n_routed_experts"] \
        + (z["n_shared_experts"] + z["held"]) * expert_params(z)
    return 2 * C * z["vocab_size"] + C + sum(mixer[kind] + 2 * C + routed for kind in z["kinds"])


def matmul_params(cfg: Dict[str, Any], held_experts_per_token: Optional[float] = None) -> float:
    z = _sizes(cfg)
    C = z["hidden_size"]
    held = uniform_held_experts_per_token(cfg) if held_experts_per_token is None \
        else float(held_experts_per_token)
    mixer = {"K": kda_matmul_params(z), "G": gqa_mixer_params(z)}
    routed = C * z["n_routed_experts"] + (z["n_shared_experts"] + held) * expert_params(z)
    return float(C * z["vocab_size"]) + sum(mixer[kind] + routed for kind in z["kinds"])


def core_flops_per_token(cfg: Dict[str, Any], seq_len: int) -> float:
    """Forward operations a token of the mixers' cores, every layer summed: the
    delta rule's chunked matmuls a KDA layer, ``4 D`` a pair over ``(S + 1) / 2``
    keys a query and head a softmax layer."""
    z = _sizes(cfg)
    kda = kda_chunk.fwd_flops(1, 1, z["kda_heads"], z["kda_dim"])
    softmax = 2 * (int(seq_len) + 1) * z["num_attention_heads"] * z["head_dim"]
    return z["kinds"].count("K") * kda + z["kinds"].count("G") * softmax


def train_flops_per_token(cfg: Dict[str, Any], seq_len: int,
                          held_experts_per_token: Optional[float] = None) -> float:
    return 6.0 * matmul_params(cfg, held_experts_per_token) + 3.0 * core_flops_per_token(cfg, seq_len)
