"""Parameters, and operations a training step *requires* per token, of the
latent-attention, sparse-expert decoder as its configuration file cuts it
(``experts_held`` of ``n_routed_experts``, one MTP module).

Matmuls forward and backward are 6 FLOPs per weight a token is multiplied
by: the latent projections, the two mixing maps' ``Phi``, the router, the
shared expert, the dense layer's FFN, the MTP module's joining projection,
the output head once for each of the two heads; not the input table (a
lookup), not norm gains, not the Sinkhorn iterations (elementwise). The routed
experts count by the rows they were sent: ``held_experts_per_token`` is the
mean number of *held* experts a token chose in a routed layer, measured from
the program's ``moe_rows_held`` counter in the run's window; a uniform router
sends ``num_experts_per_tok * held / n_routed_experts`` (0.5 at 4 * 8 / 64).
Causal attention is ``3 * S * heads * (d_qk + d_v)`` a layer: QK^T over d_qk
and PV over d_v, half the square, forward plus twice that backward.
Recomputation under remat is not counted.
"""

from __future__ import annotations

from typing import Any, Dict, Optional


def _sizes(cfg: Dict[str, Any]) -> Dict[str, int]:
    z = {k: int(cfg[k]) for k in (
        "hidden_size", "intermediate_size", "num_hidden_layers", "first_k_dense_replace",
        "num_attention_heads", "vocab_size", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
        "qk_rope_head_dim", "v_head_dim", "n_routed_experts", "num_experts_per_tok",
        "moe_intermediate_size", "n_shared_experts", "hc_mult", "num_nextn_predict_layers")}
    z["held"] = int(cfg["experts_held"]["count"])
    return z


def attention_params(z) -> int:
    C, H = z["hidden_size"], z["num_attention_heads"]
    dqk = z["qk_nope_head_dim"] + z["qk_rope_head_dim"]
    return (C * z["q_lora_rank"] + z["q_lora_rank"] * H * dqk
            + C * (z["kv_lora_rank"] + z["qk_rope_head_dim"])
            + z["kv_lora_rank"] * H * (z["qk_nope_head_dim"] + z["v_head_dim"])
            + H * z["v_head_dim"] * C)


def mixing_params(z) -> int:
    n = z["hc_mult"]
    return 2 * n * z["hidden_size"] * (2 * n + n * n)


def expert_params(z) -> int:
    return 3 * z["hidden_size"] * z["moe_intermediate_size"]


def uniform_held_experts_per_token(cfg: Dict[str, Any]) -> float:
    z = _sizes(cfg)
    return z["num_experts_per_tok"] * z["held"] / z["n_routed_experts"]


def routed_layers(cfg: Dict[str, Any]) -> int:
    """Layers with a router: the main model's and the MTP module's."""
    z = _sizes(cfg)
    return z["num_hidden_layers"] - z["first_k_dense_replace"] + z["num_nextn_predict_layers"]


def matmul_params(cfg: Dict[str, Any], held_experts_per_token: Optional[float] = None) -> float:
    z = _sizes(cfg)
    C = z["hidden_size"]
    held = uniform_held_experts_per_token(cfg) if held_experts_per_token is None \
        else float(held_experts_per_token)
    shared = attention_params(z) + mixing_params(z)
    routed = shared + C * z["n_routed_experts"] + (z["n_shared_experts"] + held) * expert_params(z)
    dense = shared + 3 * C * z["intermediate_size"]
    n_mtp = z["num_nextn_predict_layers"]
    return (z["first_k_dense_replace"] * dense + routed_layers(cfg) * routed
            + n_mtp * 2 * C * C + (1 + n_mtp) * C * z["vocab_size"])


def total_params(cfg: Dict[str, Any]) -> int:
    """Every number the program trains, the selection bias included."""
    z = _sizes(cfg)
    C, n = z["hidden_size"], z["hc_mult"]
    k = 2 * n + n * n
    small = 2 * (3 + k) + 2 * C + z["q_lora_rank"] + z["kv_lora_rank"]   # a layer's gains and map biases
    routed = (attention_params(z) + mixing_params(z) + small + C * z["n_routed_experts"]
              + z["n_routed_experts"] + (z["n_shared_experts"] + z["held"]) * expert_params(z))
    dense = attention_params(z) + mixing_params(z) + small + 3 * C * z["intermediate_size"]
    n_mtp = z["num_nextn_predict_layers"]
    return (z["first_k_dense_replace"] * dense + routed_layers(cfg) * routed
            + n_mtp * (2 * C * C + 3 * C) + 2 * C * z["vocab_size"] + C)


def train_flops_per_token(cfg: Dict[str, Any], seq_len: int,
                          held_experts_per_token: Optional[float] = None) -> float:
    z = _sizes(cfg)
    layers = z["num_hidden_layers"] + z["num_nextn_predict_layers"]
    d = z["qk_nope_head_dim"] + z["qk_rope_head_dim"] + z["v_head_dim"]
    attention = 3.0 * layers * int(seq_len) * z["num_attention_heads"] * d
    return 6.0 * matmul_params(cfg, held_experts_per_token) + attention
