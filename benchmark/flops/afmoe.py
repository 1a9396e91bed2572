"""Parameters, and operations a training step *requires* per token, of the
``afmoe`` decoder as its configuration file cuts it (``experts_held`` of
``num_experts``, ``layer_types`` entry by entry).

Matmuls forward and backward are 6 FLOPs per weight a token is multiplied by:
q, k, v, the output gate and ``wo``, the router, the shared expert, the dense
layer's FFN, the output head; not the input table (a lookup), not norm gains.
The routed experts count by the rows they were sent: ``held_experts_per_token``
is the mean number of *held* experts a token chose in a routed layer, measured
from the program's ``moe_rows_held`` counter in the run's window; a uniform
router sends ``num_experts_per_tok * held / num_experts`` (1 at 8 * 16 / 128).
Attention is what each layer's own mask requires: ``12 H D`` a (query, key)
pair (Q K^T and P V forward, twice that backward), over ``S (S + 1) / 2`` pairs
a sequence in a full layer and ``flash_window.band_positions(S, W)`` in a
sliding one. No recomputation, no dead rows.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from benchmark.flops.flash_window import band_positions

SLIDING = "sliding_attention"


def _sizes(cfg: Dict[str, Any]) -> Dict[str, int]:
    z = {k: int(cfg[k]) for k in (
        "hidden_size", "intermediate_size", "num_hidden_layers", "num_dense_layers",
        "num_attention_heads", "num_key_value_heads", "head_dim", "vocab_size", "num_experts",
        "num_experts_per_tok", "moe_intermediate_size", "num_shared_experts")}
    z["held"] = int(cfg["experts_held"]["count"])
    return z


def attention_params(z) -> int:
    """q, the output gate and wo (H D wide each), k and v (G D wide)."""
    return z["hidden_size"] * z["head_dim"] * (3 * z["num_attention_heads"]
                                               + 2 * z["num_key_value_heads"])


def expert_params(z) -> int:
    return 3 * z["hidden_size"] * z["moe_intermediate_size"]


def uniform_held_experts_per_token(cfg: Dict[str, Any]) -> float:
    z = _sizes(cfg)
    return z["num_experts_per_tok"] * z["held"] / z["num_experts"]


def routed_layers(cfg: Dict[str, Any]) -> int:
    z = _sizes(cfg)
    return z["num_hidden_layers"] - z["num_dense_layers"]


def routed_layer_params(cfg: Dict[str, Any]) -> int:
    """Every number of one routed layer: attention with its two head norms,
    four norms, the router with its selection bias, the shared expert, the
    held experts."""
    z = _sizes(cfg)
    C = z["hidden_size"]
    return (attention_params(z) + 2 * z["head_dim"] + 4 * C + C * z["num_experts"]
            + z["num_experts"] + (z["num_shared_experts"] + z["held"]) * expert_params(z))


def dense_layer_params(cfg: Dict[str, Any]) -> int:
    z = _sizes(cfg)
    C = z["hidden_size"]
    return attention_params(z) + 2 * z["head_dim"] + 4 * C + 3 * C * z["intermediate_size"]


def total_params(cfg: Dict[str, Any]) -> int:
    """Every number the program trains, the selection bias included."""
    z = _sizes(cfg)
    return (z["num_dense_layers"] * dense_layer_params(cfg)
            + routed_layers(cfg) * routed_layer_params(cfg)
            + 2 * z["hidden_size"] * z["vocab_size"] + z["hidden_size"])


def matmul_params(cfg: Dict[str, Any], held_experts_per_token: Optional[float] = None) -> float:
    z = _sizes(cfg)
    C = z["hidden_size"]
    held = uniform_held_experts_per_token(cfg) if held_experts_per_token is None \
        else float(held_experts_per_token)
    routed = attention_params(z) + C * z["num_experts"] \
        + (z["num_shared_experts"] + held) * expert_params(z)
    dense = attention_params(z) + 3 * C * z["intermediate_size"]
    return z["num_dense_layers"] * dense + routed_layers(cfg) * routed + C * z["vocab_size"]


def attention_pairs(cfg: Dict[str, Any], seq_len: int) -> int:
    """(query, key) pairs a head of one sequence attends to, every layer summed."""
    S, W = int(seq_len), int(cfg["sliding_window"])
    return sum(band_positions(S, W) if t == SLIDING else S * (S + 1) // 2
               for t in cfg["layer_types"])


def train_flops_per_token(cfg: Dict[str, Any], seq_len: int,
                          held_experts_per_token: Optional[float] = None) -> float:
    z = _sizes(cfg)
    attention = 12.0 * z["num_attention_heads"] * z["head_dim"] \
        * attention_pairs(cfg, seq_len) / int(seq_len)
    return 6.0 * matmul_params(cfg, held_experts_per_token) + attention
