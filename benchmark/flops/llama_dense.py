"""Operations a training step of the dense decoder *requires*, per token, from
shapes alone. Matmuls forward and backward are 6 FLOPs per weight they touch;
the input embedding table is a lookup and is not counted (with untied tables
it is a tenth of the weights); causal attention is 6 * layers * seq *
(heads * head_dim) (QK^T and PV, half the square, forward plus twice that
backward). Recomputation under remat is work the chip does, not work the
model requires, and is not counted.
"""

from __future__ import annotations

from typing import Any, Dict


def matmul_params(cfg: Dict[str, Any]) -> int:
    """Weights that a token is multiplied by: every layer matrix and the
    output head; not the input table, not the norm gains."""
    D, F = int(cfg["hidden_size"]), int(cfg["intermediate_size"])
    L, V = int(cfg["num_hidden_layers"]), int(cfg["vocab_size"])
    dq = int(cfg["num_attention_heads"]) * int(cfg["head_dim"])
    dkv = int(cfg["num_key_value_heads"]) * int(cfg["head_dim"])
    per_layer = D * dq + 2 * D * dkv + dq * D + 3 * D * F
    return L * per_layer + D * V


def total_params(cfg: Dict[str, Any]) -> int:
    D, L, V = int(cfg["hidden_size"]), int(cfg["num_hidden_layers"]), int(cfg["vocab_size"])
    return matmul_params(cfg) + V * D + (2 * L + 1) * D


def train_flops_per_token(cfg: Dict[str, Any], seq_len: int) -> float:
    dq = int(cfg["num_attention_heads"]) * int(cfg["head_dim"])
    attention = 6.0 * int(cfg["num_hidden_layers"]) * int(seq_len) * dq
    return 6.0 * matmul_params(cfg) + attention
