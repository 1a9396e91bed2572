"""Parameters, and MXU operations a training step *requires* per token, of the
``sambay`` decoder as its configuration file cuts it (``layer_kinds`` entry by
entry: ``M`` Mamba, ``S`` / ``F`` window / full differential attention, ``G``
gated memory unit, ``C`` cross-attention).

Matmuls forward and backward are 6 FLOPs per weight a token is multiplied by:
every mixer's projections, the FFNs, and the tied table counted once, as the
head (the lookup is no matmul); not biases, norms, the depthwise convolution,
``A_log``, ``D`` or the lambda vectors. Attention is both softmax maps of a
layer under its mask: ``num_attention_heads`` head-maps a layer (half as many
pairs, two maps each), ``2 (D + 2 D)`` operations a (query, key) pair forward
(``Q K^T`` over 64, ``P V`` over 128) and three times that with the backward,
over ``S (S + 1) / 2`` pairs a sequence in an ``F`` or ``C`` layer and
``flash_window.band_positions(S, W)`` in an ``S`` layer. No recomputation.

The selective scan is left out on purpose: its ``d_inner * d_state`` state
updates a token a layer are elementwise work on the VPU and the EUP, which the
chip's bf16 peak does not describe (``flops/ssm_scan.py`` counts them and the
bytes a scan has to move).
"""

from __future__ import annotations

from typing import Any, Dict

from benchmark.flops.flash_window import band_positions


def _sizes(cfg: Dict[str, Any]) -> Dict[str, int]:
    z = {k: int(cfg[k]) for k in ("hidden_size", "intermediate_size", "num_hidden_layers",
                                  "num_attention_heads", "num_key_value_heads", "head_dim",
                                  "vocab_size", "sliding_window")}
    ssm = cfg["ssm"]
    z.update(d_inner=int(ssm["expand"]) * z["hidden_size"], d_state=int(ssm["d_state"]),
             d_conv=int(ssm["d_conv"]), dt_rank=int(ssm["dt_rank"]))
    return z


def mixer_matmul_params(cfg: Dict[str, Any]) -> Dict[str, int]:
    """Weights of each kind's mixer that a token is multiplied by."""
    z = _sizes(cfg)
    C, Di, N, R = z["hidden_size"], z["d_inner"], z["d_state"], z["dt_rank"]
    H, G, D = z["num_attention_heads"], z["num_key_value_heads"], z["head_dim"]
    attn = C * (H + 2 * G) * D + H * D * C
    return {"M": C * 2 * Di + Di * (R + 2 * N) + R * Di + Di * C, "G": 2 * C * Di,
            "S": attn, "F": attn, "C": 2 * C * H * D}


def layer_params(cfg: Dict[str, Any]) -> Dict[str, int]:
    """Every number of one layer of each kind: the mixer with its biases and
    vectors, two LayerNorms (weight and bias), the FFN."""
    z = _sizes(cfg)
    C, Di, N = z["hidden_size"], z["d_inner"], z["d_state"]
    H, G, D = z["num_attention_heads"], z["num_key_value_heads"], z["head_dim"]
    mm = mixer_matmul_params(cfg)
    diff = 4 * D + 2 * D + C                     # lambda vectors, the norm's gain, b_o
    extra = {"M": Di * z["d_conv"] + Di + Di + Di * N + Di,   # conv w, b; b_dt; A_log; D
             "G": 0, "S": (H + 2 * G) * D + diff, "F": (H + 2 * G) * D + diff, "C": H * D + diff}
    shared = 4 * C + 3 * C * z["intermediate_size"]
    return {k: mm[k] + extra[k] + shared for k in mm}


def _kinds(cfg: Dict[str, Any], published: bool):
    kinds = cfg["published"]["layer_kinds"] if published else cfg["layer_kinds"]
    if not published and len(kinds) != int(cfg["num_hidden_layers"]):
        raise ValueError("layer_kinds does not name num_hidden_layers layers")
    return list(kinds)


def total_params(cfg: Dict[str, Any], published: bool = False) -> int:
    """Every number the program trains (``published``: at the published depth,
    from the configuration's ``published.layer_kinds``). The table once: it is
    the head too."""
    z = _sizes(cfg)
    by_kind = layer_params(cfg)
    return (sum(by_kind[k] for k in _kinds(cfg, published))
            + z["vocab_size"] * z["hidden_size"] + 2 * z["hidden_size"])


def matmul_params(cfg: Dict[str, Any]) -> int:
    z = _sizes(cfg)
    mm = mixer_matmul_params(cfg)
    ffn = 3 * z["hidden_size"] * z["intermediate_size"]
    return sum(mm[k] + ffn for k in _kinds(cfg, False)) + z["hidden_size"] * z["vocab_size"]


def attention_pairs(cfg: Dict[str, Any], seq_len: int) -> int:
    """(query, key) pairs one softmax map of one sequence attends to, every
    attention layer summed."""
    S, W = int(seq_len), int(cfg["sliding_window"])
    return sum(band_positions(S, W) if k == "S" else S * (S + 1) // 2
               for k in _kinds(cfg, False) if k in "SFC")


def train_flops_per_token(cfg: Dict[str, Any], seq_len: int) -> float:
    z = _sizes(cfg)
    per_pair = 3.0 * z["num_attention_heads"] * 2 * (z["head_dim"] + 2 * z["head_dim"])
    return 6.0 * matmul_params(cfg) + per_pair * attention_pairs(cfg, seq_len) / int(seq_len)
