"""Parameters, and operations a training step *requires* per data token, of the
``sdar_moe`` decoder as its configuration file cuts it (``experts_held`` of
``num_experts``), under the block-diffusion objective.

A data token is two rows through every layer (its noised copy and its clean
copy) and one through the head (the noised copy alone). Matmuls forward and
backward are 6 FLOPs per weight a row is multiplied by: q, k, v and ``wo``, the
router, the output head; not the input table (a lookup), not norm gains. The
routed experts count by the rows they were sent: ``held_experts_per_token`` is
the mean number of *held* experts the two rows of a token chose in a layer,
measured from the program's ``moe_rows_held`` counter in the run's window; a
uniform router sends ``2 * num_experts_per_tok * held / num_experts`` (2 at 2 *
8 * 16 / 128). Attention is what the mask requires: ``12 H D`` a (query, key)
pair (Q K^T and P V forward, twice that backward) over
``flash_blockdiff.pairs(L, B') = L^2 + L B'`` pairs a sequence and layer. No
recomputation; the last layer's clean rows, which feed no loss, are counted as
the equations have them (PERF.md section 7).
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from benchmark.flops.flash_blockdiff import pairs


def _sizes(cfg: Dict[str, Any]) -> Dict[str, int]:
    z = {k: int(cfg[k]) for k in (
        "hidden_size", "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
        "head_dim", "vocab_size", "num_experts", "num_experts_per_tok", "moe_intermediate_size",
        "block_length")}
    z["held"] = int(cfg["experts_held"]["count"])
    return z


def attention_params(z) -> int:
    """q and wo (H D wide each), k and v (G D wide)."""
    return z["hidden_size"] * z["head_dim"] * (2 * z["num_attention_heads"]
                                               + 2 * z["num_key_value_heads"])


def expert_params(z) -> int:
    return 3 * z["hidden_size"] * z["moe_intermediate_size"]


def uniform_held_experts_per_token(cfg: Dict[str, Any]) -> float:
    """Held experts the two rows of a data token choose in a layer, a uniform router."""
    z = _sizes(cfg)
    return 2.0 * z["num_experts_per_tok"] * z["held"] / z["num_experts"]


def routed_layers(cfg: Dict[str, Any]) -> int:
    return _sizes(cfg)["num_hidden_layers"]


def layer_params(cfg: Dict[str, Any]) -> int:
    """Every number of one layer: attention with its two head norms, two norms,
    the router, the held experts."""
    z = _sizes(cfg)
    C = z["hidden_size"]
    return (attention_params(z) + 2 * z["head_dim"] + 2 * C + C * z["num_experts"]
            + z["held"] * expert_params(z))


def total_params(cfg: Dict[str, Any]) -> int:
    z = _sizes(cfg)
    return (z["num_hidden_layers"] * layer_params(cfg)
            + 2 * z["hidden_size"] * z["vocab_size"] + z["hidden_size"])


def matmul_params(cfg: Dict[str, Any], held_experts_per_token: Optional[float] = None) -> float:
    """Weights a data token is multiplied by: two rows a layer outside the
    experts, ``held_experts_per_token`` experts (both rows' together), one row
    through the head."""
    z = _sizes(cfg)
    C = z["hidden_size"]
    held = uniform_held_experts_per_token(cfg) if held_experts_per_token is None \
        else float(held_experts_per_token)
    layer = 2 * (attention_params(z) + C * z["num_experts"]) + held * expert_params(z)
    return z["num_hidden_layers"] * layer + C * z["vocab_size"]


def attention_pairs(cfg: Dict[str, Any], seq_len: int) -> int:
    """(query, key) pairs a head of one sequence attends to, every layer summed."""
    z = _sizes(cfg)
    return z["num_hidden_layers"] * pairs(int(seq_len), z["block_length"])


def train_flops_per_token(cfg: Dict[str, Any], seq_len: int,
                          held_experts_per_token: Optional[float] = None) -> float:
    z = _sizes(cfg)
    attention = 12.0 * z["num_attention_heads"] * z["head_dim"] \
        * attention_pairs(cfg, seq_len) / int(seq_len)
    return 6.0 * matmul_params(cfg, held_experts_per_token) + attention
