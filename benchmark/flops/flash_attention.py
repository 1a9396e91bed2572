"""Operations one call of each flash-attention kernel *executes*, causal,
from the call's shapes: B sequences, Hq query heads, S positions, head size D.

A full S x S tile pass is 2*S*S*D operations per matmul and head; the causal
kernels skip the tiles above the diagonal, half of the square (the diagonal
tiles' masked halves, S/block of the rest, are counted as skipped too, so a
share of peak computed from these reads a little low, never high):

- forward: Q K^T and P V, 2 matmuls -> 2 * B*Hq*S*S*D;
- dQ kernel: recomputes Q K^T, then dO V^T and dS K, 3 matmuls -> 3 * ...;
- dK/dV kernel: recomputes Q K^T, then dO V^T, P^T dO and dS^T Q, 4 -> 4 * ....

Together 9, against the 6 a step *requires* for attention forward and
backward (``llama_dense.train_flops_per_token``): the difference is the
recomputation the kernels do by design. At S 4,096 and D 128 in bf16 each is
compute-bound on a v5e: the forward moves about 4*B*Hq*S*D*2 bytes of q, k, v
and o for 2*B*Hq*S*S*D operations, S/4 = 1,024 operations a byte against the
chip's 197e12 / 819e9 = 240.
"""

from __future__ import annotations


def _half_square(B: int, Hq: int, S: int, D: int) -> float:
    return float(B) * Hq * S * S * D


def fwd(B: int, Hq: int, S: int, D: int) -> float:
    return 2.0 * _half_square(B, Hq, S, D)


def bwd_dq(B: int, Hq: int, S: int, D: int) -> float:
    return 3.0 * _half_square(B, Hq, S, D)


def bwd_dkv(B: int, Hq: int, S: int, D: int) -> float:
    return 4.0 * _half_square(B, Hq, S, D)


BY_KERNEL = {"flash_fwd": fwd, "flash_bwd_dq": bwd_dq, "flash_bwd_dkv": bwd_dkv}
