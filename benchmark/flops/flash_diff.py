"""Operations one causal call of each flash-attention kernel *executes* at
differential attention's head sizes: q and k 64 wide, v and dO 128 (a pair's
two value heads side by side). Per (sequence, head-map), the triangle's ``S (S
+ 1) / 2`` (query, key) pairs, 2 operations a multiply-add:

- forward: Q K^T over 64 and P V over 128 -> pairs * 2 (64 + 128);
- dQ kernel: Q K^T (64), dO V^T (128), dS K (64) -> pairs * 2 (64 + 128 + 64);
- dK/dV kernel: Q K^T (64), dO V^T (128), P^T dO (128), dS^T Q (64)
  -> pairs * 2 (64 + 128 + 128 + 64).

A reader hands each function the dimensions of the call's first output: ``o
[B, H, S, 128]``, ``dq [B, H, S, 64]``, ``dk [B, H, S, 64]`` (``H`` the stacked
head-maps: both softmax maps of every pair). Each asserts the width it reads,
so a call of another head size is refused and not miscounted, as
``flops/flash_mla.py`` does. What the kernels execute beyond the triangle (the
masked halves of the diagonal tiles) is not counted, so a share of peak
computed from these reads a little low, never high.
"""

from __future__ import annotations

D_QK, D_V = 64, 128


def _pairs(B: int, H: int, S: int, D: int, expect: int) -> float:
    if D != expect:
        raise ValueError(f"first output is {D} wide, a {D_QK}/{D_V} call's is {expect}")
    return float(B) * H * (S * (S + 1) // 2)


def fwd(B: int, H: int, S: int, D: int) -> float:
    return _pairs(B, H, S, D, D_V) * 2 * (D_QK + D_V)


def bwd_dq(B: int, H: int, S: int, D: int) -> float:
    return _pairs(B, H, S, D, D_QK) * 2 * (D_QK + D_V + D_QK)


def bwd_dkv(B: int, H: int, S: int, D: int) -> float:
    return _pairs(B, H, S, D, D_QK) * 2 * (D_QK + D_V + D_V + D_QK)


BY_KERNEL = {"flash_fwd": fwd, "flash_bwd_dq": bwd_dq, "flash_bwd_dkv": bwd_dkv}
