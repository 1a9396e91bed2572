"""Operations one causal call of each flash-attention kernel *executes* at
latent attention's head sizes: q and k 192 wide (128 + 64 rotary), v and dO
128. Per (sequence, head), half the S x S square, 2 operations a
multiply-add:

- forward: Q K^T over 192 and P V over 128 -> B*H*S^2 * (192 + 128);
- dQ kernel: Q K^T (192), dO V^T (128), dS K (192) -> B*H*S^2 * (192 + 128 + 192);
- dK/dV kernel: Q K^T (192), dO V^T (128), P^T dO (128), dS^T Q (192)
  -> B*H*S^2 * (192 + 128 + 128 + 192).

The trace's reader (``trace_scopes.kernel_peak_pct``) hands each function the
dimensions of the call's first output: ``o [B, H, S, 128]``, ``dq [B, H, S,
192]``, ``dk [B, H, S, 192]``. Each asserts the width it reads, so a call of
another head size is refused and not miscounted (the 128/128 calls of a dense
model are ``flops/flash_attention.py``'s). The forward's ``o`` is 128 wide
there too, so ``fwd`` cannot tell: its metric lists only cells of this head
size under ``workloads``.
"""

from __future__ import annotations

D_QK, D_V = 192, 128


def _square(B: int, H: int, S: int, D: int, expect: int) -> float:
    if D != expect:
        raise ValueError(f"first output is {D} wide, a {D_QK}/{D_V} call's is {expect}")
    return float(B) * H * S * S


def fwd(B: int, H: int, S: int, D: int) -> float:
    return _square(B, H, S, D, D_V) * (D_QK + D_V)


def bwd_dq(B: int, H: int, S: int, D: int) -> float:
    return _square(B, H, S, D, D_QK) * (D_QK + D_V + D_QK)


def bwd_dkv(B: int, H: int, S: int, D: int) -> float:
    return _square(B, H, S, D, D_QK) * (D_QK + D_V + D_V + D_QK)


BY_KERNEL = {"flash_fwd": fwd, "flash_bwd_dq": bwd_dq, "flash_bwd_dkv": bwd_dkv}
