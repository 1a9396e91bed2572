"""Operations one call of each flash-attention kernel *executes* under the
sliding-window mask, from the call's shapes ``[B, Hq, S, D]`` and the window.

Query ``i`` sees keys ``j <= i`` with ``i - j < W``: ``band_positions(S, W) =
W (W + 1) / 2 + (S - W) W`` (query, key) pairs a head (all of the triangle
where ``W >= S``). A pair costs ``2 D`` operations a matmul; the forward runs
2 (Q K^T, P V), the dQ kernel 3, the dK/dV kernel 4, as
``flops/flash_attention.py`` counts the causal triangle (there ``S^2 / 2``
pairs a head; here the exact count, diagonal included). What the kernels
execute beyond the band (the masked parts of the tiles the band's two edges
cut) is not counted, so a share of peak computed from these reads a little
low, never high.

A band 2,048 wide is compute-bound like the triangle: a query block reads its
band's K and V once from VMEM-resident operands, 1,024 operations a byte of K
and V against the chip's 240.
"""

from __future__ import annotations


def band_positions(S: int, W: int) -> int:
    w = min(int(W), int(S))
    return w * (w + 1) // 2 + (int(S) - w) * w


def _pairs(B: int, Hq: int, S: int, D: int, W: int) -> float:
    return 2.0 * B * Hq * band_positions(S, W) * D


def fwd(B: int, Hq: int, S: int, D: int, W: int) -> float:
    return 2.0 * _pairs(B, Hq, S, D, W)


def bwd_dq(B: int, Hq: int, S: int, D: int, W: int) -> float:
    return 3.0 * _pairs(B, Hq, S, D, W)


def bwd_dkv(B: int, Hq: int, S: int, D: int, W: int) -> float:
    return 4.0 * _pairs(B, Hq, S, D, W)


BY_KERNEL = {"flash_fwd": fwd, "flash_bwd_dq": bwd_dq, "flash_bwd_dkv": bwd_dkv}
