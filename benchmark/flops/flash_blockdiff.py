"""Operations one call of each flash-attention kernel *requires* under the
block-diffusion mask (``ops/masks.py::block_diffusion``), from the call's
shapes ``[B, Hq, S, D]`` (``S = 2 L`` rows: the noised copy of a sequence, then
the clean one) and the block length ``B'``.

With ``nb = L / B'`` blocks a copy, the (query, key) pairs a head:

- clean on clean, causal by block: ``B'^2 nb (nb + 1) / 2``;
- noised on clean, every earlier block: ``B'^2 nb (nb - 1) / 2``;
- noised on noised, a block's own square: ``nb B'^2``;

together ``L^2 + L B'``. A pair costs ``2 D`` operations a matmul; the forward
runs 2 (Q K^T, P V), the dQ kernel 3, the dK/dV kernel 4, as
``flops/flash_attention.py`` counts the causal triangle. Counted from ``L``,
``B'``, heads and head size alone, whatever calls and tiles implement it: a
plan that computes dead tiles, or one that makes two calls where one would do,
reads a lower share of peak for the same work, never a higher one. What the
kernels execute beyond the pairs (the masked parts of the tiles the mask's
edges cut: 48 of the 288 live 512 x 512 tiles at ``L`` 8,192 are such) is not
counted either.
"""

from __future__ import annotations


def pairs(L: int, Bp: int) -> int:
    """(query, key) pairs a head the mask admits over the ``2 L`` rows."""
    L, Bp = int(L), int(Bp)
    nb = L // Bp
    if nb * Bp != L:
        raise ValueError(f"block length {Bp} does not divide {L}")
    return Bp * Bp * (nb * (nb + 1) // 2 + nb * (nb - 1) // 2 + nb)


def _ops(B: int, Hq: int, S: int, D: int, Bp: int) -> float:
    return 2.0 * B * Hq * pairs(S // 2, Bp) * D


def fwd(B: int, Hq: int, S: int, D: int, Bp: int) -> float:
    return 2.0 * _ops(B, Hq, S, D, Bp)


def bwd_dq(B: int, Hq: int, S: int, D: int, Bp: int) -> float:
    return 3.0 * _ops(B, Hq, S, D, Bp)


def bwd_dkv(B: int, Hq: int, S: int, D: int, Bp: int) -> float:
    return 4.0 * _ops(B, Hq, S, D, Bp)


BY_KERNEL = {"flash_fwd": fwd, "flash_bwd_dq": bwd_dq, "flash_bwd_dkv": bwd_dkv}
