"""What one call of the Kimi Delta Attention core has to do, from the call's
shapes ``[B, S, H, d]``, whatever implements it (``ops/kda.py`` is one
implementation; the equations are in ``reference/kimi_linear.py``).

**Matmul operations**, a token a head, of the chunked gated delta rule at the
chunk ``COUNT_CHUNK`` = 64 (ISSUE 51's count, fixed here so that the share does
not move with the chunk an implementation picks), 2 a multiply-add, whole
``c x c`` squares: the two products under the decays ``A`` (k k^T) and ``P`` (q
k^T), ``T (beta K exp(G))``, ``T (beta V)`` and ``P V'`` at ``2 c d`` each; ``W
S``, ``(Q exp(G)) S`` and the state's update ``(K exp(G_c - G))^T V'`` at ``2
d^2`` each; the solve at ``c^2``: ``10 c d + 6 d^2 + c^2`` = 184,320 at 64 and
128. The backward transposes every product: twice that. Nothing recomputed is
counted (a backward that rebuilds ``A``, ``P``, ``T`` and ``V'`` does more), nor
the elementwise work (one ``exp`` a channel a pair inside a sub-block is VPU and
EUP work, and is where a first kernel's time goes).

**Bytes**, each array crossing HBM once: ``q``, ``k``, ``v``, ``o`` and their
cotangents at 2 bytes an element (bfloat16 matmul operands), ``g`` and ``dg`` at
4 (the configuration states the decay in float32, so no implementation may hand
it narrower), ``beta`` and ``dbeta`` at 4 a token a head. The states saved at
chunk starts are an implementation's and are not counted: a share computed from
these reads low for a kernel that moves more, never high.

At ``kimi-linear-48b-a3b-ep16.train-seq8k``'s call (2 x 8,192 x 32 x 128) the
forward is 96.6 GFLOP (0.49 ms at 197 TFLOP/s) and 807 MB (0.99 ms at 819
GB/s), the backward 193 GFLOP (0.98 ms) and 1,481 MB (1.81 ms): **the HBM roof
binds both**.
"""

from __future__ import annotations

COUNT_CHUNK = 64


def fwd_flops(B: int, S: int, H: int, d: int, chunk: int = COUNT_CHUNK) -> float:
    return float(B) * S * H * (10 * chunk * d + 6 * d * d + chunk * chunk)


def bwd_flops(B: int, S: int, H: int, d: int, chunk: int = COUNT_CHUNK) -> float:
    return 2.0 * fwd_flops(B, S, H, d, chunk)


def fwd_bytes(B: int, S: int, H: int, d: int) -> float:
    return float(B) * S * H * (4 * d * 2 + d * 4 + 4)


def bwd_bytes(B: int, S: int, H: int, d: int) -> float:
    return float(B) * S * H * (7 * d * 2 + 2 * d * 4 + 2 * 4)


def roof_seconds(flops: float, nbytes: float, peaks) -> float:
    """The least time the chip could take: the larger of operations over the
    bf16 peak and bytes over the HBM bandwidth (``benchmark/peaks.py``)."""
    return max(flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])


BY_KERNEL = {"kda_fwd": (fwd_flops, fwd_bytes), "kda_bwd": (bwd_flops, bwd_bytes)}
