"""What one selective scan has to do, from the call's shapes ``[B, S, d_inner]``
and ``N``, whatever implements it.

Bytes, each array crossing HBM once at ``itemsize`` bytes an element (4: the
kernels of ``ops/selective_scan.py`` take and give float32):

- forward: ``c`` and ``Delta`` in, ``y`` out (``B S d_inner`` each), ``B_t`` and
  ``C_t`` in (``B S N`` each), ``A`` (``d_inner N``) and ``D`` in;
- backward: those five and ``dy`` in, ``dc`` and ``dDelta`` out (``B S d_inner``
  each), ``dB`` and ``dC`` out (``B S N``), ``dA`` and ``dD`` out.

The saved chunk-boundary states and the per-block partial sums of ``dB`` and
``dC`` are an implementation's, not the scan's, and are not counted: a share
of the HBM roof computed from these reads low for a kernel that moves more,
never high. A scan has no matmul: the only published roof it has is the
chip's HBM bandwidth, and the kernels are bound by the VPU and the
exponentials long before it, so the share is expected to be small.

``state_updates``: ``B S d_inner N`` a pass (one ``exp``, about eight
multiply-adds each in the forward), for reasoning about the VPU.
"""

from __future__ import annotations


def fwd_bytes(B: int, S: int, Di: int, N: int, itemsize: int = 4) -> float:
    return float(itemsize) * (3 * B * S * Di + 2 * B * S * N + Di * N + Di)


def bwd_bytes(B: int, S: int, Di: int, N: int, itemsize: int = 4) -> float:
    return float(itemsize) * (5 * B * S * Di + 4 * B * S * N + 2 * Di * N + 2 * Di)


def state_updates(B: int, S: int, Di: int, N: int) -> float:
    return float(B) * S * Di * N


BYTES_BY_KERNEL = {"ssm_scan_fwd": fwd_bytes, "ssm_scan_bwd": bwd_bytes}
