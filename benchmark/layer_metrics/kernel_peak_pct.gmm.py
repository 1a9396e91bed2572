"""Share of the chip's bf16 peak the grouped-matmul kernels reach: calls of
``gmm`` (forward, recomputed, and dX) and ``tgmm`` (dW) in the whole steps
times the operations each executes, over their self time. Every call
multiplies all ``M`` rows of the expert layer's row buffer (its tiles past
the last group too, against zero rows) by a ``K x N`` expert matrix: ``2 M K
N``. ``M`` is read off a ``gmm`` call's output ``[M, N]`` and ``K N`` off a
``tgmm`` call's ``[E, K, N]``; every matrix of a SwiGLU expert has the same
``K N`` (hidden x expert width). Executed, not required: the buffer is larger
than the rows the experts were sent."""

from benchmark.trace_scopes import of_run


def read(sources):
    red, peaks = of_run(sources), sources.get("peaks")
    if red is None or not peaks:
        return None
    gmm, tgmm = red["kernels"].get("gmm"), red["kernels"].get("tgmm")
    if not gmm or not tgmm or not gmm["dims"] or not tgmm["dims"] \
            or len(gmm["dims"]) != 2 or len(tgmm["dims"]) != 3:
        return None
    seconds = gmm["seconds"] + tgmm["seconds"]
    if seconds <= 0:
        return None
    flops = (gmm["calls"] + tgmm["calls"]) * 2.0 * gmm["dims"][0] * tgmm["dims"][1] * tgmm["dims"][2]
    return 100.0 * flops / seconds / peaks["bf16_flops"]
