"""Share of its roof of the delta rule's backward (``kda_bwd``, or the summed
self times of several ``kda_bwd_<part>``; ``ops/kda.py``): the larger of twice
the forward's required matmul operations over the bf16 peak and the backward's
required bytes over the HBM bandwidth (``flops/kda_chunk.py``; the bandwidth
binds at the cell's shapes), over the kernels' self time, by ``_kda.py``."""

from _kda import kernel_roof_pct


def read(sources):
    return kernel_roof_pct(sources, "kda_bwd")
