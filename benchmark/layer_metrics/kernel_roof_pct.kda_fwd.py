"""Share of its roof of ``kda_fwd`` (``ops/kda.py``), forward and recomputed
calls: the larger of the chunked delta rule's required matmul operations over
the bf16 peak and its required bytes over the HBM bandwidth (``flops/kda_chunk.py``;
the bandwidth binds at the cell's shapes), over the kernel's self time, by
``_kda.py``."""

from _kda import kernel_roof_pct


def read(sources):
    return kernel_roof_pct(sources, "kda_fwd")
