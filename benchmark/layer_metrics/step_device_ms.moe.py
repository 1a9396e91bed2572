"""Device time a step in the routed experts: the router (``moe_router``), the
sort, gather and scatter around the kernels (``moe_experts``) and the grouped
matmul kernels themselves (``gmm``, ``tgmm``). The shared expert is ``ffn``."""

from benchmark.trace_scopes import step_ms


def read(sources):
    return step_ms(sources, ("moe_router", "moe_experts", "gmm", "tgmm"))
