"""Required FLOPs per token (benchmark/flops: no input table, causal
attention, no recomputation) times tokens/s/chip over the chip's bf16 peak."""


def read(sources):
    if not sources.get("peaks") or "flops_per_token" not in sources:
        return None
    return 100.0 * sources["flops_per_token"] * sources["tokens_per_s_per_chip"] \
        / sources["peaks"]["bf16_flops"]
