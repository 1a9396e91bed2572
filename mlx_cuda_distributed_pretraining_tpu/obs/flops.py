"""Model FLOPs accounting: FLOPs/token, chip peak detection, MFU, goodput.

MFU follows the PaLM appendix-B convention: the model needs
``6*N`` FLOPs per token for the matmuls (fwd + bwd) plus the attention
term ``6 * num_layers * seq * d_attn`` for the [S, S] score/value
matmuls, and utilization is that analytic cost divided by what the chips
could theoretically sustain:

    MFU = flops_per_token * tokens_per_second / (peak_flops_per_chip * n_chips)

Peak FLOPs are looked up from ``jax.devices()[0].device_kind`` for the
TPU generations the installed libtpu drives (bf16 dense peak, matching how
the matmuls actually run); ``GRAFT_PEAK_FLOPS`` forces a value. On CPU the
lookup returns None and callers report ``mfu=unknown``. An accelerator that
is missing from the table is an error, not a default: a number computed
against no peak, or the wrong one, must not reach a log line.

Decode is bandwidth-bound, not FLOPs-bound: every generated token must
stream the (active) weight plane from HBM, so the decode roofline is
``HBM bytes/s / weight bytes per token``. :func:`weight_bytes_per_token`
models that byte cost per serving ``weight_dtype`` (fp / weight-only
int8 / packed int4 + per-channel scales) and
:func:`decode_roofline_tok_s` turns it into the tok/s ceiling of a
bandwidth-bound decode.

The goodput ledger answers "where did the wall clock go": every logging
window books seconds into named components (compile, data wait, H2D
wait, dispatch, checkpoint save, eval, restart-lost time fed in by the
supervisor) and the residual ``other_s`` absorbs whatever was not
attributed, so the components ALWAYS sum to window wall time.
"""

from __future__ import annotations

import os
from typing import Any, Dict, Optional

# bf16 dense peak FLOPs per chip, keyed by device_kind substring
# (checked in order — first match wins, so more specific kinds first).
# Source: Google Cloud TPU documentation, per-generation system pages
# (v5e: 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s).
_PEAK_BY_KIND = (
    ("v6e", 918e12), ("v6 lite", 918e12),
    ("v5p", 459e12),
    ("v5e", 197e12), ("v5 lite", 197e12), ("v5lite", 197e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
)

PEAK_FLOPS_ENV = "GRAFT_PEAK_FLOPS"

# HBM bandwidth (bytes/s) per chip, same keying/override convention as
# the FLOPs table. Numbers are vendor peak memory bandwidth.
_HBM_BW_BY_KIND = (
    ("v6e", 1640e9), ("v6 lite", 1640e9),
    ("v5p", 2765e9),
    ("v5e", 819e9), ("v5 lite", 819e9), ("v5lite", 819e9),
    ("v4", 1228e9),
    ("v3", 900e9),
    ("v2", 700e9),
)

HBM_BW_ENV = "GRAFT_HBM_BW"


def flops_per_token(n_params: int, num_layers: int, seq_len: int,
                    d_attn: int) -> float:
    """Analytic train-step FLOPs per token: 6N matmul + attention term.

    ``d_attn`` is the total attention width ``num_heads * head_dim``.
    """
    return 6.0 * float(n_params) + 6.0 * float(num_layers) * float(seq_len) * float(d_attn)


def moe_active_params(n_params: int, num_layers: int, hidden_size: int,
                      intermediate_size: int, num_experts: int,
                      experts_per_tok: int) -> int:
    """Params a token actually multiplies against in an MoE model.

    ``n_params`` counts all E experts, but each token passes through the
    router plus only K of them (plus every shared weight), so ``6*N``
    over-counts MoE FLOPs by ~E/K. Subtract the (E-K) inactive experts'
    three SwiGLU matrices per layer; the router and all shared weights stay
    in. Matches the grouped dispatch exactly and the einsum impl's useful
    work (capacity-slot padding is overhead, not model FLOPs).
    """
    if num_experts <= 0 or experts_per_tok <= 0 or experts_per_tok >= num_experts:
        return int(n_params)
    per_expert = 3 * int(hidden_size) * int(intermediate_size)
    inactive = int(num_layers) * (int(num_experts) - int(experts_per_tok)) * per_expert
    return int(n_params) - inactive


def matmul_params(model_cfg: Any, n_params: int,
                  vocab_size: Optional[int] = None) -> int:
    """Params a token is multiplied by, from a ModelConfig (config.py) plus
    the exact param count (llama.num_params — analytic dim products would
    drift from tied-embedding / MoE variants). MoE configs
    (``moe.num_local_experts``) are costed on ACTIVE params — router + top-k
    experts + shared weights. With ``vocab_size`` given and untied
    embeddings (``misc.tie_word_embeddings: false``) the input table
    ``[vocab, hidden]`` is left out: it is a lookup, and counting it read
    ``mfu=`` 12.5% high on a 4-layer Mistral-7B cut. A tied table stays in:
    it is the output head's matmul."""
    moe = dict(getattr(model_cfg, "moe", None) or {})
    n_active = int(n_params)
    if int(moe.get("num_local_experts", 0) or 0) > 0:
        n_active = moe_active_params(
            n_params, int(model_cfg.num_layers), int(model_cfg.hidden_size),
            int(model_cfg.intermediate_size),
            int(moe.get("num_local_experts", 0) or 0),
            int(moe.get("num_experts_per_tok", 0) or 0),
        )
    misc = dict(getattr(model_cfg, "misc", None) or {})
    if vocab_size and not bool(misc.get("tie_word_embeddings", True)):
        n_active -= int(vocab_size) * int(model_cfg.hidden_size)
    return n_active


def model_flops_per_token(model_cfg: Any, n_params: int, seq_len: int,
                          vocab_size: Optional[int] = None) -> float:
    """FLOPs/token of a training step: ``6 * matmul_params`` (see there for
    what counts) plus the attention term, so ``mfu=`` on window lines
    reflects work the model requires."""
    d_attn = int(model_cfg.num_heads) * int(model_cfg.head_dim)
    return flops_per_token(matmul_params(model_cfg, n_params, vocab_size),
                           int(model_cfg.num_layers), int(seq_len), d_attn)


def _per_chip(table, env_name: str, what: str,
              device_kind: Optional[str]) -> Optional[float]:
    env = os.environ.get(env_name)
    if env:
        try:
            return float(env)
        except ValueError:
            pass
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    kind = str(device_kind).lower()
    if kind == "cpu":
        return None
    for needle, value in table:
        if needle in kind:
            return value
    raise ValueError(
        f"no {what} listed for device_kind {device_kind!r}: add it to the "
        f"table in obs/flops.py with its source (or set {env_name})")


def peak_flops_per_chip(device_kind: Optional[str] = None) -> Optional[float]:
    """bf16 peak FLOPs for one chip; None on CPU; an accelerator missing
    from the table raises.

    ``GRAFT_PEAK_FLOPS`` (float, FLOPs) overrides the lookup.
    """
    return _per_chip(_PEAK_BY_KIND, PEAK_FLOPS_ENV, "peak FLOP/s", device_kind)


def hbm_bw_per_chip(device_kind: Optional[str] = None) -> Optional[float]:
    """Peak HBM bytes/s for one chip; None on CPU; an accelerator missing
    from the table raises.

    ``GRAFT_HBM_BW`` (float, bytes/s) overrides the lookup, mirroring
    ``GRAFT_PEAK_FLOPS``.
    """
    return _per_chip(_HBM_BW_BY_KIND, HBM_BW_ENV, "HBM bandwidth", device_kind)


def quantizable_weight_counts(model_cfg: Any) -> tuple:
    """(matmul params, per-channel scale count) a decoded token streams.

    Counts exactly the leaves the weight-only quantizer touches
    (models/quantize.QUANT_LEAF_RE): the four attention projections and
    the SwiGLU matrices — for MoE, the top-k ACTIVE expert banks only,
    since decode gathers K experts per token; the router stays fp and is
    counted with the remainder. Scales are one fp32 per output channel
    per matrix.
    """
    h = int(model_cfg.hidden_size)
    inter = int(model_cfg.intermediate_size)
    L = int(model_cfg.num_layers)
    dq = int(model_cfg.num_heads) * int(model_cfg.head_dim)
    dkv = int(model_cfg.num_kv_heads) * int(model_cfg.head_dim)
    attn_q = h * dq + 2 * h * dkv + dq * h
    attn_s = dq + 2 * dkv + h
    moe = dict(getattr(model_cfg, "moe", None) or {})
    k = int(moe.get("num_experts_per_tok", 0) or 0)
    if int(moe.get("num_local_experts", 0) or 0) > 0 and k > 0:
        ffn_q = k * 3 * h * inter
        ffn_s = k * (2 * inter + h)
    else:
        ffn_q = 3 * h * inter
        ffn_s = 2 * inter + h
    return L * (attn_q + ffn_q), L * (attn_s + ffn_s)


def weight_bytes_per_token(model_cfg: Any, n_params: int,
                           weight_dtype: str = "fp",
                           vocab_size: Optional[int] = None,
                           fp_bytes: int = 4) -> float:
    """Bytes of weights one decoded token streams from HBM.

    The quantizable matmul plane costs 1 byte/param at int8 and 0.5 at
    packed int4, plus fp32 per-channel scales; everything else (norms,
    router, output head) streams at ``fp_bytes``. The input embedding is
    a single-row gather, not a stream — pass ``vocab_size`` to exclude
    one [vocab, hidden] table from the fp remainder (tied heads still
    pay it once: the logits matmul reads the full table). MoE models are
    costed on ACTIVE params, matching :func:`model_flops_per_token`.
    """
    wd = str(weight_dtype or "fp").lower()
    qbytes = {"fp": float(fp_bytes), "int8": 1.0, "int4": 0.5}.get(wd)
    if qbytes is None:
        raise ValueError(f"unknown weight_dtype {weight_dtype!r}")
    moe = dict(getattr(model_cfg, "moe", None) or {})
    n_active = int(n_params)
    if int(moe.get("num_local_experts", 0) or 0) > 0:
        n_active = moe_active_params(
            n_params, int(model_cfg.num_layers), int(model_cfg.hidden_size),
            int(model_cfg.intermediate_size),
            int(moe.get("num_local_experts", 0) or 0),
            int(moe.get("num_experts_per_tok", 0) or 0))
    n_quant, n_scales = quantizable_weight_counts(model_cfg)
    rest = max(0, n_active - n_quant)
    if vocab_size:
        rest = max(0, rest - int(vocab_size) * int(model_cfg.hidden_size))
    out = n_quant * qbytes + rest * float(fp_bytes)
    if wd != "fp":
        out += 4.0 * n_scales
    return out


def decode_roofline_tok_s(bytes_per_token: float,
                          bw_per_chip: Optional[float],
                          n_chips: int = 1) -> Optional[float]:
    """Bandwidth-roofline decode ceiling: HBM bytes/s over bytes/token.

    None when bandwidth is undetectable — same convention as
    :func:`mfu`. Sharded serving divides the weight stream across chips,
    hence the ``n_chips`` multiplier.
    """
    if bw_per_chip is None or bw_per_chip <= 0 or bytes_per_token <= 0:
        return None
    return float(bw_per_chip) * max(1, int(n_chips)) / float(bytes_per_token)


def mfu(tok_s: float, flops_per_tok: float,
        peak_per_chip: Optional[float], n_chips: int) -> Optional[float]:
    """Model FLOPs utilization in [0, 1]-ish, or None when peak unknown.

    Useful-FLOPs-only by construction, including under pipeline
    parallelism: the numerator is analytic model FLOPs times REAL tokens
    per second, so warmup/drain bubble ticks (and, with
    ``pipeline_compute_skip: false``, slab applications on masked garbage)
    only ever show up as a lower ``tok_s`` — never as credited work. The
    schedule overhead itself is reported separately via
    :func:`pipeline_bubble_frac` / :func:`pipeline_executed_flops_ratio`.
    """
    if peak_per_chip is None or peak_per_chip <= 0 or n_chips <= 0:
        return None
    return float(flops_per_tok) * float(tok_s) / (peak_per_chip * n_chips)


def pipeline_bubble_frac(pp: int, microbatches: int,
                         interleave: int = 1) -> float:
    """Fraction of schedule ticks each stage spends idle (the bubble).

    The GPipe schedule runs ``T = V*M + P - 1`` ticks per step (P stages,
    M microbatches, V interleaved virtual stages) of which each stage
    works exactly ``V*M`` — so ``(P-1) / (V*M + P-1)`` of its tick-time is
    bubble. Interleave shrinks the bubble because each tick applies only
    ``1/V`` of the stage's layers: the same P-1 warmup/drain ticks cost
    ``(P-1)/V`` full-slab-times. With compute-skip the bubble is idle
    time; without it, the same fraction is garbage compute.
    """
    P = max(1, int(pp))
    M = max(1, int(microbatches))
    V = max(1, int(interleave))
    return float(P - 1) / float(V * M + P - 1)


def pipeline_executed_flops_ratio(pp: int, microbatches: int,
                                  interleave: int = 1,
                                  compute_skip: bool = True) -> float:
    """Hardware slab FLOPs executed per useful slab FLOP.

    1.0 with compute-skip (non-working ticks run no slab compute). With
    ``pipeline_compute_skip: false`` every stage applies its chunk on all
    ``V*M + P - 1`` ticks but only ``V*M`` carry real microbatches, so the
    chips burn ``(V*M + P - 1) / (V*M)`` times the useful FLOPs — strictly
    worse than an idle bubble. MFU never credits the excess (see
    :func:`mfu`); this ratio is the honest "what did the hardware do"
    multiplier for capacity planning.
    """
    if compute_skip:
        return 1.0
    P = max(1, int(pp))
    M = max(1, int(microbatches))
    V = max(1, int(interleave))
    return float(V * M + P - 1) / float(V * M)


# Goodput components in reporting order. ``other_s`` is the residual and
# is appended by close_window — never booked directly.
GOODPUT_COMPONENTS = (
    "compile_s", "data_wait_s", "h2d_wait_s", "dispatch_s",
    "ckpt_save_s", "eval_s", "restart_lost_s",
)


class GoodputLedger:
    """Window + cumulative attribution of wall-clock seconds.

    ``add(component, seconds)`` books time into the current window;
    ``close_window(elapsed_s)`` returns the window breakdown with the
    residual ``other_s = max(0, elapsed - sum(booked))`` appended, folds
    it into the cumulative totals, and resets the window. Components
    therefore sum to window wall time by construction (up to clamping
    when booked time exceeds elapsed — overlapping attributions).
    """

    def __init__(self):
        self._window: Dict[str, float] = {c: 0.0 for c in GOODPUT_COMPONENTS}
        self._total: Dict[str, float] = {c: 0.0 for c in GOODPUT_COMPONENTS}
        self._total["other_s"] = 0.0

    def add(self, component: str, seconds: float) -> None:
        if component not in self._window:
            raise KeyError(f"unknown goodput component: {component!r} "
                           f"(one of {GOODPUT_COMPONENTS})")
        self._window[component] += max(0.0, float(seconds))

    def window_view(self) -> Dict[str, float]:
        return dict(self._window)

    def close_window(self, elapsed_s: float) -> Dict[str, float]:
        booked = sum(self._window.values())
        out = {c: v for c, v in self._window.items()}
        out["other_s"] = max(0.0, float(elapsed_s) - booked)
        for c, v in out.items():
            self._total[c] += v
        self._window = {c: 0.0 for c in GOODPUT_COMPONENTS}
        return out

    def totals(self) -> Dict[str, float]:
        return dict(self._total)
