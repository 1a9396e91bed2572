"""Mixture-of-Experts feed-forward block (real, TPU-first).

The reference *declares* MoE fields (``num_local_experts`` /
``num_experts_per_tok``, reference: models/llama.py:40-41 and config plumbing
core/training.py:1055-1056) but never builds an MoE layer. Here they drive a
real block with two interchangeable dispatch implementations
(``moe.impl`` in the model config, ``LlamaArgs.moe_impl``):

- ``grouped`` (default) — MegaBlocks-style **dropless** routing: fp32 router
  → top-k → stable argsort by expert id → gather into a per-expert
  block-aligned buffer → grouped GEMM SwiGLU (ops/grouped_matmul.py) →
  gather of each token's rows back and a gate-weighted sum. Every shape is
  static (sort + gather, no data-dependent shapes) and **no token is ever
  dropped** — there is no expert capacity. On one device both directions
  are gathers in the backward pass too (:func:`dispatch_rows`,
  :func:`combine_rows`: custom backwards over one :class:`DispatchPlan`),
  so the layer's gradient holds no scatter of activation rows. On ``ep``
  meshes (``_grouped_moe_ep``, which still scatters and can take the same
  two functions) the sorted dispatch drops below GSPMD
  via ``jax.shard_map``: each shard routes its local tokens,
  exchanges rows with the owning expert shard through a pair of
  ``all_to_all`` collectives with static per-destination send slots, and
  scatter-adds the returned rows (mirroring how
  ``ops/fused_ce.fused_cross_entropy_sp`` handles sp). Send capacity
  defaults to worst-case (``moe_ep_capacity_factor: 0``) so the exchange
  is dropless too; a positive factor trades all-to-all volume for
  (counted) overflow drops.
- ``einsum`` — the GShard/Switch dispatch/combine-tensor formulation kept
  as the parity oracle: top-k gating, per-group expert capacity ``C``,
  one-hot dispatch ``[B, S, E, C]``; tokens beyond capacity are dropped to
  the residual path. Expert parallelism happens implicitly under GSPMD via
  the ``ep``-sharded ``[E, ...]`` weight stacking.

Router math runs in fp32 regardless of compute dtype. The load-balancing
aux loss (Switch Transformer style) and optional router z-loss are computed
over **real tokens only** — ``moe_group_size`` padding rows are excluded —
and returned pre-scaled.

Routing observability rides a trace-time tap (:func:`routing_stats_tap`):
when a tap is active, ``transformer_block`` converts each layer's recorded
expert-load / dropped-token stats into return values (so they survive
``jax.checkpoint`` and ``lax.scan`` boundaries) and ``loss_fn`` surfaces
them to the train step.
"""

from __future__ import annotations

import collections
import contextlib
import functools
import threading
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..ops import grouped_matmul as gm
from ..ops import token_sum as ts

Params = Dict[str, Any]


def init_moe_params(keys, args, dtype=jnp.float32) -> Params:
    """Stacked expert weights [E, ...] + router [D, E].

    ``keys`` is an iterator of PRNG keys (4 consumed).
    """
    D, I, E = args.hidden_size, args.intermediate_size, args.num_local_experts
    std = 0.02
    res_std = std / (2 * args.num_layers) ** 0.5

    def dense(key, shape, s):
        return (jax.random.normal(key, shape, jnp.float32) * s).astype(dtype)

    return {
        "router": {"weight": dense(next(keys), (D, E), std)},
        "experts": {
            "w_gate": {"weight": dense(next(keys), (E, D, I), std)},
            "w_up": {"weight": dense(next(keys), (E, D, I), std)},
            "w_down": {"weight": dense(next(keys), (E, I, D), res_std)},
        },
    }


# -- routing-stats tap -------------------------------------------------------
# Stats are traced values; a side list only works when producer and consumer
# sit in the SAME trace. transformer_block therefore re-emits tap entries as
# return values across jax.checkpoint / lax.scan boundaries, and loss_fn
# returns the merged stats through value_and_grad's aux.
_TAPS: List[list] = []

STAT_KEYS = ("moe_load", "moe_dropped", "moe_chunks_whole")


@contextlib.contextmanager
def routing_stats_tap():
    """Collect per-layer routing stats dicts recorded while tracing."""
    tap: list = []
    _TAPS.append(tap)
    try:
        yield tap
    finally:
        _TAPS.pop()


def stats_tap_active() -> bool:
    return bool(_TAPS)


def record_stats(stats: Dict[str, jnp.ndarray]) -> None:
    if _TAPS:
        _TAPS[-1].append(stats)


def zero_stats(num_experts: int) -> Dict[str, jnp.ndarray]:
    """One layer's statistics at zero: selections an expert, selections dropped,
    and trips of a held share's chunk loop that took the whole dropless buffer
    because some chunk's rows did not fit the small one (:func:`held_share_ffn`:
    all of the whole loop's trips or none)."""
    return {
        "moe_load": jnp.zeros((num_experts,), jnp.float32),
        "moe_dropped": jnp.zeros((), jnp.float32),
        "moe_chunks_whole": jnp.zeros((), jnp.float32),
    }


def merge_stats(entries, num_experts: int) -> Dict[str, jnp.ndarray]:
    """Sum a list of stats dicts (layers) into one."""
    total = zero_stats(num_experts)
    for e in entries:
        total = {k: total[k] + e[k] for k in total}
    return total


def expert_capacity(seq_len: int, num_experts: int, k: int, capacity_factor: float) -> int:
    """Per-sequence slots each expert can accept (static). Einsum impl only —
    the grouped impl is dropless and has no capacity."""
    c = int(capacity_factor * k * seq_len / num_experts + 0.5)
    return max(1, min(c, seq_len * k))


def _dispatch_combine(
    probs: jnp.ndarray, k: int, capacity: int
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Build dispatch/combine tensors from router probabilities.

    probs [B, S, E] fp32 → dispatch [B, S, E, C] in {0,1},
    combine [B, S, E, C] carrying renormalized top-k gate weights.
    Tokens beyond an expert's capacity are dropped (their combine weight is
    zero, so the residual path carries them — standard Switch behavior).
    """
    B, S, E = probs.shape
    gate_w, gate_idx = jax.lax.top_k(probs, k)  # [B, S, K]
    gate_w = gate_w / jnp.maximum(gate_w.sum(-1, keepdims=True), 1e-9)

    # Slot-flatten [S, K] -> S*K in token-major order so earlier tokens win
    # capacity; one-hot over experts per selection.
    oh = jax.nn.one_hot(gate_idx, E, dtype=probs.dtype)  # [B, S, K, E]
    ohf = oh.reshape(B, S * k, E)
    # Position of each selection within its expert's queue.
    pos = jnp.cumsum(ohf, axis=1) - ohf  # [B, S*K, E]
    pos_in_expert = (pos * ohf).sum(-1)  # [B, S*K]
    keep = ((pos_in_expert < capacity) & (ohf.sum(-1) > 0)).astype(probs.dtype)
    pos_oh = jax.nn.one_hot(pos_in_expert.astype(jnp.int32), capacity, dtype=probs.dtype)
    # [B, S*K, E, C]
    dispatch_f = ohf[..., None] * pos_oh[:, :, None, :] * keep[..., None, None]
    combine_f = dispatch_f * gate_w.reshape(B, S * k)[..., None, None]
    dispatch = dispatch_f.reshape(B, S, k, E, capacity).sum(2)
    combine = combine_f.reshape(B, S, k, E, capacity).sum(2)
    return dispatch, combine


def load_balancing_loss(probs: jnp.ndarray, gate_idx_top1: jnp.ndarray, num_experts: int) -> jnp.ndarray:
    """Switch Transformer aux loss: E * Σ_e f_e · P_e where f_e is the
    fraction of tokens whose top-1 choice is e and P_e the mean router prob."""
    f = jnp.mean(jax.nn.one_hot(gate_idx_top1, num_experts, dtype=jnp.float32), axis=(0, 1))
    p = jnp.mean(probs, axis=(0, 1))
    return num_experts * jnp.sum(f * p)


def router_z_loss(router_logits: jnp.ndarray) -> jnp.ndarray:
    """Mean squared logsumexp of router logits (stabilizes router scale)."""
    z = jax.nn.logsumexp(router_logits, axis=-1)
    return jnp.mean(z * z)


# -- quantized expert banks ---------------------------------------------------
def _expert_bank(experts: Params, name: str, dtype):
    """Resolve one expert bank leaf to ``(weights, scales | None)``.

    Quantized banks (models/quantize.py: ``weight_q`` int8 [E, in, out]
    or packed ``weight_q4`` [E, in//2, out], scales [E, out]) store at
    <= 1 byte/elem; the unpack/cast happens here at the dispatch site
    and the per-(expert, out-channel) scale is applied by the caller on
    the matmul RESULT — after the grouped GEMM / einsum, never as a
    scaled fp weight copy."""
    leaf = experts[name]
    if "weight_q4" in leaf:
        from .quantize import unpack_int4

        return unpack_int4(leaf["weight_q4"]).astype(dtype), leaf["weight_s"]
    if "weight_q" in leaf:
        return leaf["weight_q"].astype(dtype), leaf["weight_s"]
    return leaf["weight"], None


def _maybe_dequant_experts(p: Params) -> Params:
    """fp view of a (possibly quantized) expert subtree — only for paths
    that ship the banks through shard_map operands (expert-parallel),
    where threading separate scale operands isn't worth the wiring."""
    experts = p["experts"]
    if not any(("weight_q" in leaf or "weight_q4" in leaf)
               for leaf in experts.values() if isinstance(leaf, dict)):
        return p
    from .quantize import dequantize_leaf

    return {**p, "experts": {
        name: {"weight": dequantize_leaf(leaf)}
        for name, leaf in experts.items()}}


# -- einsum (GShard/Switch) implementation -----------------------------------
def _einsum_moe(
    p: Params, x: jnp.ndarray, probs: jnp.ndarray, args
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Dense dispatch/combine einsum pipeline → (out, dropped_selections).

    Tokens are routed in fixed-size groups of ``moe_group_size``
    (GShard-style) so capacity — and with it the [G, g*K, E, C] dispatch
    tensors — stays constant as sequence length grows: memory is O(S), not
    O(S²). Pad rows carry uniform router probs (softmax of a zero row),
    exactly as if zero-padded activations had been routed; their combine
    output is sliced off, though they can steal a little tail-group
    capacity, which is standard.
    """
    B, S, D = x.shape
    E, K = args.num_local_experts, args.num_experts_per_tok

    g = min(int(getattr(args, "moe_group_size", 256) or 256), S)
    S_pad = ((S + g - 1) // g) * g
    if S_pad != S:
        x_in = jnp.pad(x, ((0, 0), (0, S_pad - S), (0, 0)))
        probs_in = jnp.pad(probs, ((0, 0), (0, S_pad - S), (0, 0)),
                           constant_values=1.0 / E)
    else:
        x_in, probs_in = x, probs
    xg = x_in.reshape(B * (S_pad // g), g, D)
    probs_g = probs_in.reshape(B * (S_pad // g), g, E)
    C = expert_capacity(g, E, K, getattr(args, "moe_capacity_factor", 1.25))

    dispatch, combine = _dispatch_combine(probs_g, K, C)
    # Kept selections per token (0..K), real rows only → overflow drops.
    kept = dispatch.sum((2, 3)).reshape(B, S_pad)[:, :S]
    dropped = jax.lax.stop_gradient(K * B * S - kept.sum())
    dispatch = dispatch.astype(x.dtype)

    # [G,g,E,C] x [G,g,D] -> [E,G,C,D]: the all-to-all under ep sharding.
    expert_in = jnp.einsum("bsec,bsd->ebcd", dispatch, xg)
    wg_, sg = _expert_bank(p["experts"], "w_gate", expert_in.dtype)
    wu, su = _expert_bank(p["experts"], "w_up", expert_in.dtype)
    wd, sd = _expert_bank(p["experts"], "w_down", expert_in.dtype)

    def scaled(y, s):  # per-(expert, out-channel) dequant epilogue
        return y if s is None else y * s[:, None, None, :].astype(y.dtype)

    h = jax.nn.silu(scaled(jnp.einsum("ebcd,edi->ebci", expert_in, wg_), sg)) * scaled(
        jnp.einsum("ebcd,edi->ebci", expert_in, wu), su
    )
    expert_out = scaled(jnp.einsum("ebci,eid->ebcd", h, wd), sd)
    out = jnp.einsum("bsec,ebcd->bsd", combine.astype(x.dtype), expert_out)
    return out.reshape(B, S_pad, D)[:, :S], dropped


# -- grouped (sort-based dropless) implementation ----------------------------
# The dispatch into the expert buffer and the combine out of it are gathers in
# both directions: each is a ``jax.custom_vjp`` whose backward gathers the
# other way through the same plan, so neither the forward nor the backward
# holds a scatter of activation rows (XLA's scatters on the TPU are serial in
# their updates). Counted while tracing, so this counts traces, not calls of
# the compiled step: what a jitted program runs is what its one trace counted.
# ``chunk_loop_tail``: chunk loops of a layer that holds a share traced with the
# layer's token-local tail inside (:func:`held_share_ffn`); ``chunk_two_sizes``:
# chunk functions traced at two buffer sizes, each of which adds one dispatch
# and one combine to the tally (a layer's chunk function is traced once a size).
# ``chunk_trips_small``, ``chunk_trips_whole``: the trips a traced layer's tokens
# take through its held experts, summed over the layers traced, in the loop at
# the small buffer (0 for a layer of one size) and in the loop at the whole one
# (:func:`held_chunks`: each loop cuts by what its own buffer has rows for).
# ``token_sum_kernel``, ``token_sum_xla``: token-side passes (a combine, a dispatch's
# backward, a combine's ``dgate_w``) traced in the kernels' form and in XLA's
# (:func:`_token_tile`).
_PLAN_KEYS = ("dispatch_gather", "combine_gather", "chunk_loop_tail", "chunk_two_sizes",
              "chunk_trips_small", "chunk_trips_whole", "token_sum_kernel", "token_sum_xla")
_plan_counts: Dict[str, int] = collections.Counter()
_plan_counts_lock = threading.Lock()


def _count_plan(key: str, n: int = 1) -> None:
    with _plan_counts_lock:
        _plan_counts[key] += n


def plan_counts() -> Dict[str, int]:
    """Dispatches into an expert buffer and combines out of one traced so far
    in this process, both in the gather form (:func:`dispatch_rows`,
    :func:`combine_rows`), chunk loops traced with their layer's tail
    inside (:func:`held_share_ffn`: 0 in a step whose layers hold a share in
    chunks is an executable that runs the held experts' forward a third time),
    chunk functions traced at a small buffer and the whole one (0 in a step
    whose layers hold less than a quarter of the experts is an executable
    whose every chunk multiplies a row for every selection), and the trips of
    those layers' loops at either buffer (``chunk_trips_small`` equal to
    ``chunk_trips_whole`` in a step with ``chunk_two_sizes`` is an executable
    whose small loop still cuts by a chunk's selections: twice the trips its
    buffer needs where a layer holds an eighth of the experts), and the
    token-side passes by form: a combine traces one, its backward another for
    ``dgate_w`` and a differentiated dispatch a third (``token_sum_kernel`` 0
    in a step on the chip is an executable that still builds ``[T, K, D]`` for
    the sums; off the chip they are all ``token_sum_xla``, where ``dgate_w`` is
    taken on the buffer's side)."""
    with _plan_counts_lock:
        return {key: _plan_counts[key] for key in _PLAN_KEYS}


class DispatchPlan(NamedTuple):
    """Both directions of one dispatch, as indices: no array here is made by
    a scatter. Rows are the buffer's (``T_buf``), selections are ``[T, K]``."""
    group_sizes: jnp.ndarray  # [E] rows of each expert's group, block_t-aligned
    row_sel: jnp.ndarray      # [T_buf] flat selection t * K + k of the row (0 where dead)
    row_live: jnp.ndarray     # [T_buf] bool: the row holds a selection
    sel_row: jnp.ndarray      # [T, K] buffer row of the selection (0 where not held)
    sel_held: jnp.ndarray     # [T, K] bool: the selection's expert is held here

    @property
    def row_tok(self) -> jnp.ndarray:
        """[T_buf] token whose row this is (0 where dead)."""
        return self.row_sel // self.sel_row.shape[-1]


def buffer_rows(selections: int, num_experts: int, block_t: int) -> int:
    """Rows of the dropless buffer of ``selections`` selections: one for every
    selection, since any of them may be a held one, and every expert's group
    rounded up to a full tile."""
    return gm.round_up(selections + num_experts * (block_t - 1), block_t)


def held_rows(gate_idx: jnp.ndarray, num_experts: int, block_t: int, first: int = 0) -> jnp.ndarray:
    """Rows (traced int32, one number for ``gate_idx [T, K]`` and one a chunk for
    ``[n, T, K]``) the groups of the experts ``first ..`` take in a buffer, each
    rounded up to a tile: what :func:`dispatch_plan` fills."""
    local = gate_idx.reshape(gate_idx.shape[:-2] + (-1,)).astype(jnp.int32) - first
    counts = jnp.sum(local[..., None] == jnp.arange(num_experts, dtype=jnp.int32), axis=-2,
                     dtype=jnp.int32)
    return jnp.sum(gm.round_up(counts, block_t), axis=-1)


def dispatch_plan(gate_idx: jnp.ndarray, num_experts: int, block_t: int,
                  first: int = 0, rows: Optional[int] = None) -> DispatchPlan:
    """Where every selection's row lies in the ``block_t``-aligned buffer of
    the ``num_experts`` experts ``first ..``, and which selection every row
    holds. Token-major within an expert, as a stable sort by expert id gives.
    The buffer has :func:`buffer_rows` rows, or ``rows`` where the caller has
    seen that :func:`held_rows` of these selections is no more.

    A selection's rank inside its group is a running count over the one-hot of
    its expert id, so its row comes selection-major without inverting the
    sort; a row finds its group by comparing its number with the groups'
    bounds, and its selection in the sorted order at the group's start plus
    its rank. Selections of experts held elsewhere have an all-zero one-hot."""
    T, K = gate_idx.shape
    TK = T * K
    T_buf = buffer_rows(TK, num_experts, block_t) if rows is None else rows
    local = gate_idx.reshape(TK).astype(jnp.int32) - first
    held = (local >= 0) & (local < num_experts)
    onehot = (local[:, None] == jnp.arange(num_experts, dtype=jnp.int32)).astype(jnp.int32)
    running = jnp.cumsum(onehot, axis=0)                       # [TK, E]
    counts = running[-1]
    padded = ((counts + block_t - 1) // block_t) * block_t
    p_off = jnp.cumsum(padded) - padded                        # group starts in the buffer
    raw_off = jnp.cumsum(counts) - counts                      # group starts in sorted order
    sel_row = jnp.sum(onehot * (p_off + running - 1), axis=-1)  # 0 where not held

    # Selections of experts held elsewhere sort past every real group.
    order = jnp.argsort(jnp.where(held, local, num_experts), stable=True).astype(jnp.int32)
    row = jnp.arange(T_buf, dtype=jnp.int32)[:, None]
    in_group = (row >= p_off) & (row < p_off + counts)         # [T_buf, E], one-hot or zero
    row_live = in_group.any(-1)
    sorted_pos = jnp.sum(jnp.where(in_group, row + (raw_off - p_off), 0), axis=-1)
    row_sel = jnp.where(row_live, order.at[sorted_pos].get(mode="promise_in_bounds"), 0)
    return DispatchPlan(padded, row_sel, row_live, sel_row.reshape(T, K), held.reshape(T, K))


def _take_rows(a: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """``a[idx]`` along axis 0; a plan's indices are in bounds by construction."""
    return a.at[idx].get(mode="promise_in_bounds")


def _sum_held(rows: jnp.ndarray, scale: jnp.ndarray, dtype) -> jnp.ndarray:
    """``sum_k scale[t, k] * rows[t, k]`` accumulated in float32, cast once."""
    return jnp.sum(rows.astype(jnp.float32) * scale[..., None].astype(jnp.float32),
                   axis=1).astype(dtype)


def _token_tile(buf: jnp.ndarray, plan: "DispatchPlan") -> int:
    """Tokens a tile of the token-side kernels takes for this buffer and plan, tallied;
    0 for XLA's form (``ops/token_sum.py::token_sum_plan``: shapes and the
    grouped-matmul backend)."""
    T, K = plan.sel_row.shape
    bt = ts.token_sum_plan(T, K, buf.shape[1], buf.shape[0], buf.dtype)
    _count_plan("token_sum_kernel" if bt else "token_sum_xla")
    return bt


def _token_sum(buf: jnp.ndarray, plan: "DispatchPlan", scale: jnp.ndarray,
               exact_scale: bool = False) -> jnp.ndarray:
    """``out[t] = sum_k scale[t, k] * buf[plan.sel_row[t, k]]`` over the held
    selections → ``[T, D]`` in ``buf``'s dtype, accumulated in float32 and cast
    once: the kernel, which reads the rows a token holds and builds no
    ``[T, K, D]``, or XLA's gather of a row for every selection, scaled by zero
    where not held and summed in ascending ``k``."""
    bt = _token_tile(buf, plan)
    if bt:
        return ts.token_sum(buf, plan.sel_row, plan.sel_held, scale, plan.group_sizes, bt,
                            exact_scale=exact_scale)
    return _sum_held(_take_rows(buf, plan.sel_row), jnp.where(plan.sel_held, scale, 0), buf.dtype)


def _dispatch_rows(x_flat, plan):
    return jnp.where(plan.row_live[:, None], _take_rows(x_flat, plan.row_tok), 0)


def _dispatch_fwd(x_flat, plan):
    return _dispatch_rows(x_flat, plan), plan


def _dispatch_bwd(plan, dx_buf):
    with jax.named_scope("moe_experts"):
        dx = _token_sum(dx_buf, plan, plan.sel_held, exact_scale=True)
    return dx, None


_dispatch = jax.custom_vjp(_dispatch_rows)
_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


def _combine_rows(y_buf, gate_w, plan):
    return _token_sum(y_buf, plan, gate_w)


def _combine_fwd(y_buf, gate_w, plan):
    return _combine_rows(y_buf, gate_w, plan), (y_buf, gate_w, plan)


def _combine_bwd(residuals, dout):
    y_buf, gate_w, plan = residuals
    with jax.named_scope("moe_experts"):
        w_row = _take_rows(gate_w.reshape(-1), plan.row_sel).astype(jnp.float32)
        dout_row = _take_rows(dout, plan.row_tok).astype(jnp.float32)
        dy_buf = jnp.where(plan.row_live[:, None], dout_row * w_row[:, None], 0).astype(y_buf.dtype)
        # dgate_w: a selection's row dotted with its token's cotangent, in float32
        bt = _token_tile(y_buf, plan)
        if bt:
            dw = ts.token_dot(y_buf, plan.sel_row, plan.sel_held, dout, plan.group_sizes, bt)
        else:   # on the buffer's side, where the rows lie, then a gather of [T, K] scalars
            dw_row = jnp.sum(y_buf.astype(jnp.float32) * dout_row, axis=-1)
            dw = jnp.where(plan.sel_held, _take_rows(dw_row, plan.sel_row), 0)
        dw = dw.astype(gate_w.dtype)
    return dy_buf, dw, None


_combine = jax.custom_vjp(_combine_rows)
_combine.defvjp(_combine_fwd, _combine_bwd)


def dispatch_rows(x_flat: jnp.ndarray, plan: DispatchPlan) -> jnp.ndarray:
    """x_flat [T, D] → the expert buffer [T_buf, D]: every held selection's
    token row at its place, pad rows zero. One gather of ``T_buf`` rows; its
    backward one token-side sum (:func:`_token_sum`) of the buffer's cotangent."""
    _count_plan("dispatch_gather")
    return _dispatch(x_flat, plan)


def combine_rows(y_buf: jnp.ndarray, gate_w: jnp.ndarray, plan: DispatchPlan) -> jnp.ndarray:
    """``out[t] = sum_k gate_w[t, k] * y_buf[row of (t, k)]`` over the held
    selections → [T, D] in ``y_buf``'s dtype, the sum in float32: one
    token-side sum (:func:`_token_sum`). Its backward is one gather of ``T_buf``
    rows of the cotangent, scaled for ``dy_buf``, and for ``dgate_w`` each
    selection's row of ``y_buf`` dotted with its token's cotangent: the kernel
    ``token_dot``, or in XLA's form the dots taken a buffer row at a time and
    a gather of ``[T, K]`` of them."""
    _count_plan("combine_gather")
    return _combine(y_buf, gate_w, plan)


def grouped_ffn(
    experts: Params,
    x_flat: jnp.ndarray,
    gate_idx: jnp.ndarray,
    gate_w: jnp.ndarray,
    num_experts: int,
    block_t: int,
    precision=None,
    first: int = 0,
    rows: Optional[int] = None,
) -> jnp.ndarray:
    """Sorted dropless expert FFN over local tokens.

    x_flat [T, D], gate_idx [T, K] int32 (ids over the router's whole
    width), gate_w [T, K] → out [T, D]. ``experts`` holds the
    ``num_experts`` banks of ids ``first .. first + num_experts - 1``: a
    selection of any other id belongs to an expert some other chip holds,
    gets no row here and adds nothing (the caller's gate weights are
    normalised over all chosen, held or not). :func:`dispatch_plan` places
    the selections, stably sorted by expert id, in a per-expert
    ``block_t``-aligned buffer (static size, :func:`buffer_rows`: every
    selection could be a held one, and every expert's group rounds up to a
    full tile; or ``rows``, where the caller has seen that these selections'
    :func:`held_rows` is no more: the same rows in the same order in a shorter
    buffer); :func:`dispatch_rows` gathers the tokens' rows into it, the three
    expert matmuls run as grouped GEMMs, and :func:`combine_rows` gathers the
    gate-weighted rows back and sums them per token. No capacity, no drops,
    and no scatter, forward or backward.
    """
    plan = dispatch_plan(gate_idx, num_experts, block_t, first, rows)
    x_buf = dispatch_rows(x_flat, plan)
    T_buf = x_buf.shape[0]

    gs = plan.group_sizes
    wg_, sg = _expert_bank(experts, "w_gate", x_buf.dtype)
    wu, su = _expert_bank(experts, "w_up", x_buf.dtype)
    wd, sd = _expert_bank(experts, "w_down", x_buf.dtype)
    if sg is not None or su is not None or sd is not None:
        # Expert id of each buffer row (pad rows clamp to the last group —
        # they are all-zero, any scale is fine).
        row_e = jnp.minimum(
            jnp.searchsorted(jnp.cumsum(gs), jnp.arange(T_buf), side="right"),
            num_experts - 1)

        def scaled(y, s):  # per-row dequant epilogue
            return y if s is None else y * s[row_e].astype(y.dtype)
    else:
        def scaled(y, s):
            return y

    h = jax.nn.silu(
        scaled(gm.gmm(x_buf, wg_, gs, block_t=block_t, precision=precision), sg)
    ) * scaled(gm.gmm(x_buf, wu, gs, block_t=block_t, precision=precision), su)
    y_buf = scaled(gm.gmm(h, wd, gs, block_t=block_t, precision=precision), sd)

    return combine_rows(y_buf, gate_w, plan).astype(x_flat.dtype)


def sigmoid_route(x: jnp.ndarray, router: Params, k: int, route_scale: float
                  ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Sigmoid scoring with a selection bias → ``(gate_idx [..., K], gate_w
    [..., K], scores [..., E])``, float32. The top-k is taken of ``scores +
    bias``; the weights are the chosen *scores*, normalised over the chosen
    and times ``route_scale``. ``router["bias"]`` is a buffer (it moves the
    choice, never the weights, and so gets no gradient)."""
    scores = jax.nn.sigmoid(jnp.einsum(
        "...d,de->...e", x, router["weight"].astype(x.dtype),
        preferred_element_type=jnp.float32))
    _, gate_idx = jax.lax.top_k(
        scores + jax.lax.stop_gradient(router["bias"].astype(jnp.float32)), k)
    chosen = jnp.take_along_axis(scores, gate_idx, axis=-1)
    gate_w = chosen / (chosen.sum(-1, keepdims=True) + 1e-20) * route_scale
    return gate_idx, gate_w, scores


def softmax_route(x: jnp.ndarray, router: Params, k: int
                  ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Softmax scoring over every expert, then the top-k renormalised
    (``norm_topk_prob``) → ``(gate_idx [..., K], gate_w [..., K], probs [...,
    E])``, float32: the weights are the chosen probabilities over their sum.
    No selection bias and no scale."""
    probs = jax.nn.softmax(jnp.einsum(
        "...d,de->...e", x, router["weight"].astype(x.dtype),
        preferred_element_type=jnp.float32), axis=-1)
    chosen, gate_idx = jax.lax.top_k(probs, k)
    return gate_idx, chosen / (chosen.sum(-1, keepdims=True) + 1e-20), probs


# Rows of one chunk's expert buffer, before tile padding, in a layer that holds a share,
# where the model names no size of its own. ``grouped_ffn`` is dropless: its whole buffer
# has a row for every selection, since any of them may be a held one, and a share of the
# experts fills few of them. So such a layer takes its tokens in chunks, one after another,
# and the step holds the buffers of one. What the number bounds depends on the buffer a
# loop takes (:func:`held_chunks`): a chunk's selections in the loop at the whole dropless
# buffer, a chunk's ``SMALL_BUFFER_LOADS`` balanced loads in the loop at the small one,
# which therefore takes fewer, longer trips through a buffer of the same size (xing4_0's
# benchmark cell compiled to 14.36, 14.41 and 14.73 GiB at 2,048, 4,096 and 8,192 while
# both loops cut by selections).
HELD_CHUNK_ROWS = 4096

# A chunk of a held share takes a buffer for this many times the selections a balanced
# router sends the share (``top_k * held / n_routed`` a token), and the whole dropless
# buffer when a chunk's rows do not fit that. A multiple of the balanced load and of
# nothing measured: it has to lie so far above what a router sends that the whole
# buffer is for inputs a job does not produce, or the step's time follows the router
# in steps of a buffer.
SMALL_BUFFER_LOADS = 4


def _balanced_loads(selections: int, held: int, n_routed: int) -> int:
    """Rows for ``SMALL_BUFFER_LOADS`` times what a balanced router sends ``held`` of
    ``n_routed`` experts of ``selections`` selections: every selection where the layer
    holds ``n_routed / SMALL_BUFFER_LOADS`` experts or more."""
    return -(-selections * min(n_routed, SMALL_BUFFER_LOADS * held) // n_routed)


def held_chunks(tokens: int, top_k: int, held: int, n_routed: int,
                chunk_rows: int = HELD_CHUNK_ROWS) -> Tuple[int, int]:
    """``(small, whole)``: the chunks (powers of two dividing ``tokens``) a layer
    that holds ``held`` of ``n_routed`` experts takes its tokens in, by the buffer
    a chunk takes (:func:`chunk_buffer_rows`). Each loop cuts until what its
    buffer has rows for, tile padding apart, fits ``chunk_rows``: at the whole
    dropless buffer a chunk's selections, at the small one a chunk's
    ``SMALL_BUFFER_LOADS`` balanced loads, so the small loop takes as many
    trips or fewer (half where the layer holds an eighth of the experts). One
    count where the layer holds ``n_routed / SMALL_BUFFER_LOADS`` experts or
    more, and ``(1, 1)`` where it holds every expert, whose buffer is all rows."""
    def trips(rows_of: Callable[[int], int]) -> int:
        n = 1
        while rows_of(tokens * top_k // n) > chunk_rows and tokens % (2 * n) == 0:
            n *= 2
        return n

    if held >= n_routed:
        return 1, 1
    return (trips(lambda selections: _balanced_loads(selections, held, n_routed)),
            trips(lambda selections: selections))


def chunk_buffer_rows(selections: int, held: int, n_routed: int, block_t: int) -> Tuple[int, int]:
    """``(small, whole)``: rows of the two buffers of a chunk of ``selections``
    selections in a layer that holds ``held`` of ``n_routed`` experts. The
    small one has a row for ``SMALL_BUFFER_LOADS`` times the selections a
    balanced router sends and the whole one's tile padding; the two are one
    size where the layer holds that share of the experts or more."""
    return (gm.round_up(_balanced_loads(selections, held, n_routed) + held * (block_t - 1), block_t),
            buffer_rows(selections, held, block_t))


def _chunked(a: jnp.ndarray, axis: int, n: int) -> jnp.ndarray:
    """``a`` with its token axes ``(B, S)`` at ``axis, axis + 1`` → ``[n, ..., T, ...]``:
    chunk ``i`` holds tokens ``i T .. (i + 1) T - 1`` of the flattened ``B S``."""
    a = a.reshape(a.shape[:axis] + (n, -1) + a.shape[axis + 2:])
    return jnp.moveaxis(a, axis, 0)


def _longer_chunks(a: jnp.ndarray, axis: int, r: int) -> jnp.ndarray:
    """:func:`_chunked`'s ``[n, ..., T, ...]`` (the tokens at ``axis + 1``) →
    ``[n / r, ..., r T, ...]``: every ``r`` chunks in a row as one. A view where the
    tokens lead (``axis`` 0), a transposed copy where they do not."""
    a = jnp.moveaxis(a.reshape((-1, r) + a.shape[1:]), 1, axis + 1)
    return a.reshape(a.shape[:axis + 1] + (-1,) + a.shape[axis + 3:])


def _shorter_chunks(a: jnp.ndarray, axis: int, r: int) -> jnp.ndarray:
    """:func:`_longer_chunks`'s inverse: ``[m, ..., r T, ...]`` → ``[m r, ..., T, ...]``."""
    a = a.reshape(a.shape[:axis + 1] + (r, -1) + a.shape[axis + 2:])
    a = jnp.moveaxis(a, axis + 1, 1)
    return a.reshape((-1,) + a.shape[2:])


class ChunkLoop(NamedTuple):
    """One way to take a layer's tokens through its held experts: ``trips`` chunks,
    each through a buffer of ``rows`` rows in tiles of ``block_t``."""
    trips: int
    rows: int
    block_t: int


def _chunks_at_either_size(one: Callable, loops: Tuple[ChunkLoop, ChunkLoop], fit: Callable,
                           axes: Sequence[int], chunks: Sequence[jnp.ndarray]):
    """``one(loop, x_c, idx_c, w_c, *operands_c)`` of every chunk of ``chunks =
    (x, idx, w, *operands)``, one after another, stacked: as ``loops[0]``, at
    its ``rows`` and in its ``trips``, where the traced ``fit`` holds of
    ``idx`` in that loop's chunks, and as ``loops[1]`` where not → ``(results,
    trips that ran as loops[1]'s)``.
    Each chunk is rematerialised: the backward has the chunks' inputs and
    nothing else.

    ``chunks`` come in ``loops[1]``'s chunks (:func:`_chunked`, ``axes[i]`` the
    token axis of array ``i`` before it), and the results leave in them. The
    two loops take their own trips (:func:`held_chunks`): ``loops[0]``, whose
    trips divide ``loops[1]``'s, takes several of those chunks as one
    (:func:`_longer_chunks`: a view of an array whose tokens lead) and cuts
    its results up again, inside its own branch, forward and backward, so the
    branch not taken costs nothing. What enters a ``cond`` enters it behind an
    ``optimization_barrier``: where one branch reshapes its operands and the
    other does not, XLA moves the reshape that made the chunks outside (``[B,
    S, C] -> [trips, T, C]``) into the branches, nothing then holds the
    layer's ``[B, S, C]`` arrays to rows first, and xing4_0's streams all take
    a layout with ``S`` innermost, with a copy of 56 MB at every matmul and
    every loop they meet (its benchmark cell compiled to 14.93-15.02 GiB so,
    and ran 1% slower than with equal trips; 14.633 behind the barriers).

    Why not ``jax.checkpoint`` of a ``lax.cond``: differentiating a ``cond``
    gives every branch the residuals of all branches, zero-filled, so the
    small branch would write the whole buffer's residuals as zeros and the
    step would hold both sets. Here no residual crosses a ``cond``: the
    forward is one ``cond`` over two loops, and the backward one ``cond`` over
    two loops whose every trip recomputes its chunk (under ``jax.checkpoint``,
    so a profile names it ``rematted_computation`` as before), transposes it
    and adds what ``one`` closes over (the banks, a tail's weights) its share
    of the gradient in place. The choice is one a loop and not one a chunk:
    chosen inside the loop, the banks' three gradients leave a ``cond`` as
    fresh arrays in every trip (xing4_0's benchmark cell compiled to 14.756
    GiB so, for 14.520 with one buffer size). ``one`` is traced once a loop,
    with what it closes over hoisted, since a ``custom_vjp`` differentiates
    its arguments only."""
    ratios = [loops[1].trips // loop.trips for loop in loops]

    def longer(r, arrays):
        if r == 1:
            return list(arrays)
        with jax.named_scope("layer"):  # as the tail: what moving its operands costs is the layer's
            return [_longer_chunks(a, axis, r) for a, axis in zip(arrays, axes)]

    def shorter(r, tree):   # results are ``[trips, T, ...]``: their tokens lead
        return tree if r == 1 else jax.tree_util.tree_map(lambda a: _shorter_chunks(a, 0, r), tree)

    (small, consts), (whole, same) = (
        jax.closure_convert(functools.partial(one, loop), *(a[0] for a in longer(r, chunks)))
        for loop, r in zip(loops, ratios))
    if len(consts) != len(same) or any(a is not b for a, b in zip(consts, same)):
        raise RuntimeError("a chunk function closes over other arrays at another buffer size")

    def forward(r, f):
        def run(consts, *chunks):
            return shorter(r, jax.lax.scan(lambda _, c: (None, f(*c, *consts)), None,
                                           tuple(longer(r, chunks)))[1])
        return run

    def backward(r, f):
        def trip(consts, acc, c):
            ct_c, x_c, idx_c, *rest_c = c

            def again(consts, x_c, *rest_c):
                return f(x_c, idx_c, *rest_c, *consts)
            # no barrier against merging the recomputation with a forward: this trip holds
            # none, and the barrier cost the cells' steps 0.015 and 0.063 GiB
            d_consts, *d_inputs_c = jax.vjp(jax.checkpoint(again, prevent_cse=False),
                                            consts, x_c, *rest_c)[1](ct_c)
            return [a + d for a, d in zip(acc, d_consts)], d_inputs_c

        def run(consts, chunks, cts):   # the last chunk first, as a scan's transpose has it
            cts = jax.tree_util.tree_map(lambda a: _longer_chunks(a, 0, r), cts) if r > 1 else cts
            d_consts, d_inputs = jax.lax.scan(
                functools.partial(trip, consts), [jnp.zeros_like(c) for c in consts],
                (cts, *longer(r, chunks)), reverse=True)
            if r > 1:   # idx, chunks[1], has no cotangent
                with jax.named_scope("layer"):
                    d_inputs = [_shorter_chunks(d, axis, r)
                                for d, axis in zip(d_inputs, (axes[0], *axes[2:]))]
            return d_consts, d_inputs
        return run

    branches = list(zip(ratios, (small, whole)))

    @jax.custom_vjp
    def loop(idx, consts, x, w, *operands):
        fits = fit(_longer_chunks(idx, 0, ratios[0]))
        x, idx, w, *operands = jax.lax.optimization_barrier((x, idx, w, *operands))
        return (jax.lax.cond(fits, *(forward(r, f) for r, f in branches), consts, x, idx, w, *operands),
                loops[1].trips * (1.0 - fits.astype(jnp.float32)))

    def loop_forward(idx, *inputs):
        return loop(idx, *inputs), (idx, inputs)

    def loop_backward(saved, cts):
        idx, (consts, x, w, *operands) = saved
        fits = fit(_longer_chunks(idx, 0, ratios[0]))
        (x, idx, w, *operands), ct = jax.lax.optimization_barrier(((x, idx, w, *operands), cts[0]))
        d_consts, d_inputs = jax.lax.cond(fits, *(backward(r, f) for r, f in branches),
                                          consts, [x, idx, w, *operands], ct)
        return (None, d_consts, *d_inputs)

    loop.defvjp(loop_forward, loop_backward)
    x, idx, w, *operands = chunks
    return loop(idx, consts, x, w, *operands)


def held_share_ffn(experts: Params, x: jnp.ndarray, gate_idx: jnp.ndarray, gate_w: jnp.ndarray,
                   held: Tuple[int, int], n_routed: int, chunk_rows: int = HELD_CHUNK_ROWS,
                   precision=None, tail: Optional[Callable] = None,
                   operands: Sequence[Tuple[int, jnp.ndarray]] = ()):
    """The held experts' part of a routed layer: ``x [B, S, C]``, the router's
    ``gate_idx``/``gate_w [B, S, K]`` over all ``n_routed`` → ``([B, S, C],
    trips that took the whole buffer)``. ``experts`` holds the banks of
    ``held = (first, count)``; the tokens go through :func:`grouped_ffn` in
    chunks, each rematerialised, so the step holds one chunk's buffers and not
    all.

    A chunk's buffer is the small one of :func:`chunk_buffer_rows` where every
    chunk's :func:`held_rows` fit it and the whole dropless one where some
    chunk's do not (:func:`_chunks_at_either_size`), and each of the two loops
    takes the trips of the buffer it takes (:func:`held_chunks`: both buffers
    stay within ``chunk_rows`` and their tile padding, so the small loop's
    chunks are the longer ones and ``fit`` is asked of those): the same rows in
    the same order either way, nothing dropped, and the second result
    (float32) counts the trips that took the whole one, all of its loop's or
    none. A layer that holds ``n_routed / SMALL_BUFFER_LOADS`` experts or more
    has one size and one count, traces no choice and counts 0.

    ``tail(routed_c, *operands_c)`` is the rest of the layer after its experts,
    which must be token-local (a norm over a token's channels, a mix of a
    token's own streams): it runs inside the chunk function, rematerialised
    with it, on the chunk's routed output ``[T, C]`` and on the
    chunk's slice of every ``(axis, array)`` of ``operands`` (the array's token
    axes ``(B, S)`` lie at ``axis, axis + 1`` and arrive flattened to one of
    ``T``), and its result (an array ``[T, ...]`` or a tuple of them) is the
    chunk's part of what this function then returns, ``[B, S, ...]``.

    Why it runs here: a reader of the loop's *value* outside it (a norm's or a
    mix's backward) makes a rematerialised layer run the whole loop forward
    once more to have that value, and then the loop's own backward recomputes
    every chunk again: three forward passes of the experts where full remat
    asks for two. With the tail inside, the loop's value is read only by
    operations whose backward needs no value (a residual add, a stack), and
    the extra pass is dead code. One chunk (every expert held, or few tokens)
    is no loop: the tail is applied to the whole."""
    B, S, C = x.shape
    K, (first, count) = gate_idx.shape[-1], held

    def loop_at(trips: int, size: int) -> ChunkLoop:   # size: 0 the small buffer, 1 the whole
        selections = B * S * K // trips
        block_t = gm.pick_block_t(selections, count)
        return ChunkLoop(trips, chunk_buffer_rows(selections, count, n_routed, block_t)[size], block_t)

    n_small, n_whole = held_chunks(B * S, K, count, n_routed, chunk_rows)
    small, dropless = loop_at(n_small, 0), loop_at(n_small, 1)
    two_sizes = small.rows < dropless.rows
    whole = loop_at(n_whole, 1) if two_sizes else dropless

    def one(loop, x_c, idx_c, w_c, *operands_c):
        routed = grouped_ffn(experts, x_c, idx_c, w_c, count, loop.block_t,
                             precision=precision, first=first, rows=loop.rows)
        if tail is None:
            return routed
        with jax.named_scope("layer"):  # the layer's operations, not the experts'
            return tail(routed, *operands_c)

    axes = (0, 0, 0) + tuple(axis for axis, _ in operands)
    chunks = (x.reshape(whole.trips, -1, C), gate_idx.reshape(whole.trips, -1, K),
              gate_w.reshape(whole.trips, -1, K).astype(x.dtype))
    with jax.named_scope("layer"):  # as the tail: what moving its operands costs is the layer's
        chunks += tuple(_chunked(a, axis, whole.trips) for axis, a in operands)
    if tail is not None and whole.trips > 1:
        _count_plan("chunk_loop_tail")
    _count_plan("chunk_trips_whole", whole.trips)
    if two_sizes:
        _count_plan("chunk_two_sizes")
        _count_plan("chunk_trips_small", small.trips)
        out, took_whole = _chunks_at_either_size(
            one, (small, whole),
            lambda idx: jnp.all(held_rows(idx, count, small.block_t, first) <= small.rows),
            axes, chunks)
    else:
        took_whole = jnp.zeros((), jnp.float32)
        at_whole = functools.partial(one, whole)
        if whole.trips == 1:
            out = jax.tree_util.tree_map(lambda a: a[None], at_whole(*(a[0] for a in chunks)))
        else:  # rematerialised, or the loop keeps every chunk's buffers for the backward
            _, out = jax.lax.scan(lambda _, c: (None, jax.checkpoint(at_whole)(*c)), None, chunks)
    return jax.tree_util.tree_map(lambda a: a.reshape((B, S) + a.shape[2:]), out), took_whole


def routed_share_ffn(p: Params, x: jnp.ndarray, route: Callable, held: Tuple[int, int],
                     n_routed: int, chunk_rows: int = HELD_CHUNK_ROWS, precision=None,
                     tail: Optional[Callable] = None,
                     operands: Sequence[Tuple[int, jnp.ndarray]] = ()):
    """A routed layer as one expert-parallel rank computes it: the held share of
    the routed experts, and beside it the shared expert every token visits
    where ``p`` has one (``p["shared"]``) → ``(y, stats)``. ``route(x, router)
    -> (gate_idx, gate_w, scores)`` over the router's whole width
    (:func:`sigmoid_route`, :func:`softmax_route`); ``p`` holds ``router`` and
    the held ``experts``; the load counts every selection over the router's
    whole width, and nothing is dropped.

    With ``tail`` (and its ``operands``: :func:`held_share_ffn`)
    the first result is ``tail(y_c, *operands_c)`` of every chunk of tokens,
    put together: the sum with the shared expert's output and the caller's
    tail both run inside the chunk loop, so nothing outside it reads its value
    (the shared expert itself is computed here, once, over all tokens)."""
    from .llama import mlp_block

    with jax.named_scope("moe_router"):
        gate_idx, gate_w, _ = route(x, p["router"])
    shared = None
    if "shared" in p:
        with jax.named_scope("ffn"):
            shared = mlp_block(p["shared"], x)
    with jax.named_scope("moe_experts"):
        if tail is not None and shared is not None:
            tail, operands = ((lambda routed, shared_c, *rest, tail=tail: tail(shared_c + routed, *rest)),
                              ((0, shared),) + tuple(operands))
        out, took_whole = held_share_ffn(p["experts"], x, gate_idx, gate_w, held, n_routed,
                                         chunk_rows, precision, tail=tail, operands=operands)
    stats = dict(zero_stats(n_routed), moe_chunks_whole=took_whole, moe_load=jax.lax.stop_gradient(
        jnp.bincount(gate_idx.reshape(-1), length=n_routed).astype(jnp.float32)))
    return (shared + out if tail is None and shared is not None else out), stats


def sigmoid_routed_ffn(p: Params, x: jnp.ndarray, top_k: int, route_scale: float,
                       held: Tuple[int, int], n_routed: int, chunk_rows: int = HELD_CHUNK_ROWS,
                       precision=None, tail: Optional[Callable] = None,
                       operands: Sequence[Tuple[int, jnp.ndarray]] = ()):
    """:func:`routed_share_ffn` under :func:`sigmoid_route`; ``p["router"]``
    holds ``weight`` and the buffer ``bias``, ``p["shared"]`` the shared expert."""
    return routed_share_ffn(p, x, lambda h, router: sigmoid_route(h, router, top_k, route_scale),
                            held, n_routed, chunk_rows, precision, tail, operands)


def _usable_ep_mesh(args, num_experts: int):
    """The mesh to drop below GSPMD with, or None for the local path.

    Requires a multi-device mesh whose axes are not already bound manual
    (i.e. we are not inside another shard_map, e.g. the pipeline stage
    body), and an expert count divisible by the ep axis.
    """
    from ..parallel.context import current_mesh

    mesh = current_mesh()
    if mesh is None or mesh.size == 1:
        return None
    ep = mesh.shape.get("ep", 1)
    if num_experts % max(ep, 1):
        return None
    if set(jax.sharding.get_abstract_mesh().manual_axes) & set(mesh.axis_names):
        return None
    return mesh


def _grouped_moe_ep(
    p: Params, x: jnp.ndarray, gate_idx: jnp.ndarray, gate_w: jnp.ndarray,
    args, mesh,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Expert-parallel sorted dispatch under shard_map → (out, dropped).

    Each shard routes its local tokens, posts rows into per-destination
    send slots ([ep, cap, D], expert id → owning shard = id // E_loc),
    exchanges them with one ``all_to_all``, runs the local grouped FFN over
    its E/ep experts, and returns rows with a second ``all_to_all``; gate
    weighting and the combine scatter-add stay on the source shard, so
    gradients flow through the exchange untouched.

    ``cap`` (send slots per source→dest pair) is static:
    ``moe_ep_capacity_factor <= 0`` means worst-case (= local selections,
    dropless); a positive factor shrinks the exchange to
    ``factor · TK / ep`` and overflow beyond it is dropped and counted.
    """
    from ..parallel.sharding_rules import moe_dispatch_specs

    B, S, D = x.shape
    E, K = args.num_local_experts, args.num_experts_per_tok
    ep = max(mesh.shape.get("ep", 1), 1)
    e_loc = E // ep

    specs = moe_dispatch_specs(mesh)
    # Static per-shard token geometry (shard_map divides batch evenly).
    b_shards = 1
    for a in specs["batch_axes"]:
        b_shards *= mesh.shape.get(a, 1)
    t_loc = (B // b_shards) * S
    tk = t_loc * K
    factor = float(getattr(args, "moe_ep_capacity_factor", 0.0) or 0.0)
    cap = tk if factor <= 0 else max(1, min(tk, int(factor * tk / ep + 0.5)))
    block_t = gm.pick_block_t(ep * cap, e_loc)

    def body(x_l, gi_l, gw_l, wg_l, wu_l, wd_l):
        b_l, s_l, _ = x_l.shape
        T_l = b_l * s_l
        TK = T_l * K
        xf = x_l.reshape(T_l, D)
        ids = gi_l.reshape(TK)
        gwf = gw_l.reshape(TK)
        tok = jnp.arange(TK, dtype=jnp.int32) // K

        dest_shard = ids // e_loc
        local_eid = ids % e_loc

        # Slot assignment: stable sort by destination shard (token-major
        # fairness within each destination, like einsum capacity).
        order = jnp.argsort(dest_shard, stable=True)
        ds_s = dest_shard[order]
        cnt = jnp.bincount(dest_shard, length=ep)
        start = jnp.cumsum(cnt) - cnt
        rank = jnp.arange(TK, dtype=jnp.int32) - start[ds_s].astype(jnp.int32)
        keep = rank < cap
        slot = ds_s * cap + rank
        slot_put = jnp.where(keep, slot, ep * cap)  # OOB scatter = drop

        send_x = jnp.zeros((ep * cap, D), xf.dtype).at[slot_put].set(xf[tok[order]])
        send_id = jnp.full((ep * cap,), e_loc, jnp.int32).at[slot_put].set(
            local_eid[order])

        recv_x = jax.lax.all_to_all(
            send_x.reshape(ep, cap, D), "ep", split_axis=0, concat_axis=0,
            tiled=True)
        recv_id = jax.lax.all_to_all(
            send_id.reshape(ep, cap), "ep", split_axis=0, concat_axis=0,
            tiled=True)

        # Local grouped FFN over the E/ep resident experts; sentinel id
        # e_loc marks empty slots and sorts past every real group.
        R = ep * cap
        rx = recv_x.reshape(R, D)
        rid = recv_id.reshape(R)
        counts = jnp.bincount(rid, length=e_loc)  # sentinels fall off
        padded = ((counts + block_t - 1) // block_t) * block_t
        p_off = jnp.concatenate([jnp.zeros((1,), padded.dtype), jnp.cumsum(padded)])
        raw_off = jnp.cumsum(counts) - counts
        order2 = jnp.argsort(rid, stable=True)
        rid_s = rid[order2]
        real2 = rid_s < e_loc
        rid_c = jnp.minimum(rid_s, e_loc - 1)
        rank2 = jnp.arange(R, dtype=jnp.int32) - raw_off[rid_c].astype(jnp.int32)
        T_buf = buffer_rows(R, e_loc, block_t)
        dest2 = jnp.where(real2, (p_off[rid_c] + rank2).astype(jnp.int32), T_buf)

        x_buf = jnp.zeros((T_buf, D), rx.dtype).at[dest2].set(rx[order2])
        prec = getattr(args, "matmul_precision", None)
        h = jax.nn.silu(
            gm.gmm(x_buf, wg_l, padded, block_t=block_t, precision=prec)
        ) * gm.gmm(x_buf, wu_l, padded, block_t=block_t, precision=prec)
        y_buf = gm.gmm(h, wd_l, padded, block_t=block_t, precision=prec)

        y_sorted = y_buf[jnp.minimum(dest2, T_buf - 1)] * real2[:, None]
        y_recv = jnp.zeros((R, D), y_buf.dtype).at[order2].set(y_sorted)

        y_back = jax.lax.all_to_all(
            y_recv.reshape(ep, cap, D), "ep", split_axis=0, concat_axis=0,
            tiled=True).reshape(R, D)

        y_sel = y_back[jnp.minimum(slot, R - 1)] * keep[:, None]
        out = jnp.zeros((T_l, D), x_l.dtype).at[tok[order]].add(
            y_sel * gwf[order][:, None].astype(y_sel.dtype))

        dropped = jax.lax.psum(
            (TK - keep.sum()).astype(jnp.float32), tuple(mesh.axis_names))
        return out.reshape(b_l, s_l, D), dropped

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(specs["activation"], specs["gate"], specs["gate"],
                  specs["expert_weight"], specs["expert_weight"],
                  specs["expert_weight"]),
        out_specs=(specs["activation"], specs["replicated"]),
        check_vma=False,
    )
    p = _maybe_dequant_experts(p)  # ep ships fp banks through shard_map
    out, dropped = fn(
        x, gate_idx, gate_w,
        p["experts"]["w_gate"]["weight"],
        p["experts"]["w_up"]["weight"],
        p["experts"]["w_down"]["weight"],
    )
    return out, jax.lax.stop_gradient(dropped)


# -- block entry point -------------------------------------------------------
def moe_block(p: Params, x: jnp.ndarray, args) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x [B, S, D] → (out [B, S, D], aux_loss scalar fp32).

    Routes through the impl selected by ``args.moe_impl`` (see module
    docstring). The returned aux term is **fully pre-scaled**:
    ``moe_aux_weight * load_balance + router_z_weight * z_loss``; callers
    add it to the CE loss unweighted. Aux is computed from real tokens only
    and is identical across impls (it depends on the router, not the
    dispatch).
    """
    B, S, D = x.shape
    E, K = args.num_local_experts, args.num_experts_per_tok
    impl = getattr(args, "moe_impl", "grouped") or "grouped"

    # Project in the activation dtype, then route in fp32: only the tiny
    # [B, S, E] logits are upcast, not the [B, S, D] activations — under
    # bf16 compute the old fp32 projection paid an activation-sized
    # convert plus a 2x-wide matmul for logits that top_k/softmax need at
    # fp32 anyway (caught by graftaudit's dtype-upcast rule).
    if impl not in ("einsum", "grouped"):
        raise ValueError(f"unknown moe impl {impl!r} (grouped|einsum)")
    with jax.named_scope("moe_router"):
        router_logits = (x @ p["router"]["weight"].astype(x.dtype)).astype(jnp.float32)
        probs = jax.nn.softmax(router_logits, axis=-1)  # [B, S, E] fp32
        gate_w, gate_idx = jax.lax.top_k(probs, K)  # [B, S, K]; einsum: stats only
        gate_w = gate_w / jnp.maximum(gate_w.sum(-1, keepdims=True), 1e-9)

        aw = float(getattr(args, "moe_aux_weight", 0.0) or 0.0)
        zw = float(getattr(args, "router_z_weight", 0.0) or 0.0)
        aux = jnp.zeros((), jnp.float32)
        if aw:
            aux = aux + aw * load_balancing_loss(probs, jnp.argmax(router_logits, axis=-1), E)
        if zw:
            aux = aux + zw * router_z_loss(router_logits)

    with jax.named_scope("moe_experts"):
        if impl == "einsum":
            out, dropped = _einsum_moe(p, x, probs, args)
        else:
            mesh = _usable_ep_mesh(args, E)
            if mesh is not None:
                out, dropped = _grouped_moe_ep(p, x, gate_idx, gate_w, args, mesh)
            else:
                out = grouped_ffn(
                    p["experts"], x.reshape(B * S, D), gate_idx.reshape(B * S, K),
                    gate_w.reshape(B * S, K), E,
                    gm.pick_block_t(B * S * K, E),
                    precision=getattr(args, "matmul_precision", None),
                ).reshape(B, S, D)
                dropped = jnp.zeros((), jnp.float32)

    if stats_tap_active():
        record_stats(dict(
            zero_stats(E),
            moe_load=jax.lax.stop_gradient(
                jnp.bincount(gate_idx.reshape(-1), length=E).astype(jnp.float32)),
            moe_dropped=jax.lax.stop_gradient(dropped.astype(jnp.float32))))
    return out, aux
