"""Grouped expert matmuls (``gmm``) for dropless MoE dispatch.

A grouped GEMM multiplies a token-sorted activation matrix ``x [T, N_in]``
against stacked per-expert weights ``w [E, N_in, N_out]``: rows
``[offset_e, offset_{e+1})`` of ``x`` hit expert ``e``'s weight. This is the
MegaBlocks formulation (Gale et al., 2022): routing becomes a sort + two
gathers (into the buffer by row, out of it by selection; their backward is
the same two gathers the other way: models/moe.py ``dispatch_rows`` and
``combine_rows``) and the expert FFN becomes three grouped GEMMs, so no
token is ever dropped and no dispatch one-hots are materialized.

Three backends behind one differentiable entry point:

- ``pallas`` — two tiled TPU kernels, ``gmm`` and ``tgmm``. Row tiles of
  ``block_t`` map onto expert weight blocks through a scalar-prefetch
  ``tile → expert`` table. **What is multiplied:** every row tile of the
  buffer, the dead tail (clamped to the last expert) included: the buffer
  is sized for the worst case the router could send, its shape is static,
  and no tile is skipped, so a call's time does not follow the router.
  **What is resident:** the operand that is the same for consecutive row
  tiles of an expert stays in VMEM across them (:func:`gmm_plan`, a pure
  function of the shapes, as ``flash_plan`` is for attention). In ``gmm``
  (forward, recomputation, and dX) the grid is (column blocks, row tiles)
  with the row tile fastest, so the weight block ``[K, bn]`` at
  ``(te[t], 0, n)`` keeps its index across an expert's tiles and across
  the whole dead tail, Pallas skips the copy, and what is streamed a grid
  step is one ``[block_t, K]`` row tile at ``bn`` operations a byte. In
  ``tgmm`` (dW) the grid is the same and the output block ``[K, bn]`` of
  an expert is revisited across that expert's row tiles; its partial sums
  are kept in a float32 VMEM scratch and rounded once, on the expert's
  last tile, and the ``[T, K]`` buffer is read ``N / bn`` times. The plan
  picks the widest ``bn`` (``N`` itself, else a multiple of 128 dividing
  it, down to 128) whose blocks fit a VMEM budget, and every call raises
  Mosaic's scoped limit to hold them. Until PR 34 the row tile was
  outermost at a ``bn`` of 128, which streamed an expert's whole
  ``[K, N]`` matrix from HBM again for every row tile (110 operations a
  byte at ``block_t`` 128: the 35% of peak the kernels read then). There
  is no knob: the ``GMM_BLOCK_N`` override is gone, and
  :func:`plan_counts` says how many calls were traced and with which
  ``bn``. Backward is a custom VJP: dX is a gmm over the forward's own
  weights, contracting their last axis inside the kernel (no transposed
  copy of the bank), dW is ``tgmm``. Runs under Pallas interpret mode
  off-TPU, so tier-1 CPU tests exercise the same kernel code.
- ``blocked`` — the kernel's tiling expressed as plain XLA ops: reshape the
  tile-aligned buffer to ``[n_tiles, block_t, K]``, gather each tile's
  expert weight through the same ``tile_experts`` table, one batched
  matmul. Differentiates itself (dW is XLA's scatter-add through the
  gather). Default off-TPU: interpret-mode Pallas is an emulator, and
  ``jax.lax.ragged_dot`` lowers to a serial row walk on CPU (~10x slower
  than the equivalent dense matmul, measured) — the batched form keeps the
  padded-buffer overhead (~T_buf/T) as the only cost over dense.
- ``ragged`` — ``jax.lax.ragged_dot``, which XLA lowers natively on every
  backend and differentiates itself; the reference semantics the other
  two backends are tested against.

Contract shared by the backends (the dispatcher in models/moe.py
guarantees it): ``group_sizes`` must each be a multiple of ``block_t`` so a
row tile never straddles two experts, and rows inside a group beyond the
real token count are zero padding. Rows past ``sum(group_sizes)`` are
compute-garbage tiles the caller must never read back.
"""

from __future__ import annotations

import collections
import functools
import os
import threading
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import backend_from_env

__all__ = [
    "DEFAULT_BLOCK_T",
    "gmm",
    "gmm_plan",
    "pick_block_t",
    "plan_counts",
    "round_up",
    "tile_experts",
]

# Row-tile height. 128 matches the MXU systolic array; off-TPU the value only
# shapes the dispatch padding. The column block is the plan's (gmm_plan).
DEFAULT_BLOCK_T = int(os.environ.get("GMM_BLOCK_T", 128))


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def default_backend() -> str:
    """``pallas`` on TPU, ``blocked`` elsewhere; ``GMM_BACKEND`` overrides
    (tests force ``pallas`` to run the kernel under interpret mode)."""
    return backend_from_env("GMM_BACKEND", "pallas", "blocked")


def round_up(value: int, multiple: int) -> int:
    return ((value + multiple - 1) // multiple) * multiple


def pick_block_t(rows: int, num_experts: int = 0) -> int:
    """Largest power-of-two tile ≤ DEFAULT_BLOCK_T that does not dwarf the
    row count — decode steps route a handful of tokens and would otherwise
    pay E·(128−1) rows of padding per microbatch.

    With ``num_experts`` the tile also shrinks while the worst-case
    per-expert alignment padding (``E·(bt−1)`` rows) exceeds half the real
    rows: production token counts (rows ≫ E·256) keep the MXU-matched
    default, while decode-sized dispatches trade tile width for a
    near-dense buffer. The threshold is deliberately loose — each halving
    also doubles the tile count, and the blocked backend pays one expert
    weight gather per tile, so small tiles cost more than the padding
    they save.
    """
    bt = 8
    while bt < DEFAULT_BLOCK_T and bt < rows:
        bt *= 2
    if num_experts > 0:
        while bt > 8 and num_experts * (bt - 1) > rows // 2:
            bt //= 2
    return bt


def tile_experts(group_sizes: jnp.ndarray, n_tiles: int, block_t: int) -> jnp.ndarray:
    """int32 ``[n_tiles]`` owning expert of each row tile.

    Expert ``e`` covers rows ``[ends[e-1], ends[e])``; a tile starting at
    ``s`` belongs to the first expert whose end exceeds ``s``. Tiles past
    the last group (static padding tail) clamp to the final expert — they
    multiply zero rows and their output is never read.
    """
    ends = jnp.cumsum(group_sizes.astype(jnp.int32))
    starts = jnp.arange(n_tiles, dtype=jnp.int32) * block_t
    te = jnp.searchsorted(ends, starts, side="right")
    return jnp.minimum(te, group_sizes.shape[0] - 1).astype(jnp.int32)


# -- the plan: a call's column block from its shapes ---------------------------
_LANES = 128
# Every call raises Mosaic's scoped VMEM limit to this (a v5e core has
# 128 MiB; the default scope is 16), as the flash kernels' resident paths do,
# and takes the widest column block whose buffers stay under the budget: the
# held block (an expert's weights ``[K, bn]`` in gmm, its dW ``[K, bn]`` in
# tgmm) and the streamed tiles, each double-buffered by the pipeline, tgmm's
# float32 partial sums, and the float32 product of a grid step before it is
# cast or added. The rest is for what the compiler spills.
_RESIDENT_VMEM_LIMIT = 32 * 2**20
_RESIDENT_VMEM_BUDGET = 24 * 2**20
# tgmm multiplies a row tile against dy in chunks of this many of K's columns,
# so the float32 product that is added to the partial sums stays small.
_TGMM_K_CHUNK = 512


def _tgmm_k_chunk(K: int) -> int:
    return _TGMM_K_CHUNK if K % _TGMM_K_CHUNK == 0 else K


def _vmem_bytes(kernel: str, K: int, bn: int, block_t: int, itemsize: int) -> int:
    """VMEM one call holds at column block ``bn``: VMEM pads the last axis to
    128 lanes and the one before it to a register's rows."""
    sublanes = 32 // itemsize
    bt = round_up(block_t, sublanes)
    lanes = round_up(bn, _LANES)
    held = 2 * round_up(K, sublanes) * lanes * itemsize
    tiles = 2 * bt * (round_up(K, _LANES) + lanes) * itemsize
    if kernel == "gmm":
        return held + tiles + round_up(block_t, 8) * lanes * 4
    return held + tiles + (round_up(K, 8) + _tgmm_k_chunk(K)) * lanes * 4


def gmm_plan(K: int, N: int, block_t: int, dtype, kernel: str = "gmm") -> int:
    """The column block ``bn`` of a ``gmm`` or ``tgmm`` call over ``[T, K]``
    rows and ``[E, K, N]`` weights; a pure function of its arguments. The
    widest of ``N`` itself and the multiples of 128 dividing it whose buffers
    fit the budget; the narrowest of them where none does (Mosaic then has
    the last word on the call)."""
    itemsize = jnp.dtype(dtype).itemsize
    widths = [N] + [bn for bn in range((N - 1) // _LANES * _LANES, 0, -_LANES) if N % bn == 0]
    for bn in widths:
        if _vmem_bytes(kernel, K, bn, block_t, itemsize) <= _RESIDENT_VMEM_BUDGET:
            return bn
    return widths[-1]


# The plan is chosen while tracing, so this counts traces, not calls of the
# compiled step: what a jitted program runs is what its one trace counted.
_PLAN_KEYS = ("gmm_resident", "tgmm_resident")
_plan_counts: Dict[str, int] = collections.Counter()
_plan_counts_lock = threading.Lock()


def plan_counts() -> Dict[str, int]:
    """Kernel calls traced so far in this process, by kernel (every call
    holds an expert's block across its row tiles), and again by their column
    block (``gmm_bn1024``: a key a block that was traced; the two totals
    always)."""
    with _plan_counts_lock:
        return {**{key: _plan_counts[key] for key in _PLAN_KEYS},
                **{key: n for key, n in sorted(_plan_counts.items()) if key not in _PLAN_KEYS}}


def _traced_plan(kernel, K, N, block_t, dtype) -> int:
    """The column block of one kernel call, tallied."""
    bn = gmm_plan(K, N, block_t, dtype, kernel)
    with _plan_counts_lock:
        _plan_counts[f"{kernel}_resident"] += 1
        _plan_counts[f"{kernel}_bn{bn}"] += 1
    return bn


def _compiler_params(semantics):
    if _interpret():
        return None
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=_RESIDENT_VMEM_LIMIT)


def _blocks(T, N, block_t, bn):
    if T % block_t or N % bn:
        raise ValueError(
            f"gmm pallas backend needs T ({T}) % block_t ({block_t}) == 0 and "
            f"N ({N}) % block_n ({bn}) == 0; the moe dispatcher pads for this")
    return T // block_t, N // bn


# -- forward kernel ----------------------------------------------------------
def _gmm_kernel(te_ref, x_ref, w_ref, o_ref, *, w_contracts):
    del te_ref  # only consumed by the index maps
    o_ref[...] = jax.lax.dot_general(
        x_ref[...], w_ref[0],
        (((1,), (w_contracts,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(o_ref.dtype)


def _gmm_pallas(x, w, group_sizes, block_t, transposed_w=False):
    """``x [T, K]`` against ``w [E, K, N]``, or with ``transposed_w`` against
    ``w [E, N, K]`` contracted over its last axis (dX against the forward's
    own weights, with no transposed copy of the bank)."""
    T, K = x.shape
    N = w.shape[1] if transposed_w else w.shape[2]
    bn = _traced_plan("gmm", K, N, block_t, x.dtype)
    n_t, n_n = _blocks(T, N, block_t, bn)
    te = tile_experts(group_sizes, n_t, block_t)
    # The column block outermost and the row tile fastest, so the weight
    # block's index repeats across an expert's tiles (and the dead tail's)
    # and it is copied in once.
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_n, n_t),
        in_specs=[
            pl.BlockSpec((block_t, K), lambda n, t, te: (t, 0)),
            pl.BlockSpec((1, bn, K), lambda n, t, te: (te[t], n, 0)) if transposed_w
            else pl.BlockSpec((1, K, bn), lambda n, t, te: (te[t], 0, n)),
        ],
        out_specs=pl.BlockSpec((block_t, bn), lambda n, t, te: (t, n)),
    )
    return pl.pallas_call(
        functools.partial(_gmm_kernel, w_contracts=1 if transposed_w else 0),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, N), x.dtype),
        # every output block is written once: both axes may be split over cores
        compiler_params=_compiler_params(("parallel", "parallel")),
        interpret=_interpret(),
        name="gmm",  # also the innermost scope of its ops
    )(te, x, w)


# -- backward dW kernel (tgmm) -----------------------------------------------
def _tgmm_kernel(te_ref, x_ref, dy_ref, dw_ref, acc_ref, *, k_chunk):
    # Grid is (n_n, n_t) with t fastest, so revisits of one expert's output
    # block are consecutive: the partial sums start on the first tile of
    # each group, grow in float32 on the rest, and are rounded into the
    # output block once, on the group's last tile.
    t, n_t = pl.program_id(1), pl.num_programs(1)
    first = jnp.logical_or(t == 0, te_ref[t] != te_ref[jnp.maximum(t - 1, 0)])
    last = jnp.logical_or(t == n_t - 1, te_ref[t] != te_ref[jnp.minimum(t + 1, n_t - 1)])

    for k in range(0, x_ref.shape[1], k_chunk):
        rows = pl.ds(k, k_chunk)
        part = jax.lax.dot_general(
            x_ref[:, rows], dy_ref[...],
            (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        # a select, not 0 * the scratch: what the last expert left there is stale
        acc_ref[rows, :] = jnp.where(first, part, acc_ref[rows, :] + part)

    @pl.when(last)
    def _round():
        dw_ref[0] = acc_ref[...].astype(dw_ref.dtype)


def _tgmm_pallas(x, dy, group_sizes, n_experts, block_t):
    """dW ``[E, K, N]`` = per-group ``x_rows.T @ dy_rows``."""
    T, K = x.shape
    _, N = dy.shape
    bn = _traced_plan("tgmm", K, N, block_t, x.dtype)
    n_t, n_n = _blocks(T, N, block_t, bn)
    te = tile_experts(group_sizes, n_t, block_t)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_n, n_t),
        in_specs=[
            pl.BlockSpec((block_t, K), lambda n, t, te: (t, 0)),
            pl.BlockSpec((block_t, bn), lambda n, t, te: (t, n)),
        ],
        out_specs=pl.BlockSpec((1, K, bn), lambda n, t, te: (te[t], 0, n)),
        scratch_shapes=[pltpu.VMEM((K, bn), jnp.float32)],
    )
    dw = pl.pallas_call(
        functools.partial(_tgmm_kernel, k_chunk=_tgmm_k_chunk(K)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_experts, K, N), x.dtype),
        compiler_params=_compiler_params(("parallel", "arbitrary")),
        interpret=_interpret(),
        name="tgmm",
    )(te, x, dy)
    # Experts that received no tiles were never written; also covers the
    # clamped tail tiles double-writing the last expert with zero rows.
    return jnp.where((group_sizes > 0)[:, None, None], dw, 0)


# -- int8 forward (amax/scale tracked), fp backward --------------------------
def _quantize_rows_int8(x):
    """Per-row symmetric int8 over the contraction dim: [T, K] ->
    (int8 [T, K], fp32 scales [T, 1])."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    s = jnp.maximum(amax / 127.0, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / s), -127, 127)
    return q.astype(jnp.int8), s


def _quantize_cols_int8(w):
    """Per-(expert, out-column) symmetric int8 over the contraction dim:
    [E, K, N] -> (int8 [E, K, N], fp32 scales [E, 1, N])."""
    amax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=1, keepdims=True)
    s = jnp.maximum(amax / 127.0, 1e-8)
    q = jnp.clip(jnp.round(w.astype(jnp.float32) / s), -127, 127)
    return q.astype(jnp.int8), s


def _gmm_int8_impl(x, w, group_sizes, block_t):
    """Real int8×int8→int32 grouped GEMM in the blocked formulation: both
    operands are amax/scale-quantized per contraction row/column, the
    tile-batched ``dot_general`` contracts in integers, and the scales
    multiply back on the [T, N] result (rank-1 per tile: row scales ×
    that tile's expert column scales)."""
    T, K = x.shape
    if T % block_t:
        raise ValueError(
            f"gmm int8 path needs T ({T}) % block_t ({block_t}) == 0; "
            "the moe dispatcher pads for this")
    n_t = T // block_t
    te = tile_experts(group_sizes.astype(jnp.int32), n_t, block_t)
    xq, sx = _quantize_rows_int8(x)
    wq, sw = _quantize_cols_int8(w)
    yt = jnp.einsum("tbk,tkn->tbn", xq.reshape(n_t, block_t, K), wq[te],
                    preferred_element_type=jnp.int32)
    y = yt.astype(jnp.float32) * sx.reshape(n_t, block_t, 1) * sw[te]
    return y.reshape(T, w.shape[2]).astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _gmm_int8(x, w, group_sizes, block_t):
    return _gmm_int8_impl(x, w, group_sizes, block_t)


def _gmm_int8_fwd(x, w, group_sizes, block_t):
    return _gmm_int8_impl(x, w, group_sizes, block_t), (x, w, group_sizes)


def _gmm_int8_bwd(block_t, residuals, dy):
    # Straight-through: gradients flow as if the forward were the fp
    # grouped GEMM (the quantization error is treated as noise), keeping
    # the backward in full precision like the flash-attention int8 path.
    x, w, group_sizes = residuals
    T, K = x.shape
    n_t = T // block_t
    te = tile_experts(group_sizes.astype(jnp.int32), n_t, block_t)
    dx = gmm(dy, w.transpose(0, 2, 1), group_sizes, block_t=block_t,
             backend="blocked")
    part = jnp.einsum("tbk,tbn->tkn", x.reshape(n_t, block_t, K),
                      dy.reshape(n_t, block_t, -1),
                      preferred_element_type=jnp.float32)
    dw = jnp.zeros(w.shape, jnp.float32).at[te].add(part)
    dw = jnp.where((group_sizes > 0)[:, None, None], dw, 0)
    return dx.astype(x.dtype), dw.astype(w.dtype), None


_gmm_int8.defvjp(_gmm_int8_fwd, _gmm_int8_bwd)


# -- differentiable entry point ----------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _gmm_pallas_diff(x, w, group_sizes, block_t):
    return _gmm_pallas(x, w, group_sizes, block_t)


def _gmm_fwd(x, w, group_sizes, block_t):
    return _gmm_pallas(x, w, group_sizes, block_t), (x, w, group_sizes)


def _gmm_bwd(block_t, residuals, dy):
    x, w, group_sizes = residuals
    # dX contracts the weights' last axis inside the kernel: handing it
    # w.transpose(0, 2, 1) costs a layout copy of every expert's matrix a
    # call, and the MXU takes either orientation alike.
    dx = _gmm_pallas(dy, w, group_sizes, block_t, transposed_w=True)
    dw = _tgmm_pallas(x, dy, group_sizes, w.shape[0], block_t)
    return dx.astype(x.dtype), dw.astype(w.dtype), None


_gmm_pallas_diff.defvjp(_gmm_fwd, _gmm_bwd)


def gmm(
    x: jnp.ndarray,
    w: jnp.ndarray,
    group_sizes: jnp.ndarray,
    *,
    block_t: int = DEFAULT_BLOCK_T,
    backend: Optional[str] = None,
    precision: Optional[str] = None,
) -> jnp.ndarray:
    """``x [T, N_in]`` × ``w [E, N_in, N_out]`` → ``[T, N_out]`` where row
    block ``e`` of ``x`` (per ``group_sizes``, block_t-aligned) multiplies
    ``w[e]``. Differentiable in ``x`` and ``w`` on both backends.

    ``precision`` (model.matmul_precision): "int8" runs the forward as a
    real int8×int8→int32 grouped contraction with amax/scale tracking
    (per activation row, per expert output column) and a full-precision
    backward; "bf16" casts the operands. None/"fp32" is the fp path."""
    from .flash_attention import check_matmul_precision

    precision = check_matmul_precision(precision)
    if precision == "int8":
        return _gmm_int8(x, w, group_sizes, block_t)
    if precision == "bf16":
        x = x.astype(jnp.bfloat16)
        w = w.astype(jnp.bfloat16)
    backend = backend or default_backend()
    if backend == "ragged":
        # XLA-native ragged dot: differentiates itself (dX transpose rule +
        # grouped dW) and tolerates unaligned groups.
        return jax.lax.ragged_dot(x, w, group_sizes.astype(jnp.int32))
    if backend == "blocked":
        T, K = x.shape
        if T % block_t:
            raise ValueError(
                f"gmm blocked backend needs T ({T}) % block_t ({block_t})"
                " == 0; the moe dispatcher pads for this")
        n_t = T // block_t
        te = tile_experts(group_sizes.astype(jnp.int32), n_t, block_t)
        xt = x.reshape(n_t, block_t, K)
        # One weight gather + one batched matmul; XLA's transpose rules
        # give dX (batched matmul vs w[te].T) and dW (scatter-add of the
        # per-tile outer products back through the gather) for free.
        yt = jnp.einsum("tbk,tkn->tbn", xt, w[te],
                        preferred_element_type=jnp.float32)
        return yt.reshape(T, w.shape[2]).astype(x.dtype)
    if backend != "pallas":
        raise ValueError(
            f"unknown gmm backend {backend!r} (pallas|blocked|ragged)")
    return _gmm_pallas_diff(x, w, group_sizes.astype(jnp.int32), block_t)
