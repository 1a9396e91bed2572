"""Grouped expert matmuls (``gmm``) for dropless MoE dispatch.

A grouped GEMM multiplies a token-sorted activation matrix ``x [T, N_in]``
against stacked per-expert weights ``w [E, N_in, N_out]``: rows
``[offset_e, offset_{e+1})`` of ``x`` hit expert ``e``'s weight. This is the
MegaBlocks formulation (Gale et al., 2022): routing becomes a sort + two
gathers (into the buffer by row, out of it by selection; their backward is
the same two gathers the other way: models/moe.py ``dispatch_rows`` and
``combine_rows``) and the expert FFN becomes three grouped GEMMs, so no
token is ever dropped and no dispatch one-hots are materialized.

Two backends behind one differentiable entry point:

- ``pallas`` — a tiled TPU kernel. Row tiles of ``block_t`` map onto expert
  weight blocks through a scalar-prefetch ``tile → expert`` table, so the
  MXU only ever touches the experts that actually received tokens. Backward
  is a custom VJP: dX is a gmm against transposed weights, dW is a
  per-group accumulation kernel (``tgmm``) that revisits each expert's
  output block across that expert's row tiles. Runs under Pallas interpret
  mode off-TPU, so tier-1 CPU tests exercise the same kernel code.
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

Contract shared by both backends (the dispatcher in models/moe.py
guarantees it): ``group_sizes`` must each be a multiple of ``block_t`` so a
row tile never straddles two experts, and rows inside a group beyond the
real token count are zero padding. Rows past ``sum(group_sizes)`` are
compute-garbage tiles the caller must never read back.
"""

from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "DEFAULT_BLOCK_T",
    "gmm",
    "pick_block_t",
    "round_up",
    "tile_experts",
]

# Row-tile height and output-column tile width. 128 matches the MXU systolic
# array; off-TPU the values only shape the dispatch padding.
DEFAULT_BLOCK_T = int(os.environ.get("GMM_BLOCK_T", 128))
DEFAULT_BLOCK_N = int(os.environ.get("GMM_BLOCK_N", 128))


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def default_backend() -> str:
    """``pallas`` on TPU, ``blocked`` elsewhere; ``GMM_BACKEND`` overrides
    (tests force ``pallas`` to run the kernel under interpret mode)."""
    env = os.environ.get("GMM_BACKEND", "").strip()
    if env:
        return env
    return "pallas" if jax.default_backend() == "tpu" else "blocked"


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


def _compiler_params(semantics):
    if _interpret():
        return None
    return pltpu.CompilerParams(dimension_semantics=semantics)


# -- forward kernel ----------------------------------------------------------
def _gmm_kernel(te_ref, x_ref, w_ref, o_ref):
    del te_ref  # only consumed by the index maps
    o_ref[...] = jax.lax.dot_general(
        x_ref[...], w_ref[0],
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(o_ref.dtype)


def _gmm_pallas(x, w, group_sizes, block_t, block_n):
    T, K = x.shape
    E, _, N = w.shape
    bn = min(block_n, N)
    if T % block_t or N % bn:
        raise ValueError(
            f"gmm pallas backend needs T ({T}) % block_t ({block_t}) == 0 and "
            f"N ({N}) % block_n ({bn}) == 0; the moe dispatcher pads for this")
    n_t, n_n = T // block_t, N // bn
    te = tile_experts(group_sizes, n_t, block_t)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_t, n_n),
        in_specs=[
            pl.BlockSpec((block_t, K), lambda t, n, te: (t, 0)),
            pl.BlockSpec((1, K, bn), lambda t, n, te: (te[t], 0, n)),
        ],
        out_specs=pl.BlockSpec((block_t, bn), lambda t, n, te: (t, n)),
    )
    return pl.pallas_call(
        _gmm_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, N), x.dtype),
        compiler_params=_compiler_params(("parallel", "parallel")),
        interpret=_interpret(),
        name="gmm",  # also the innermost scope of its ops
    )(te, x, w)


# -- backward dW kernel (tgmm) -----------------------------------------------
def _tgmm_kernel(te_ref, x_ref, dy_ref, dw_ref):
    # Grid is (n_n, n_t) with t fastest, so revisits of one expert's output
    # block are consecutive — initialize on the first tile of each group,
    # accumulate on the rest (the Pallas output-revisit rule).
    t = pl.program_id(1)
    prev = te_ref[jnp.maximum(t - 1, 0)]
    first = jnp.logical_or(t == 0, te_ref[t] != prev)
    part = jax.lax.dot_general(
        x_ref[...], dy_ref[...],
        (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )[None].astype(dw_ref.dtype)

    @pl.when(first)
    def _init():
        dw_ref[...] = part

    @pl.when(jnp.logical_not(first))
    def _accumulate():
        dw_ref[...] = dw_ref[...] + part


def _tgmm_pallas(x, dy, group_sizes, n_experts, block_t, block_n):
    """dW ``[E, K, N]`` = per-group ``x_rows.T @ dy_rows``."""
    T, K = x.shape
    _, N = dy.shape
    bn = min(block_n, N)
    n_t, n_n = T // block_t, N // bn
    te = tile_experts(group_sizes, n_t, block_t)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_n, n_t),
        in_specs=[
            pl.BlockSpec((block_t, K), lambda n, t, te: (t, 0)),
            pl.BlockSpec((block_t, bn), lambda n, t, te: (t, n)),
        ],
        out_specs=pl.BlockSpec((1, K, bn), lambda n, t, te: (te[t], 0, n)),
    )
    dw = pl.pallas_call(
        _tgmm_kernel,
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
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _gmm_pallas_diff(x, w, group_sizes, block_t, block_n):
    return _gmm_pallas(x, w, group_sizes, block_t, block_n)


def _gmm_fwd(x, w, group_sizes, block_t, block_n):
    return _gmm_pallas(x, w, group_sizes, block_t, block_n), (x, w, group_sizes)


def _gmm_bwd(block_t, block_n, residuals, dy):
    x, w, group_sizes = residuals
    dx = _gmm_pallas(dy, w.transpose(0, 2, 1), group_sizes, block_t, block_n)
    dw = _tgmm_pallas(x, dy, group_sizes, w.shape[0], block_t, block_n)
    return dx.astype(x.dtype), dw.astype(w.dtype), None


_gmm_pallas_diff.defvjp(_gmm_fwd, _gmm_bwd)


def gmm(
    x: jnp.ndarray,
    w: jnp.ndarray,
    group_sizes: jnp.ndarray,
    *,
    block_t: int = DEFAULT_BLOCK_T,
    block_n: int = DEFAULT_BLOCK_N,
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
    return _gmm_pallas_diff(x, w, group_sizes.astype(jnp.int32), block_t, block_n)
