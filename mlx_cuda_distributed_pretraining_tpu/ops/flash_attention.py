"""Tiled flash attention — Pallas TPU kernels, forward + backward.

The reference's "FlashAttention" materializes the full [B,H,S,S] score
matrix ("Simple approach without tiling for now", reference:
models/attention/flash_attention.py:100,134-151). This is the real thing:

- forward: online-softmax accumulation, fp32 statistics and accumulator in
  VMEM scratch, MXU matmuls via ``dot_general(..., preferred_element_type=
  f32)``;
- backward: recomputation-based (saves only O and the logsumexp), split
  into a dQ kernel (walks K/V, dQ in scratch) and a dK/dV kernel (walks
  Q/dO, dK/dV in scratch), the flash-attention-2 decomposition;
- each of the three kernels in one of two forms that :func:`flash_plan`
  picks from the call's shapes while it is traced (:func:`plan_counts`
  tallies which): **resident** where the two operands the kernel walks (K
  and V of one (sequence, KV head); in dK/dV, Q and dO of one query head),
  double-buffered, fit a stated VMEM budget: they enter VMEM once (the
  tiles of the other axis, and in the forward and dQ the G query heads of a
  group, reuse them) and the kernel walks them itself, a loop over
  ``[block, D]`` slices from the first live chunk to the last, so no grid
  step is dead; **streamed** beyond the budget: they enter VMEM one tile a
  grid step via the Pallas pipeline (double-buffered HBM->VMEM DMA), so
  VMEM never holds the whole sequence and max context is bounded by HBM,
  not VMEM;
- block sparsity: per-mask-type tile ranges (causal skips the upper
  triangle, sliding-window skips everything outside the band). The
  resident walk runs from ``lo`` to ``hi`` and nothing else; in the
  streamed grid, skipped tiles are gated with ``pl.when`` AND their index
  maps are clamped into the live range, so the pipeline never fetches a
  tile it will not use. Tiles a canonical mask leaves whole skip the mask
  program: per tile in the streamed kernels, as one unmasked run between
  masked edges in the resident walk. ``block_diffusion`` (two copies of a
  sequence side by side, ops/masks.py) is live in two stretches of a tile's
  row or column, so its plan is a list of segments (:func:`_bd_kv_segments`,
  :func:`_bd_q_segments`): the resident walk runs each in turn over the one
  accumulator, the streamed grid gates and clamps by their union. A segment
  the canonical mask cuts says how the resident walk takes its tiles
  (:class:`_Cut`): the copy of its rows and of its columns is known before
  the tile starts, so its mask is one compare of two block-index vectors and
  no program on ``[block, block]`` lattices; and the noised diagonal, where a
  row sees its own block alone, goes in squares of ``max(128, B')`` down the
  tile's diagonal, on the matching rows of the scratch, where the tile is a
  square of several (512 x 512 tiles at a block length of 4: four squares of
  128, a quarter of the tile's matmuls and exponentials). A mask program of
  the caller's runs whole on every live tile;
- GQA: native — each query head reads its KV group's K/V; dK/dV are
  accumulated per query head and group-reduced outside the kernel;
- masks/score mods are traceable index-lattice functions (ops/masks.py)
  traced INTO the kernel, which is what makes flex_attention.py a thin
  wrapper over the same machinery.

Runs in Pallas interpret mode off-TPU, so the same code path is exercised
by the CPU test suite.

Under a device mesh the call wraps itself in a ``jax.shard_map`` over the
batch and head dims (:func:`_mesh_partition`): GSPMD cannot partition a
Mosaic kernel ("Mosaic kernels cannot be automatically partitioned"), so
left to the partitioner every sharded training config with flash attention
fails to lower on a TPU. The wrap is the same on CPU, where the interpreter
would not need it, so the mesh tests check the program the chip runs.
"""

from __future__ import annotations

import collections
import functools
import itertools
import math
import os
import threading
from typing import Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from .masks import NEG_INF, MaskMod, ScoreMod, block_index

# Lane width of the TPU vector unit: scratch vectors are padded to a full
# register row so stores never touch partial lanes.
_LANES = 128

# Opt-in low-precision matmul modes (model.matmul_precision). None/"fp32"
# is the default fp path; "bf16" casts the attention operands; "int8"
# runs amax/scale-tracked symmetric int8 quantization of q/k/v (per-row
# over the head dim, the same grid as the int8 KV cache quartet).
MATMUL_PRECISIONS = (None, "fp32", "bf16", "int8")


def check_matmul_precision(precision: Optional[str]) -> Optional[str]:
    p = str(precision).lower() if precision is not None else None
    if p in ("", "none", "fp32", "fp"):
        p = None
    if p not in MATMUL_PRECISIONS:
        raise ValueError(f"unknown matmul_precision {precision!r} "
                         f"(expected one of {MATMUL_PRECISIONS})")
    return p


def quantize_operand_int8(x: jnp.ndarray, axis: int = -1) -> jnp.ndarray:
    """amax/scale-tracked int8 matmul operand with a straight-through
    backward.

    Tracks the per-row amax over the contraction dim, scales onto the
    symmetric int8 grid and requantizes: the forward value is EXACTLY
    ``round(x/s) * s`` with ``|round(x/s)| <= 127`` — integer products
    under fp32 accumulation are exact up to 127²·D < 2²⁴ (D <= 1024), so
    the kernel's MXU dot is bit-equivalent to a native int8×int8→int32
    contraction of the tracked values. The backward passes gradients
    straight through to the fp operand (standard STE), keeping the
    recomputation-based flash backward in full precision."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=axis, keepdims=True)
    s = jnp.maximum(amax / 127.0, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / s), -127, 127)
    xq = (q * s).astype(x.dtype)
    return x + jax.lax.stop_gradient(xq - x)


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _vmem_spec(block_shape=None, index_map=None):
    kwargs = {}
    if not _interpret():
        kwargs["memory_space"] = pltpu.VMEM
    if block_shape is None:
        return pl.BlockSpec(**kwargs)
    return pl.BlockSpec(block_shape, index_map, **kwargs)


def _scratch(shape, dtype=jnp.float32):
    return pltpu.VMEM(shape, dtype)


def _compiler_params(n_parallel: int, n_total: int):
    """Mark leading grid dims parallel, trailing (reduction) dims arbitrary
    so Mosaic knows scratch state only flows along the last dim."""
    if _interpret():
        return None
    sem = ("parallel",) * n_parallel + ("arbitrary",) * (n_total - n_parallel)
    return pltpu.CompilerParams(dimension_semantics=sem)


# -- tile-range planners (block sparsity per mask type) ----------------------
def _kv_range(mask_type: str, window: int, prefix_len: int, block_q: int, block_kv: int,
              num_kv_blocks: int):
    """(qi -> lo, qi -> hi) KV-tile bounds for a given query tile.

    ``band`` is the sliding-window left edge alone — valid iff
    ``row - col < window`` with NO causal bound (window may be <= 0):
    the shape of an off-diagonal rotation chunk in sliding-window ring
    attention, where the inter-chunk offset already guarantees causality.
    """

    def lo(qi):
        if mask_type in ("sliding_window", "band"):
            # row_min = qi*bq; cols >= row_min - window + 1 can contribute,
            # but the prefix region [0, prefix) never applies here.
            return jnp.maximum((qi * block_q - window + 1) // block_kv, 0)
        return jnp.int32(0)

    def hi(qi):
        if mask_type in ("causal", "sliding_window"):
            return jnp.minimum(pl.cdiv(qi * block_q + block_q, block_kv), num_kv_blocks)
        if mask_type == "prefix_lm":
            causal_hi = pl.cdiv(qi * block_q + block_q, block_kv)
            return jnp.minimum(jnp.maximum(causal_hi, pl.cdiv(prefix_len, block_kv)), num_kv_blocks)
        return jnp.int32(num_kv_blocks)  # full / band

    return lo, hi


def _q_range(mask_type: str, window: int, prefix_len: int, block_q: int, block_kv: int,
             num_q_blocks: int):
    """(ki -> lo, ki -> hi) Q-tile bounds for a given KV tile (backward)."""

    def lo(ki):
        if mask_type in ("causal", "sliding_window"):
            # first q row that can see this kv tile is its own diagonal row
            return (ki * block_kv) // block_q
        # full / prefix_lm / band: every q tile can reach every kv tile
        # (band: rows below the edge are bounded by hi, not lo)
        return jnp.int32(0)

    def hi(ki):
        if mask_type in ("sliding_window", "band"):
            # rows < col_max + window
            return jnp.maximum(jnp.minimum(
                pl.cdiv(ki * block_kv + block_kv - 1 + window, block_q) + 1,
                num_q_blocks), 0)
        return jnp.int32(num_q_blocks)

    return lo, hi


def _full_tile_fn(mask_type: str, window: int, prefix_len: int,
                  block_q: int, block_kv: int):
    """(qi, j) -> traced bool: is the whole [block_q, block_kv] tile valid
    under the canonical mask? Interior tiles skip the iota/compare/select
    mask work on the VPU entirely (the exp/matmul path is identical), which
    matters because the kernel is VPU-bound between MXU calls — on a causal
    mask roughly half the live tiles are interior. Only canonical masks
    qualify; custom flex mask programs always evaluate in-tile."""
    if mask_type not in ("causal", "sliding_window", "prefix_lm", "band"):
        return None

    def full(qi, j):
        min_row = qi * block_q
        max_row = qi * block_q + block_q - 1
        max_col = j * block_kv + block_kv - 1
        causal_ok = max_col <= min_row
        if mask_type == "causal":
            return causal_ok
        if mask_type == "sliding_window":
            return causal_ok & (max_row - j * block_kv <= window - 1)
        if mask_type == "band":  # row - col < window, no causal bound
            return max_row - j * block_kv <= window - 1
        return causal_ok | (max_col < prefix_len)  # prefix_lm

    return full


def _live_full(i, j, lo, hi, full_tile, live_full):
    """``(live, full | None)`` of step ``j`` of tile ``i``'s streamed walk: by
    the plan's one range and :func:`_full_tile_fn`, or by a plan of segments."""
    if live_full is not None:
        return live_full(i, j)
    return (j >= lo(i)) & (j < hi(i)), full_tile(i, j) if full_tile else None


def _tile_dispatch(live, full, compute, masked):
    """Shared live/interior/edge tile dispatch for all three kernels.

    ``compute(apply_mask)`` runs the tile body; ``full`` is the traced
    is-fully-valid predicate for THIS tile (None = no fast path) and
    ``masked`` whether a mask program exists at all. Interior tiles skip
    the in-tile mask work; edge tiles mask as usual."""
    if not masked or full is None:
        @pl.when(live)
        def _one_path():
            compute(apply_mask=masked)
    else:
        @pl.when(live & full)
        def _interior():
            compute(apply_mask=False)

        @pl.when(live & jnp.logical_not(full))
        def _edge():
            compute(apply_mask=True)


# -- the two-copy mask of diffusion over blocks: a plan of segments ------------
# ``masks.block_diffusion(L, B')`` over rows [noised copy ; clean copy]. A tile
# straddles neither copy nor a block (``L % block == 0``, ``block % B' == 0``:
# ``flash_attention`` sends any other call down the reference path), so with
# ``r`` a tile's first row inside its copy the live tiles of a query tile are
# its own blocks' columns in the noised copy (masked in-tile) and, in the clean
# copy, a run the mask leaves whole and then the tiles its edge cuts; a clean
# query tile has no first part. Along the other axis a clean KV tile is read by
# an edge and a whole run of each copy's query tiles, a noised one by its own
# blocks' rows alone. Each function works on a traced tile index (in a kernel)
# and on a Python one (:func:`block_diffusion_tiles`).
#
# A segment is ``(lo, hi, cut)``. ``cut`` is static and says how the resident
# walk takes the segment's tiles: ``False`` whole, no mask; ``True`` the caller's
# mask program on the tile's index lattices (every live tile, under a program
# that is not the canonical one); a :class:`_Cut` under the canonical mask,
# where the copy of a segment's rows and of its columns is known before the tile
# starts, so the live set is one compare of two block-index vectors, and the
# noised diagonal, which no block crosses at a multiple of ``w``, is ``T / w``
# squares of ``w`` and nothing between them (:func:`_bd_narrow_width`). The
# streamed grid reads ``cut`` as masked or not.
def _cdiv(a, b: int):
    return (a + b - 1) // b


def _where(test, a, b):
    """``jnp.where`` on a traced test, plain choice on a Python one (the host's
    count of a plan, which may run while a step is traced)."""
    return (a if test else b) if isinstance(test, bool) else jnp.where(test, a, b)


class _Cut(NamedTuple):
    """How the resident walk takes a tile the canonical mask cuts."""
    # (rows, cols) -> bool on index vectors, one a column and one a row; None where a
    # square is one block and nothing of it is masked
    live: Optional[Callable]
    width: Optional[int]     # the noised diagonal in squares this wide; None: the tile in one chunk


def _bd_narrow_width(Bp: int, block_q: int, block_kv: int) -> Optional[int]:
    """The width ``w`` of the squares a noised-diagonal tile is walked in, or
    ``None`` for the whole tile at once: ``max(128, B')`` where the tile is a
    square of more than one such ``w`` (no block crosses a multiple of ``w``,
    so rows ``[a w, (a + 1) w)`` see columns ``[a w, (a + 1) w)`` and no other)."""
    w = max(_LANES, Bp)
    return w if block_q == block_kv and w < block_q and block_q % w == 0 and w % Bp == 0 else None


def _bd_cuts(L: int, Bp: int, block_q: int, block_kv: int, canonical: bool):
    """``(own, earlier)``: what a segment on the noised diagonal carries as its
    ``cut``, and ``q_clean -> cut`` for the clean copy's cut tiles."""
    if not canonical:
        return True, lambda q_clean: True
    blk = block_index(Bp)

    def own_live(rows, cols):      # both noised: a row's own block
        return blk(cols) == blk(rows)

    def earlier(q_clean):          # keys clean: every earlier block, and a clean row's own
        def live(rows, cols):
            return blk(cols - L) < blk(rows - _where(q_clean, L, 0)) + _where(q_clean, 1, 0)
        return _Cut(live, None)

    if block_q == Bp == block_kv:  # a tile that is one block
        return False, earlier
    w = _bd_narrow_width(Bp, block_q, block_kv)
    return _Cut(own_live if w != Bp else None, w), earlier


def _bd_kv_segments(L: int, Bp: int, block_q: int, block_kv: int, canonical: bool = True):
    """``qi -> ((lo, hi, cut), ...)``: the KV tiles of query tile ``qi``, in
    ascending order; a segment with ``hi <= lo`` is empty."""
    nL = L // block_kv
    own_cut, earlier = _bd_cuts(L, Bp, block_q, block_kv, canonical)

    def segments(qi):
        row = qi * block_q
        clean = row >= L
        r = _where(clean, row - L, row)
        own = r // block_kv
        whole = nL + _where(clean, (r + Bp) // block_kv, own)
        end = nL + _where(clean, _cdiv(r + block_q, block_kv), _cdiv(r + block_q - Bp, block_kv))
        return ((own, _where(clean, own, _cdiv(r + block_q, block_kv)), own_cut),
                (nL, whole, not canonical), (whole, end, earlier(clean)))

    return segments


def _bd_q_segments(L: int, Bp: int, block_q: int, block_kv: int, canonical: bool = True,
                   resident: bool = True):
    """:func:`_bd_kv_segments` along the other axis: the query tiles of KV
    tile ``ki`` (the backward's dK/dV). The first stretch's cut tiles are a
    noised tile's own blocks' rows, or the edge of the noised rows that read a
    clean tile (empty where a tile is one block): two segments for the
    ``resident`` walk, one of them empty, since each has its own closed form;
    one for the streamed grid, which masks them alike."""
    nL = L // block_q
    own_cut, earlier = _bd_cuts(L, Bp, block_q, block_kv, canonical)

    def segments(ki):
        col = ki * block_kv
        clean = col >= L
        c = _where(clean, col - L, col)
        own_end = _cdiv(c + block_kv, block_q)
        edge = nL + c // block_q                   # a noised tile's second stretch: empty, here
        whole = _where(clean, nL + _cdiv(c + block_kv - Bp, block_q), edge)
        first = _where(clean, (c + Bp) // block_q, c // block_q)
        if resident:
            split = _where(clean, first, own_end)
            head = ((first, split, own_cut), (split, own_end, earlier(False)))
        else:
            head = ((first, own_end, bool(own_cut)),)
        return head + ((own_end, _where(clean, nL, own_end), not canonical),
                       (edge, whole, earlier(True)),
                       (whole, _where(clean, 2 * nL, edge), not canonical))

    return segments


def _in_steps(steps):
    """``chunk(j, mask, square=None)`` from a chunk's body written as a generator
    that yields after its first matmuls and before its last; ``chunk.steps`` is
    the generator, for :func:`_walk_segments` to run several bodies in step."""
    def chunk(*args):
        for _ in steps(*args):
            pass
    chunk.steps = steps
    return chunk


def _walk_segments(chunk, segments, block: int):
    """The resident walk over a plan of segments: the cut ones a chunk a trip,
    the whole ones ``_RESIDENT_UNROLL``, as :func:`_split_walk`; a cut of a
    ``width`` as ``block / width`` squares ``(a, width)``, their bodies in
    step: a square's matmuls are too small to hide its softmax, and one square
    behind the other each waits for its own (forward 9.40 ms a call so, 9.29
    in step, at cell 5's call: my chip runs, PR 50)."""
    for lo, hi, cut in segments:
        if isinstance(cut, _Cut) and cut.width:
            def squares(j, cut, n=block // cut.width):
                for _ in itertools.zip_longest(*(chunk.steps(j, cut, (a, cut.width))
                                                 for a in range(n))):
                    pass
            _walk(squares, lo, hi, cut)
        else:
            _walk(chunk, lo, hi, cut, 1 if cut else _RESIDENT_UNROLL)


def _segment_tests(segments, groups):
    """For the streamed grids, from a plan of segments whose ``groups`` (tuples
    of segment indices) are its two stretches: ``live_full(i, j) -> (tile j is
    live, and whole)`` and ``clamp(i, j) -> the live tile the pipeline holds at
    step j``, which moves only onto a tile that will be used."""
    def live_full(i, j):
        live = full = False
        for lo, hi, masked in segments(i):
            inside = (j >= lo) & (j < hi)
            live = live | inside
            if not masked:
                full = full | inside
        return live, full

    def clamp(i, j):
        segs = segments(i)
        (lo1, hi1), (lo2, hi2) = ((segs[g[0]][0], segs[g[-1]][1]) for g in groups)
        into = lambda lo, hi: jnp.maximum(jnp.minimum(j, hi - 1), lo)
        first = ((j < hi1) & (hi1 > lo1)) | (hi2 <= lo2)
        return jnp.where(first, into(lo1, hi1), into(lo2, hi2))

    return live_full, clamp


_BD_KV_GROUPS, _BD_Q_GROUPS = ((0,), (1, 2)), ((0, 1), (2, 3))


def _bd_segments(mask_type: str, window: int, prefix_len: int, block_q: int, block_kv: int,
                 canonical: bool, axis: str, resident: bool = True):
    """The segment plan of a call, ``None`` for every mask but
    ``block_diffusion`` (whose ``window`` is the block length and whose
    ``prefix_len`` the rows of one copy). ``axis``: ``"kv"`` | ``"q"``."""
    if mask_type != "block_diffusion":
        return None
    if axis == "kv":
        return _bd_kv_segments(prefix_len, window, block_q, block_kv, canonical)
    return _bd_q_segments(prefix_len, window, block_q, block_kv, canonical, resident)


# What the last forward traced under ``block_diffusion`` visits, a head: a
# model's step reports it beside its other counters (models/sdar.py).
_bd_tiles_traced = {"live": 0, "grid": 0, "masked": 0, "narrow": 0}


def _note_bd_tiles(walk: Dict[str, int]) -> None:
    with _plan_counts_lock:
        _bd_tiles_traced.update(walk)


def bd_tiles_traced() -> Dict[str, int]:
    """``{"live", "grid", "masked", "narrow"}``: tiles the last traced
    ``block_diffusion`` forward computes a head, tiles of its whole grid, and of
    the live ones those whose mask runs over the whole tile and those walked in
    narrower squares (:func:`block_diffusion_walk`; all 0: none traced)."""
    with _plan_counts_lock:
        return dict(_bd_tiles_traced)


def _streamed_segments(mask_type, window, prefix_len, block_q, block_kv, canonical, axis):
    """``(live_full, clamp)`` of a streamed call (:func:`_segment_tests`), a
    pair of ``None`` for a mask with no segment plan."""
    segments = _bd_segments(mask_type, window, prefix_len, block_q, block_kv, canonical, axis,
                            resident=False)
    if segments is None:
        return None, None
    return _segment_tests(segments, _BD_KV_GROUPS if axis == "kv" else _BD_Q_GROUPS)


def block_diffusion_tiles(L: int, Bp: int, block_q: int, block_kv: int):
    """The forward's plan on the host: ``[2L / block_q, 2L / block_kv]`` int8,
    0 a tile no kernel visits, 1 one masked in-tile, 2 one left whole (the
    classes of ``masks.block_mask_map``)."""
    out = np.zeros((2 * L // block_q, 2 * L // block_kv), np.int8)
    segments = _bd_kv_segments(L, Bp, block_q, block_kv)
    for qi in range(out.shape[0]):
        for lo, hi, masked in segments(qi):
            out[qi, int(lo):max(int(hi), int(lo))] = 1 if masked else 2
    return out


def block_diffusion_walk(L: int, Bp: int, block_q: int, block_kv: int, canonical: bool = True,
                         resident: bool = True) -> Dict[str, int]:
    """How the forward walks its plan, in tiles a head: ``live`` of ``grid``
    visited; ``masked`` of them under a mask over the whole tile (the
    caller's program, or a cut's closed form); ``narrow`` the noised-diagonal
    tiles the resident walk takes in squares of :func:`_bd_narrow_width`, which
    the streamed grid masks whole like the others."""
    count = dict(live=0, grid=(2 * L // block_q) * (2 * L // block_kv), masked=0, narrow=0)
    segments = _bd_kv_segments(L, Bp, block_q, block_kv, canonical)
    for qi in range(2 * L // block_q):
        for lo, hi, cut in segments(qi):
            tiles = max(int(hi) - int(lo), 0)
            count["live"] += tiles
            if cut:
                count["narrow" if resident and isinstance(cut, _Cut) and cut.width
                      else "masked"] += tiles
    return count


# -- forward kernel ----------------------------------------------------------
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr, *,
                scale, mask_fn, score_fn, kv_lo, kv_hi, nkv, full_tile=None, live_full=None):
    j = pl.program_id(3)
    qi = pl.program_id(2)
    h = pl.program_id(1)
    bq = q_ref.shape[2]
    bkv = k_ref.shape[2]

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    def _compute(apply_mask):
        # Matmul operands stay in their storage dtype (bf16 in training) so
        # the MXU runs at full rate; accumulation is fp32.
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if score_fn is not None or apply_mask:
            row = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
            col = j * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
            if score_fn is not None:
                s = score_fn(s, row, col, h)
            if apply_mask:
                s = jnp.where(mask_fn(row, col), s, NEG_INF)
        m = m_scr[:, 0:1]                                    # [bq, 1]
        l = l_scr[:, 0:1]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    _tile_dispatch(*_live_full(qi, j, kv_lo, kv_hi, full_tile, live_full),
                   _compute, mask_fn is not None)

    @pl.when(j == nkv - 1)
    def _finalize():
        m = m_scr[:, 0]
        l = l_scr[:, 0]
        l_safe = jnp.maximum(l, 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l_safe[:, None]).astype(o_ref.dtype)
        # lse is laid out [B, H, 1, Sq]: the singleton dim keeps the block's
        # second-to-last dim equal to the array dim, satisfying TPU (8, 128)
        # tiling without padding lse out to 128 lanes.
        lse_ref[0, 0, 0] = (m + jnp.log(l_safe)).astype(lse_ref.dtype)


# -- resident-KV forward kernel ----------------------------------------------
_NO_FULL_RANGE = ("full", "block_diffusion")  # nothing masked | a plan of segments instead


def _full_range(mask_type: str, window: int, prefix_len: int,
                block_q: int, block_kv: int):
    """``(a, b)``: the chunks ``a(qi) <= j < b(qi)`` of a query tile's KV
    walk are the ones :func:`_full_tile_fn` calls fully valid: one run for
    every canonical mask, so the walk is masked edge, unmasked interior,
    masked edge with no test per chunk. ``None`` for ``a`` means no masked
    left edge (the run starts where the walk does), for ``b`` no right one.
    The caller clamps both into the walk's ``[lo, hi)``."""
    def causal_b(qi):  # (j + 1) * block_kv - 1 <= qi * block_q
        return (qi * block_q + 1) // block_kv

    def window_a(qi):  # max_row - j * block_kv <= window - 1
        return pl.cdiv(qi * block_q + block_q - window, block_kv)

    return {
        "causal": (None, causal_b),
        "sliding_window": (window_a, causal_b),
        "band": (window_a, None),
        "prefix_lm": (None, lambda qi: jnp.maximum(causal_b(qi), prefix_len // block_kv)),
    }[mask_type]


def _lane_tile(x, n: int):
    """A lane-replicated ``[rows, _LANES]`` statistic as ``[rows, n]``:
    whole registers side by side, no cross-lane broadcast."""
    if n == _LANES:
        return x
    if n < _LANES:
        return x[:, :n]
    if n % _LANES == 0:
        return jnp.concatenate([x] * (n // _LANES), axis=1)
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _first(i, block: int, off: int = 0):
    """The first index of tile ``i``'s part from ``off`` on."""
    return i * block + off if off else i * block


def _chunk_live(mask, mask_fn, row, col, row0, col0, rows: int, cols: int, k_major=False):
    """The live set of one chunk's scores (``None``: all of them). ``mask`` is
    how the plan walks the chunk: ``True`` the mask program on the index
    lattices ``row``, ``col``; a :class:`_Cut` its closed form on two index
    vectors, built here from the chunk's first row and column (``row0()``,
    ``col0()``) for ``rows`` x ``cols`` scores, held ``[cols, rows]`` where
    ``k_major``."""
    if not isinstance(mask, _Cut):
        return mask_fn(row, col) if mask else None
    if mask.live is None:
        return None
    r_shape, c_shape = ((1, rows), (cols, 1)) if k_major else ((rows, 1), (1, cols))
    return mask.live(row0() + jax.lax.broadcasted_iota(jnp.int32, r_shape, int(k_major)),
                     col0() + jax.lax.broadcasted_iota(jnp.int32, c_shape, int(not k_major)))


def _walk(chunk, lo, hi, apply_mask, unroll=1):
    """``chunk(j, apply_mask)`` for ``lo <= j < hi`` in order (none where
    ``hi <= lo``). The bounds are traced, so an unroll is by hand: ``unroll``
    chunks a trip, which lets the scheduler run one chunk's matmuls against
    its neighbour's elementwise work, then the remainder one at a time."""
    trips = jnp.maximum(hi - lo, 0) // unroll

    def trip(t, carry):
        for u in range(unroll):
            chunk(lo + t * unroll + u, apply_mask)
        return carry

    jax.lax.fori_loop(0, trips, trip, None)
    if unroll > 1:
        _walk(chunk, lo + trips * unroll, hi, apply_mask)


def _split_walk(chunk, lo, hi, i, full_range, masked):
    """The walk of grid step ``i`` over ``[lo, hi)``, for all three resident
    kernels: where ``full_range`` (:func:`_full_range`) names the run of
    chunks the mask leaves whole, a masked edge, the unmasked run, a masked
    edge, with no test per chunk; else every chunk alike. The unmasked run
    goes ``_RESIDENT_UNROLL`` chunks a trip."""
    if not masked or full_range is None:
        _walk(chunk, lo, hi, masked, _RESIDENT_UNROLL)
        return
    a_fn, b_fn = full_range
    a = lo if a_fn is None else jnp.clip(a_fn(i), lo, hi)
    b = hi if b_fn is None else jnp.clip(b_fn(i), a, hi)
    if a_fn is not None:
        _walk(chunk, lo, a, True)
    _walk(chunk, a, b, False, _RESIDENT_UNROLL)
    if b_fn is not None:
        _walk(chunk, b, hi, True)


def _fwd_resident_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr, *,
                         scale, mask_fn, score_fn, kv_lo, kv_hi, bkv, full_range=None,
                         segments=None):
    """One query tile against the whole K/V of its KV head, which the
    pipeline holds in VMEM: the KV walk is a loop in here over ``[bkv, D]``
    slices of the refs, from ``kv_lo(qi)`` to ``kv_hi(qi)`` in ascending
    order, so no grid step is dead and K/V are fetched once a KV head. The
    running max and denominator stay lane-replicated ``[bq, _LANES]``."""
    qi = pl.program_id(2)
    h = pl.program_id(1)
    bq, D = q_ref.shape[2], v_ref.shape[3]  # the accumulator is as wide as v
    m_scr[...] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
    l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
    acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)
    q = q_ref[0, 0]

    @_in_steps
    def chunk(j, mask, square=None):
        # ``square`` (a, w): the tile's rows [a w, (a + 1) w) against the same
        # columns of chunk j and no other, on their rows of the three scratches
        if square is None:
            tile, n, off, q_rows = ..., bkv, 0, q
        else:
            n, off = square[1], square[0] * square[1]
            tile = pl.ds(off, n)
            q_rows = q_ref[0, 0, tile, :]
        row0, col0 = (lambda: _first(qi, bq, off)), (lambda: _first(j, bkv, off))
        cols = pl.ds(pl.multiple_of(col0(), n), n)
        k = k_ref[0, 0, cols, :]
        v = v_ref[0, 0, cols, :]
        s = jax.lax.dot_general(q_rows, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        yield
        rows, row, col = s.shape[0], None, None
        if score_fn is not None or mask is True:
            row = row0() + jax.lax.broadcasted_iota(jnp.int32, (rows, n), 0)
            col = col0() + jax.lax.broadcasted_iota(jnp.int32, (rows, n), 1)
            if score_fn is not None:
                s = score_fn(s, row, col, h)
        live = _chunk_live(mask, mask_fn, row, col, row0, col0, rows, n)
        if live is not None:
            s = jnp.where(live, s, NEG_INF)
        m = m_scr[tile]                                      # [bq, _LANES]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - _lane_tile(m_new, n))
        alpha = jnp.exp(m - m_new)
        l_scr[tile] = alpha * l_scr[tile] + jnp.sum(p, axis=-1, keepdims=True)
        yield
        acc_scr[tile] = acc_scr[tile] * _lane_tile(alpha, D) + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[tile] = m_new

    if segments is not None:
        _walk_segments(chunk, segments(qi), bq)
    else:
        _split_walk(chunk, kv_lo(qi), kv_hi(qi), qi, full_range, mask_fn is not None)

    l_safe = jnp.maximum(l_scr[...], 1e-30)
    o_ref[0, 0] = (acc_scr[...] / _lane_tile(l_safe, D)).astype(o_ref.dtype)
    # lse is laid out [B, H, 1, Sq], as the streamed kernel writes it.
    lse_ref[0, 0, 0] = (m_scr[:, 0] + jnp.log(l_safe[:, 0])).astype(lse_ref.dtype)


# -- backward kernels --------------------------------------------------------
def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_scr, *,
                   scale, mask_fn, score_fn, kv_lo, kv_hi, nkv, full_tile=None,
                   live_full=None):
    j = pl.program_id(3)
    qi = pl.program_id(2)
    h = pl.program_id(1)
    bq = q_ref.shape[2]
    bkv = k_ref.shape[2]

    @pl.when(j == 0)
    def _init():
        dq_scr[...] = jnp.zeros(dq_scr.shape, jnp.float32)

    def _compute(apply_mask):
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0, 0].astype(jnp.float32)
        delta = delta_ref[0, 0, 0].astype(jnp.float32)
        s_raw = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) * scale
        if score_fn is not None or apply_mask:
            row = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
            col = j * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
        s = score_fn(s_raw, row, col, h) if score_fn is not None else s_raw
        if apply_mask:
            s = jnp.where(mask_fn(row, col), s, NEG_INF)
        p = jnp.exp(s - lse[:, None])
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None])
        d_mod = getattr(score_fn, "_d_score", None) if score_fn is not None else None
        if d_mod is not None:  # non-additive score mod: chain through its Jacobian
            ds = ds * d_mod(s_raw, row, col, h)
        ds = ds * scale
        dq_scr[...] = dq_scr[...] + jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _tile_dispatch(*_live_full(qi, j, kv_lo, kv_hi, full_tile, live_full),
                   _compute, mask_fn is not None)

    @pl.when(j == nkv - 1)
    def _finalize():
        dq_ref[0, 0] = dq_scr[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
                    dk_scr, dv_scr, *, scale, mask_fn, score_fn, q_lo, q_hi, nq,
                    full_tile=None, live_full=None):
    j = pl.program_id(3)   # q tile (streamed)
    ki = pl.program_id(2)  # kv tile (resident)
    h = pl.program_id(1)
    bq = q_ref.shape[2]
    bkv = k_ref.shape[2]

    @pl.when(j == 0)
    def _init():
        dk_scr[...] = jnp.zeros(dk_scr.shape, jnp.float32)
        dv_scr[...] = jnp.zeros(dv_scr.shape, jnp.float32)

    def _compute(apply_mask):
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        q = q_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0, 0].astype(jnp.float32)
        delta = delta_ref[0, 0, 0].astype(jnp.float32)
        s_raw = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) * scale
        if score_fn is not None or apply_mask:
            row = j * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
            col = ki * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
        s = score_fn(s_raw, row, col, h) if score_fn is not None else s_raw
        if apply_mask:
            s = jnp.where(mask_fn(row, col), s, NEG_INF)
        p = jnp.exp(s - lse[:, None])
        dv_scr[...] = dv_scr[...] + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None])
        d_mod = getattr(score_fn, "_d_score", None) if score_fn is not None else None
        if d_mod is not None:
            ds = ds * d_mod(s_raw, row, col, h)
        ds = ds * scale
        dk_scr[...] = dk_scr[...] + jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    # Tile geometry here is (q tile j, kv tile ki): full_tile takes
    # (query tile, kv tile) in that order.
    _tile_dispatch(*_live_full(ki, j, q_lo, q_hi,
                               (lambda i, jj: full_tile(jj, i)) if full_tile else None, live_full),
                   _compute, mask_fn is not None)

    @pl.when(j == nq - 1)
    def _finalize():
        dk_ref[0, 0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


# -- resident backward kernels -------------------------------------------------
def _full_range_q(mask_type: str, window: int, prefix_len: int,
                  block_q: int, block_kv: int):
    """:func:`_full_range` along the other axis: the query chunks
    ``a(ki) <= j < b(ki)`` of a KV tile's walk that :func:`_full_tile_fn`
    calls fully valid, again one run for every canonical mask."""
    def causal_a(ki):  # j * block_q >= ki * block_kv + block_kv - 1
        return pl.cdiv(ki * block_kv + block_kv - 1, block_q)

    def window_b(ki):  # j * block_q + block_q - 1 - ki * block_kv <= window - 1
        return (window - block_q + ki * block_kv) // block_q + 1

    def prefix_a(ki):  # a KV tile inside the prefix is whole for every query
        return jnp.where(ki * block_kv + block_kv - 1 < prefix_len, 0, causal_a(ki))

    return {
        "causal": (causal_a, None),
        "sliding_window": (causal_a, window_b),
        "band": (None, window_b),
        "prefix_lm": (prefix_a, None),
    }[mask_type]


def _bwd_p_ds(s_raw, dp, lse, delta, row, col, h, *, scale, score_fn, live):
    """``(p, ds)`` of one tile from its raw scores and ``dp = dO V^T``, in
    whichever orientation the caller holds them: ``lse`` and ``delta``
    broadcast against the tile, ``row``/``col`` are its index lattices and
    ``live()`` its live set (:func:`_chunk_live`)."""
    s = score_fn(s_raw, row, col, h) if score_fn is not None else s_raw
    live = live()
    if live is not None:
        s = jnp.where(live, s, NEG_INF)
    p = jnp.exp(s - lse)
    ds = p * (dp - delta)
    d_mod = getattr(score_fn, "_d_score", None) if score_fn is not None else None
    if d_mod is not None:  # non-additive score mod: chain through its Jacobian
        ds = ds * d_mod(s_raw, row, col, h)
    return p, ds * scale


def _bwd_dq_resident_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_scr, *,
                            scale, mask_fn, score_fn, kv_lo, kv_hi, bkv, full_range=None,
                            segments=None):
    """dQ of one query tile against the whole K/V of its KV head, held in
    VMEM as :func:`_fwd_resident_kernel` holds them: q, dO and the tile's
    ``lse`` and ``delta`` are read once, the two statistics re-laid from
    lanes to sublanes once, and the KV walk is a loop in here."""
    qi = pl.program_id(2)
    h = pl.program_id(1)
    bq = q_ref.shape[2]
    dq_scr[...] = jnp.zeros(dq_scr.shape, jnp.float32)
    q = q_ref[0, 0]
    do = do_ref[0, 0]
    lse = lse_ref[0, 0, 0].astype(jnp.float32)[:, None]       # [bq, 1]
    delta = delta_ref[0, 0, 0].astype(jnp.float32)[:, None]

    @_in_steps
    def chunk(j, mask, square=None):
        # ``square`` (a, w): as the forward's, on rows [a w, (a + 1) w) of dq_scr
        if square is None:
            tile, n, off = ..., bkv, 0
            q_rows, do_rows, lse_rows, delta_rows = q, do, lse, delta
        else:
            n, off = square[1], square[0] * square[1]
            tile = pl.ds(off, n)
            q_rows, do_rows = q_ref[0, 0, tile, :], do_ref[0, 0, tile, :]
            lse_rows, delta_rows = lse[off:off + n], delta[off:off + n]
        row0, col0 = (lambda: _first(qi, bq, off)), (lambda: _first(j, bkv, off))
        cols = pl.ds(pl.multiple_of(col0(), n), n)
        k = k_ref[0, 0, cols, :]
        v = v_ref[0, 0, cols, :]
        s_raw = jax.lax.dot_general(q_rows, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) * scale
        dp = jax.lax.dot_general(do_rows, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        yield
        rows, row, col = s_raw.shape[0], None, None
        if score_fn is not None or mask is True:
            row = row0() + jax.lax.broadcasted_iota(jnp.int32, (rows, n), 0)
            col = col0() + jax.lax.broadcasted_iota(jnp.int32, (rows, n), 1)
        _, ds = _bwd_p_ds(s_raw, dp, lse_rows, delta_rows, row, col, h, scale=scale,
                          score_fn=score_fn,
                          live=lambda: _chunk_live(mask, mask_fn, row, col, row0, col0, rows, n))
        yield
        dq_scr[tile] = dq_scr[tile] + jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if segments is not None:
        _walk_segments(chunk, segments(qi), bq)
    else:
        _split_walk(chunk, kv_lo(qi), kv_hi(qi), qi, full_range, mask_fn is not None)
    dq_ref[0, 0] = dq_scr[...].astype(dq_ref.dtype)


def _bwd_dkv_resident_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
                             dk_scr, dv_scr, *, scale, mask_fn, score_fn, q_lo, q_hi,
                             bq, full_range=None, segments=None):
    """dK and dV of one KV tile against the whole Q and dO of one query
    head, held in VMEM with that head's ``lse`` and ``delta``; the query walk
    is a loop in here. The tile is worked K-major: scores as ``[bkv, bq]``,
    so ``lse`` and ``delta`` broadcast along sublanes from the ``[1, bq]``
    layout they come in and no operand of the four matmuls is transposed."""
    ki = pl.program_id(2)
    h = pl.program_id(1)
    bkv = k_ref.shape[2]
    dk_scr[...] = jnp.zeros(dk_scr.shape, jnp.float32)
    dv_scr[...] = jnp.zeros(dv_scr.shape, jnp.float32)
    k = k_ref[0, 0]
    v = v_ref[0, 0]

    @_in_steps
    def chunk(j, mask, square=None):
        # ``square`` (a, w): the tile's columns [a w, (a + 1) w), on their rows of
        # dk_scr and dv_scr, against the same w query rows of chunk j and no other
        if square is None:
            tile, n, off, k_cols, v_cols = ..., bq, 0, k, v
        else:
            n, off = square[1], square[0] * square[1]
            tile = pl.ds(off, n)
            k_cols, v_cols = k_ref[0, 0, tile, :], v_ref[0, 0, tile, :]
        row0, col0 = (lambda: _first(j, bq, off)), (lambda: _first(ki, bkv, off))
        rows = pl.ds(pl.multiple_of(row0(), n), n)
        q = q_ref[0, 0, rows, :]
        do = do_ref[0, 0, rows, :]
        s_raw = jax.lax.dot_general(k_cols, q, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) * scale
        dp = jax.lax.dot_general(v_cols, do, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        yield
        cols, row, col = s_raw.shape[0], None, None
        if score_fn is not None or mask is True:
            row = row0() + jax.lax.broadcasted_iota(jnp.int32, (cols, n), 1)
            col = col0() + jax.lax.broadcasted_iota(jnp.int32, (cols, n), 0)
        p, ds = _bwd_p_ds(s_raw, dp, lse_ref[0, 0, :, rows].astype(jnp.float32),
                          delta_ref[0, 0, :, rows].astype(jnp.float32), row, col, h,
                          scale=scale, score_fn=score_fn,
                          live=lambda: _chunk_live(mask, mask_fn, row, col, row0, col0, n, cols,
                                                   k_major=True))
        yield
        dv_scr[tile] = dv_scr[tile] + jax.lax.dot_general(
            p.astype(do.dtype), do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_scr[tile] = dk_scr[tile] + jax.lax.dot_general(
            ds.astype(q.dtype), q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if segments is not None:
        _walk_segments(chunk, segments(ki), bkv)
    else:
        _split_walk(chunk, q_lo(ki), q_hi(ki), ki, full_range, mask_fn is not None)
    dk_ref[0, 0] = dk_scr[...].astype(dk_ref.dtype)
    dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


def fit_block(block: int, dim: int) -> int:
    """Largest power-of-two block <= requested that divides the sequence;
    128 is the TPU lane width / minimum tile. May still fail to divide for
    dims like 192 — callers must check ``dim % fit_block(...) == 0`` and
    fall back to a non-Pallas path."""
    while block > 128 and dim % block:
        block //= 2
    return min(block, dim)


def _check_divisible(Sq, bq, Skv, bkv):
    if Sq % bq or Skv % bkv:
        raise ValueError(
            f"flash kernels need block-divisible sequences: Sq={Sq} % bq={bq}"
            f" or Skv={Skv} % bkv={bkv} != 0 — pass fitted blocks "
            "(fit_block) or use the reference path")


# -- the plan: which kernel, which blocks --------------------------------------
class FlashPlan(NamedTuple):
    path: str       # "resident" | "streamed" | "reference"
    block_q: int    # query rows: a grid step's tile, or a loop chunk of the resident dK/dV
    block_kv: int   # KV columns: a grid step's tile, or a loop chunk of the resident forward and dQ


# Blocks where the caller names none. Streamed: (256, 512), the pre-ledger
# default, for all three kernels. Resident: (512, 512) and two chunks a loop
# trip, for all three, from these sweeps of each kernel alone on a TPU v5e
# (ms a call, causal, bf16). The forward (my chip runs, PR 25:
# scripts/bench_attention.py --forward-only; resident 512x512 and streamed
# 256x512 give the same bits):
#
#   B x S, heads, D            streamed   resident, block_q x block_kv
#                              256x512    256x512  512x512  512x1024  1024x512
#   4 x 4,096, 32/8, 128       13.55      5.96     5.63     6.24      6.16
#   8 x 2,048, 16/16, 128       4.12      1.95     1.79     2.11      2.00
#   8 x 2,048, 12/4, 64         2.95      1.45     1.34     1.57      1.49
#   16 x 1,024, 16/16, 128      2.62      1.52     1.36     1.60      1.50
#   2 x 8,192, 32/8, 128       24.50      9.46     9.05     9.62      9.75
#   1 x 16,384, 32/8, 128      45.69     16.39    15.88    16.35     16.91
#   4 x 4,096, window 512       7.61      3.85     3.49     4.37      4.23
#
# Also at 4 x 4,096: 256x256 9.34, 128x512 8.07, 256x1024 6.41, 1024x1024
# 6.20, 256x2048 7.68; at 512x512 one chunk a trip 5.70 (256x512: 6.97
# against 5.96) and four 5.59; the statistics as loop carries instead of
# scratch 7.02; exp2 with the scale folded in 5.64 (no gain: not VPU-bound).
#
# dQ | dK/dV (my chip runs, PR 28: scripts/bench_attention.py --backward-only;
# resident 512x512 dQ and streamed 256x512 give the same bits, dK/dV differ in
# the last place of bf16: it sums 512 query rows a chunk, K-major):
#
#   4 x 4,096, 32/8, 128     9.09|13.35  7.09| 8.18  6.52| 7.89  6.93| 8.13  6.84| 8.25
#   8 x 2,048, 16/16, 128    2.79| 3.74  2.27| 2.36  2.04| 2.30  2.28| 2.54  2.24| 2.58
#   8 x 2,048, 12/4, 64      1.88| 2.88  1.64| 1.72  1.48| 1.64  1.67| 1.86  1.63| 1.89
#   16 x 1,024, 16/16, 128   1.62| 2.24  1.67| 1.57  1.48| 1.50  1.67| 1.71  1.60| 1.84
#   2 x 8,192, 32/8, 128    15.95|24.94 11.84|14.37 10.99|13.78 11.26|13.92 11.17|14.17
#   1 x 16,384, 32/8, 128   29.24|47.47 21.32|26.66 19.92|25.55 19.92|25.62 19.83|25.71
#   4 x 4,096, window 512    5.83| 8.75  4.15| 5.01  3.73| 5.34  4.59| 6.12  4.54| 6.98
#
# Also at 4 x 4,096: one chunk a trip 6.63|7.99, three 6.48|7.85, four
# 6.51|7.88; 256x1024 7.36|8.31, 1024x1024 6.69|8.00 (one a trip), 512x256
# 8.09|8.72, 256x256 9.85|9.48; dK/dV with the tile Q-major (p and ds
# contracted over their first axis, lse[:, None] a chunk, as the streamed
# kernel has it) 8.01 at 512x512 and 9.37 at 256x512; dQ's lse and delta
# lane-replicated in scratch instead of [block_q, 1] values: the same to
# 0.01 ms. Past the budget, 1 x 32,768 streamed: 127.96|197.05 at (256, 512),
# 79.52|103.92 at (1024, 1024).
_STREAMED_BLOCKS = (256, 512)
_RESIDENT_BLOCKS = (512, 512)
_RESIDENT_UNROLL = 2
# float32 [block_q, block_kv] arrays a loop chunk keeps live: scores and
# probabilities in the forward; in the backward dp and ds beside them.
_CHUNK_TEMPS = {"flash_fwd": 2, "flash_bwd_dq": 4, "flash_bwd_dkv": 4}
# A resident call raises Mosaic's scoped VMEM limit to this (a v5e core has
# 128 MiB), and takes the path only where the two operands it holds (K and V
# of a KV head; Q and dO of a query head in dK/dV, with that head's lse and
# delta rows), double-buffered by the pipeline, plus a chunk's float32
# temporaries stay under the budget; the rest is for the tile's own blocks,
# the scratch and what the compiler spills (0.66 MiB in the forward at 256x512
# by Mosaic's own count). At heads of 128 in bf16 that admits 16,384 positions
# (16 MiB; the forward resident 15.9 ms against 45.7 streamed, table above)
# and not 32,768, whose 32 MiB Mosaic refuses under this limit.
_RESIDENT_VMEM_LIMIT = 32 * 2**20
_RESIDENT_VMEM_BUDGET = 24 * 2**20


def _plan_blocks(default, Sq, Skv, block_q, block_kv) -> Tuple[int, int]:
    return (min(block_q, Sq) if block_q else fit_block(default[0], Sq),
            min(block_kv, Skv) if block_kv else fit_block(default[1], Skv))


def flash_plan(Sq: int, Skv: int, D: int, dtype, block_q: Optional[int] = None,
               block_kv: Optional[int] = None, kernel: str = "flash_fwd",
               Dv: Optional[int] = None) -> FlashPlan:
    """Which kernel a call of these shapes runs, and its blocks, for
    ``kernel`` ``flash_fwd``, ``flash_bwd_dq`` or ``flash_bwd_dkv``; a pure
    function of its arguments. ``resident`` holds in VMEM the two operands
    the kernel streams (K and V; in dK/dV, Q and dO) and walks them inside
    the kernel; ``streamed`` fetches one tile of them a grid step, so its
    context is bounded by HBM and not by VMEM; ``reference`` (no kernel) is
    for sequences no block divides. A block the caller names is taken as
    given (capped at the sequence); one left ``None`` is the path's default,
    fitted to the sequence. ``D`` is the head size of q and k, ``Dv`` that
    of v and dO where it differs (latent attention: 192 and 128): of the two
    operands a kernel holds, one is ``D`` wide and the other ``Dv``."""
    bq, bkv = _plan_blocks(_RESIDENT_BLOCKS, Sq, Skv, block_q, block_kv)
    if Sq % bq == 0 and Skv % bkv == 0:
        # VMEM pads a head dim to whole registers; two operands, two buffers each
        lanes = sum(-(-d // _LANES) * _LANES for d in (D, D if Dv is None else Dv))
        row_bytes = 2 * lanes * jnp.dtype(dtype).itemsize
        if kernel == "flash_bwd_dkv":
            # Q and dO, and the [1, Sq] float32 rows of lse and delta, which
            # VMEM pads to 8 sublanes
            held_bytes = Sq * (row_bytes + 2 * 2 * 8 * 4)
        else:
            held_bytes = Skv * row_bytes
        chunk_bytes = _CHUNK_TEMPS[kernel] * bq * bkv * 4
        if held_bytes + chunk_bytes <= _RESIDENT_VMEM_BUDGET:
            return FlashPlan("resident", bq, bkv)
    bq, bkv = _plan_blocks(_STREAMED_BLOCKS, Sq, Skv, block_q, block_kv)
    if Sq % bq or Skv % bkv:
        return FlashPlan("reference", bq, bkv)
    return FlashPlan("streamed", bq, bkv)


# The path is chosen while tracing, so this counts traces, not calls of the
# compiled step: what a jitted program runs is what its one trace counted.
_PLAN_KEYS = ("resident", "streamed", "reference", "bwd_dq_resident", "bwd_dq_streamed",
              "bwd_dkv_resident", "bwd_dkv_streamed")
_plan_counts: Dict[str, int] = collections.Counter()
_plan_counts_lock = threading.Lock()


def _count_plan(path: str, kernel: str = "flash_fwd") -> None:
    key = path if kernel == "flash_fwd" else f"{kernel[len('flash_'):]}_{path}"
    with _plan_counts_lock:
        _plan_counts[key] += 1


def plan_counts() -> Dict[str, int]:
    """Kernel calls traced so far in this process: the forward's by path
    (``reference``: no kernel, forward or backward), the two backward
    kernels' by kernel and path."""
    with _plan_counts_lock:
        return {key: _plan_counts[key] for key in _PLAN_KEYS}


def _traced_plan(kernel, q, k, v, block_q, block_kv, _path) -> FlashPlan:
    """The plan of one raw call, tallied; ``_path`` (the tests') overrides
    the choice and takes that path's default blocks."""
    Sq, D = q.shape[2:]
    Skv = k.shape[2]
    held = q if kernel == "flash_bwd_dkv" else k
    plan = flash_plan(Sq, Skv, D, held.dtype, block_q, block_kv, kernel, Dv=v.shape[3])
    if _path is not None and _path != plan.path:
        default = _RESIDENT_BLOCKS if _path == "resident" else _STREAMED_BLOCKS
        plan = FlashPlan(_path, *_plan_blocks(default, Sq, Skv, block_q, block_kv))
    _check_divisible(Sq, plan.block_q, Skv, plan.block_kv)
    _count_plan(plan.path, kernel)
    return plan


def _resident_params():
    return None if _interpret() else pltpu.CompilerParams(
        dimension_semantics=("parallel",) * 3, vmem_limit_bytes=_RESIDENT_VMEM_LIMIT)


# -- raw kernel entry points (reused by ring attention) ----------------------
def flash_fwd(q, k, v, *, mask_fn=None, score_fn=None, mask_type="causal",
              window=512, prefix_len=0, block_q=None, block_kv=None, scale=1.0,
              canonical_mask=False, _path=None):
    """Raw tiled forward on [B, H, S, D] layout. Returns ``(o, lse)`` with
    lse laid out [B, Hq, 1, Sq]. Building block for the custom-vjp wrapper
    and for ring attention's per-chunk calls. ``canonical_mask`` asserts
    that ``mask_fn`` computes exactly the ``mask_type`` predicate, enabling
    the interior-tile fast path (skip in-tile masking where the tile is
    provably fully valid). :func:`flash_plan` picks the kernel from the
    shapes; ``_path`` is for tests, which run both on one input."""
    plan = _traced_plan("flash_fwd", q, k, v, block_q, block_kv, _path)
    if mask_type == "block_diffusion":
        _note_bd_tiles(block_diffusion_walk(prefix_len, window, plan.block_q, plan.block_kv,
                                            canonical_mask, plan.path == "resident"))
    fwd = _flash_fwd_resident if plan.path == "resident" else _flash_fwd_streamed
    return fwd(q, k, v, plan.block_q, plan.block_kv, mask_fn=mask_fn, score_fn=score_fn,
               mask_type=mask_type, window=window, prefix_len=prefix_len,
               scale=scale, canonical_mask=canonical_mask)


def _flash_fwd_resident(q, k, v, bq, bkv, *, mask_fn, score_fn, mask_type,
                        window, prefix_len, scale, canonical_mask):
    B, Hq, Sq, D = q.shape
    _, Hkv, Skv, _ = k.shape
    Dv = v.shape[3]
    G = Hq // Hkv
    kv_lo, kv_hi = _kv_range(mask_type, window, prefix_len, bq, bkv, Skv // bkv)
    full_range = (_full_range(mask_type, window, prefix_len, bq, bkv)
                  if canonical_mask and mask_type not in _NO_FULL_RANGE else None)
    segments = _bd_segments(mask_type, window, prefix_len, bq, bkv, canonical_mask, "kv")
    kernel = functools.partial(
        _fwd_resident_kernel, scale=scale, mask_fn=mask_fn, score_fn=score_fn,
        kv_lo=kv_lo, kv_hi=kv_hi, bkv=bkv, full_range=full_range, segments=segments)

    def kv_index(b, h, i):
        # Not a function of the query tile, and the same for the G heads of
        # a group: the pipeline fetches K and V once a (sequence, KV head).
        return (b, h // G, 0, 0)

    return pl.pallas_call(
        kernel,
        grid=(B, Hq, Sq // bq),
        in_specs=[
            _vmem_spec((1, 1, bq, D), lambda b, h, i: (b, h, i, 0)),
            _vmem_spec((1, 1, Skv, D), kv_index),
            _vmem_spec((1, 1, Skv, Dv), kv_index),
        ],
        out_specs=[
            _vmem_spec((1, 1, bq, Dv), lambda b, h, i: (b, h, i, 0)),
            _vmem_spec((1, 1, 1, bq), lambda b, h, i: (b, h, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Hq, Sq, Dv), q.dtype),
            jax.ShapeDtypeStruct((B, Hq, 1, Sq), jnp.float32),
        ],
        scratch_shapes=[
            _scratch((bq, _LANES)),      # running max
            _scratch((bq, _LANES)),      # running denominator
            _scratch((bq, Dv)),          # fp32 output accumulator
        ],
        compiler_params=_resident_params(),
        interpret=_interpret(),
        name="flash_fwd",  # one name for both paths: the trace's reader keys on it
    )(q, k, v)


def _flash_fwd_streamed(q, k, v, bq, bkv, *, mask_fn, score_fn, mask_type,
                        window, prefix_len, scale, canonical_mask):
    B, Hq, Sq, D = q.shape
    _, Hkv, Skv, _ = k.shape
    Dv = v.shape[3]
    G = Hq // Hkv
    nq = Sq // bq
    nkv = Skv // bkv
    kv_lo, kv_hi = _kv_range(mask_type, window, prefix_len, bq, bkv, nkv)
    full_tile = (_full_tile_fn(mask_type, window, prefix_len, bq, bkv)
                 if canonical_mask else None)
    live_full, clamp = _streamed_segments(mask_type, window, prefix_len, bq, bkv,
                                          canonical_mask, "kv")

    def kv_index(b, h, i, j):
        # Clamp skipped tiles into the live range so the pipeline never
        # DMAs a tile the kernel will not touch (block sparsity saves
        # bandwidth, not just FLOPs). Empty ranges (possible for band
        # masks: lo can exceed nkv-1, hi-1 can go below lo) are clamped
        # into [0, nkv-1] from BOTH sides — jnp.clip resolves inverted
        # bounds toward the upper one, which is always in range.
        jc = clamp(i, j) if clamp else jnp.clip(j, jnp.minimum(kv_lo(i), nkv - 1),
                                                jnp.maximum(kv_hi(i) - 1, 0))
        return (b, h // G, jc, 0)

    kernel = functools.partial(
        _fwd_kernel, scale=scale, mask_fn=mask_fn,
        score_fn=score_fn, kv_lo=kv_lo, kv_hi=kv_hi, nkv=nkv,
        full_tile=full_tile, live_full=live_full)
    return pl.pallas_call(
        kernel,
        grid=(B, Hq, nq, nkv),
        in_specs=[
            _vmem_spec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            _vmem_spec((1, 1, bkv, D), kv_index),
            _vmem_spec((1, 1, bkv, Dv), kv_index),
        ],
        out_specs=[
            _vmem_spec((1, 1, bq, Dv), lambda b, h, i, j: (b, h, i, 0)),
            _vmem_spec((1, 1, 1, bq), lambda b, h, i, j: (b, h, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Hq, Sq, Dv), q.dtype),
            jax.ShapeDtypeStruct((B, Hq, 1, Sq), jnp.float32),
        ],
        scratch_shapes=[
            _scratch((bq, _LANES)),      # running max
            _scratch((bq, _LANES)),      # running denominator
            _scratch((bq, Dv)),          # fp32 output accumulator
        ],
        compiler_params=_compiler_params(3, 4),
        interpret=_interpret(),
        name="flash_fwd",  # also the innermost scope of its ops
    )(q, k, v)


def flash_bwd_dq(q, k, v, g, lse, delta, *, mask_fn=None, score_fn=None,
                 mask_type="causal", window=512, prefix_len=0,
                 block_q=None, block_kv=None, scale=1.0, canonical_mask=False,
                 _path=None):
    """Raw dQ kernel. ``lse``/``delta``: [B, Hq, 1, Sq] fp32. Resident K/V
    or streamed by :func:`flash_plan`, as the forward (``_path``: tests)."""
    plan = _traced_plan("flash_bwd_dq", q, k, v, block_q, block_kv, _path)
    bwd = _flash_bwd_dq_resident if plan.path == "resident" else _flash_bwd_dq_streamed
    return bwd(q, k, v, g, lse, delta, plan.block_q, plan.block_kv, mask_fn=mask_fn,
               score_fn=score_fn, mask_type=mask_type, window=window,
               prefix_len=prefix_len, scale=scale, canonical_mask=canonical_mask)


def _flash_bwd_dq_resident(q, k, v, g, lse, delta, bq, bkv, *, mask_fn, score_fn,
                           mask_type, window, prefix_len, scale, canonical_mask):
    B, Hq, Sq, D = q.shape
    _, Hkv, Skv, _ = k.shape
    G = Hq // Hkv
    kv_lo, kv_hi = _kv_range(mask_type, window, prefix_len, bq, bkv, Skv // bkv)
    full_range = (_full_range(mask_type, window, prefix_len, bq, bkv)
                  if canonical_mask and mask_type not in _NO_FULL_RANGE else None)
    segments = _bd_segments(mask_type, window, prefix_len, bq, bkv, canonical_mask, "kv")
    Dv = v.shape[3]  # v and dO; q, k and dQ are D wide
    tile, tile_v = (_vmem_spec((1, 1, bq, d), lambda b, h, i: (b, h, i, 0)) for d in (D, Dv))
    held, held_v = (_vmem_spec((1, 1, Skv, d), lambda b, h, i: (b, h // G, 0, 0))
                    for d in (D, Dv))
    stat = _vmem_spec((1, 1, 1, bq), lambda b, h, i: (b, h, 0, i))
    return pl.pallas_call(
        functools.partial(
            _bwd_dq_resident_kernel, scale=scale, mask_fn=mask_fn, score_fn=score_fn,
            kv_lo=kv_lo, kv_hi=kv_hi, bkv=bkv, full_range=full_range, segments=segments),
        grid=(B, Hq, Sq // bq),
        in_specs=[tile, held, held_v, tile_v, stat, stat],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq, D), q.dtype),
        scratch_shapes=[_scratch((bq, D))],
        compiler_params=_resident_params(),
        interpret=_interpret(),
        name="flash_bwd_dq",  # one name for both paths, as the forward's
    )(q, k, v, g, lse, delta)


def _flash_bwd_dq_streamed(q, k, v, g, lse, delta, bq, bkv, *, mask_fn, score_fn,
                           mask_type, window, prefix_len, scale, canonical_mask):
    B, Hq, Sq, D = q.shape
    _, Hkv, Skv, _ = k.shape
    G = Hq // Hkv
    nq = Sq // bq
    nkv = Skv // bkv
    kv_lo, kv_hi = _kv_range(mask_type, window, prefix_len, bq, bkv, nkv)
    full_tile = (_full_tile_fn(mask_type, window, prefix_len, bq, bkv)
                 if canonical_mask else None)
    live_full, clamp = _streamed_segments(mask_type, window, prefix_len, bq, bkv,
                                          canonical_mask, "kv")

    def kv_index(b, h, i, j):
        jc = clamp(i, j) if clamp else jnp.clip(j, jnp.minimum(kv_lo(i), nkv - 1),
                                                jnp.maximum(kv_hi(i) - 1, 0))
        return (b, h // G, jc, 0)

    return pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale,
                          mask_fn=mask_fn, score_fn=score_fn,
                          kv_lo=kv_lo, kv_hi=kv_hi, nkv=nkv,
                          full_tile=full_tile, live_full=live_full),
        grid=(B, Hq, nq, nkv),
        in_specs=[
            _vmem_spec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            _vmem_spec((1, 1, bkv, D), kv_index),
            _vmem_spec((1, 1, bkv, v.shape[3]), kv_index),
            _vmem_spec((1, 1, bq, v.shape[3]), lambda b, h, i, j: (b, h, i, 0)),
            _vmem_spec((1, 1, 1, bq), lambda b, h, i, j: (b, h, 0, i)),
            _vmem_spec((1, 1, 1, bq), lambda b, h, i, j: (b, h, 0, i)),
        ],
        out_specs=_vmem_spec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq, D), q.dtype),
        scratch_shapes=[_scratch((bq, D))],
        compiler_params=_compiler_params(3, 4),
        interpret=_interpret(),
        name="flash_bwd_dq",
    )(q, k, v, g, lse, delta)


def flash_bwd_dkv(q, k, v, g, lse, delta, *, mask_fn=None, score_fn=None,
                  mask_type="causal", window=512, prefix_len=0,
                  block_q=None, block_kv=None, scale=1.0, canonical_mask=False,
                  _path=None):
    """Raw dK/dV kernel. Returns per-QUERY-head grads [B, Hq, Skv, D]
    (caller reduces GQA groups). Resident Q/dO or streamed by
    :func:`flash_plan` (``_path``: tests)."""
    plan = _traced_plan("flash_bwd_dkv", q, k, v, block_q, block_kv, _path)
    bwd = _flash_bwd_dkv_resident if plan.path == "resident" else _flash_bwd_dkv_streamed
    return bwd(q, k, v, g, lse, delta, plan.block_q, plan.block_kv, mask_fn=mask_fn,
               score_fn=score_fn, mask_type=mask_type, window=window,
               prefix_len=prefix_len, scale=scale, canonical_mask=canonical_mask)


def _flash_bwd_dkv_resident(q, k, v, g, lse, delta, bq, bkv, *, mask_fn, score_fn,
                            mask_type, window, prefix_len, scale, canonical_mask):
    B, Hq, Sq, D = q.shape
    _, Hkv, Skv, _ = k.shape
    G = Hq // Hkv
    q_lo, q_hi = _q_range(mask_type, window, prefix_len, bq, bkv, Sq // bq)
    full_range = (_full_range_q(mask_type, window, prefix_len, bq, bkv)
                  if canonical_mask and mask_type not in _NO_FULL_RANGE else None)
    segments = _bd_segments(mask_type, window, prefix_len, bq, bkv, canonical_mask, "q")
    # Q, dO and the statistics are the query head's own: fetched once a
    # (sequence, query head), whatever the KV tile.
    Dv = v.shape[3]  # v, dO and dV; q, k and dK are D wide
    held, held_v = (_vmem_spec((1, 1, Sq, d), lambda b, h, i: (b, h, 0, 0)) for d in (D, Dv))
    stat = _vmem_spec((1, 1, 1, Sq), lambda b, h, i: (b, h, 0, 0))
    tile, tile_v = (_vmem_spec((1, 1, bkv, d), lambda b, h, i: (b, h // G, i, 0))
                    for d in (D, Dv))
    out, out_v = (_vmem_spec((1, 1, bkv, d), lambda b, h, i: (b, h, i, 0)) for d in (D, Dv))
    return pl.pallas_call(
        functools.partial(
            _bwd_dkv_resident_kernel, scale=scale, mask_fn=mask_fn, score_fn=score_fn,
            q_lo=q_lo, q_hi=q_hi, bq=bq, full_range=full_range, segments=segments),
        grid=(B, Hq, Skv // bkv),
        in_specs=[held, tile, tile_v, held_v, stat, stat],
        out_specs=[out, out_v],
        out_shape=[
            jax.ShapeDtypeStruct((B, Hq, Skv, D), k.dtype),
            jax.ShapeDtypeStruct((B, Hq, Skv, Dv), v.dtype),
        ],
        scratch_shapes=[_scratch((bkv, D)), _scratch((bkv, Dv))],
        compiler_params=_resident_params(),
        interpret=_interpret(),
        name="flash_bwd_dkv",
    )(q, k, v, g, lse, delta)


def _flash_bwd_dkv_streamed(q, k, v, g, lse, delta, bq, bkv, *, mask_fn, score_fn,
                            mask_type, window, prefix_len, scale, canonical_mask):
    B, Hq, Sq, D = q.shape
    _, Hkv, Skv, _ = k.shape
    Dv = v.shape[3]
    G = Hq // Hkv
    nq = Sq // bq
    nkv = Skv // bkv
    q_lo, q_hi = _q_range(mask_type, window, prefix_len, bq, bkv, nq)
    full_tile = (_full_tile_fn(mask_type, window, prefix_len, bq, bkv)
                 if canonical_mask else None)

    live_full, clamp = _streamed_segments(mask_type, window, prefix_len, bq, bkv,
                                          canonical_mask, "q")

    def q_tile(i, j):
        return clamp(i, j) if clamp else jnp.clip(j, jnp.minimum(q_lo(i), nq - 1),
                                                  jnp.maximum(q_hi(i) - 1, 0))

    def q_index(b, h, i, j):
        return (b, h, q_tile(i, j), 0)

    def stat_index(b, h, i, j):
        return (b, h, 0, q_tile(i, j))

    return pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale,
                          mask_fn=mask_fn, score_fn=score_fn,
                          q_lo=q_lo, q_hi=q_hi, nq=nq,
                          full_tile=full_tile, live_full=live_full),
        grid=(B, Hq, nkv, nq),
        in_specs=[
            _vmem_spec((1, 1, bq, D), q_index),
            _vmem_spec((1, 1, bkv, D), lambda b, h, i, j: (b, h // G, i, 0)),
            _vmem_spec((1, 1, bkv, Dv), lambda b, h, i, j: (b, h // G, i, 0)),
            _vmem_spec((1, 1, bq, Dv), q_index),
            _vmem_spec((1, 1, 1, bq), stat_index),
            _vmem_spec((1, 1, 1, bq), stat_index),
        ],
        out_specs=[
            _vmem_spec((1, 1, bkv, D), lambda b, h, i, j: (b, h, i, 0)),
            _vmem_spec((1, 1, bkv, Dv), lambda b, h, i, j: (b, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Hq, Skv, D), k.dtype),
            jax.ShapeDtypeStruct((B, Hq, Skv, Dv), v.dtype),
        ],
        scratch_shapes=[_scratch((bkv, D)), _scratch((bkv, Dv))],
        compiler_params=_compiler_params(3, 4),
        interpret=_interpret(),
        name="flash_bwd_dkv",
    )(q, k, v, g, lse, delta)


# -- host-side wrapper -------------------------------------------------------
def _attention_core(
    mask_fn, score_fn, mask_type: str, window: int, prefix_len: int,
    block_q: Optional[int], block_kv: Optional[int], scale: float,
    canonical_mask: bool = False,
):
    """Build the custom-vjp flash attention for a fixed mask/score program.

    Inputs (to the returned fn): q [B, Hq, Sq, D], k/v [B, Hkv, Skv, D].
    Output: o [B, Hq, Sq, D]. ``scale`` is baked in (nondiff).
    ``block_q``/``block_kv`` are what the caller asked of all three
    kernels, ``None`` leaving each to :func:`flash_plan`.
    """
    kw = dict(mask_fn=mask_fn, score_fn=score_fn, mask_type=mask_type,
              window=window, prefix_len=prefix_len, block_q=block_q,
              block_kv=block_kv, scale=scale, canonical_mask=canonical_mask)

    @jax.custom_vjp
    def attn(q, k, v):
        o, _ = _fwd(q, k, v)
        return o

    def _fwd(q, k, v):
        o, lse = flash_fwd(q, k, v, **kw)
        return o, (q, k, v, o, lse)

    def _bwd(res, g):
        q, k, v, o, lse = res
        B, Hq, Sq, D = q.shape
        _, Hkv, Skv, _ = k.shape
        G = Hq // Hkv
        delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32),
                        axis=-1)[:, :, None, :]  # [B,Hq,1,Sq], lse layout
        dq = flash_bwd_dq(q, k, v, g, lse, delta, **kw)
        dk_h, dv_h = flash_bwd_dkv(q, k, v, g, lse, delta, **kw)
        # GQA: reduce per-query-head dK/dV over each group
        if G > 1:
            dk = dk_h.reshape(B, Hkv, G, Skv, D).sum(axis=2).astype(k.dtype)
            dv = dv_h.reshape(B, Hkv, G, Skv, v.shape[3]).sum(axis=2).astype(v.dtype)
        else:
            dk, dv = dk_h, dv_h
        return dq, dk, dv

    attn.defvjp(_fwd, _bwd)
    return attn


@functools.lru_cache(maxsize=64)
def _cached_core(mask_fn, score_fn, mask_type, window, prefix_len, block_q,
                 block_kv, scale, canonical_mask=False):
    return _attention_core(mask_fn, score_fn, mask_type, window, prefix_len,
                           block_q, block_kv, scale, canonical_mask)


def _mesh_partition(batch: int, q_heads: int, kv_heads: int, shard_heads: bool):
    """``(mesh, spec, manual_axes)`` to shard_map the kernel with, or None
    when no mesh is active or this trace is already manual over all of it
    (ring attention, the overlap schedule, the MoE dispatch).

    ``spec`` is for the [B, S, H, D] operands: batch over the data axes
    (as ``parallel.sharding_rules.batch_pspec``) when it divides, heads
    over ``tp`` when both head counts divide — a GQA group never straddles
    shards, since Hq/tp = G * Hkv/tp. The sequence stays whole: splitting
    it is ring attention's job. Axes the spec leaves out hold replicas.
    """
    from ..parallel.context import current_mesh

    mesh = current_mesh()
    if mesh is None or mesh.size == 1:
        return None
    manual = set(jax.sharding.get_abstract_mesh().manual_axes)
    free = [a for a in mesh.axis_names if mesh.shape[a] > 1 and a not in manual]
    if not free:
        return None
    data = tuple(a for a in ("dp", "fsdp", "ep") if a in free)
    if batch % math.prod(mesh.shape[a] for a in data):
        data = ()
    tp = "tp" if (shard_heads and "tp" in free
                  and q_heads % mesh.shape["tp"] == 0
                  and kv_heads % mesh.shape["tp"] == 0) else None
    return mesh, P(data or None, None, tp, None), set(free)


# No block named: each path's own default (``flash_plan``). The environment
# pair names one for every call of the process, as an argument does for one.
_DEF_BLOCK_Q = int(os.environ.get("FLASH_BLOCK_Q", 0)) or None
_DEF_BLOCK_KV = int(os.environ.get("FLASH_BLOCK_KV", 0)) or None


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mask_type: str = "causal",
    window_size: int = 512,
    prefix_len: int = 0,
    scale: Optional[float] = None,
    block_q: Optional[int] = _DEF_BLOCK_Q,
    block_kv: Optional[int] = _DEF_BLOCK_KV,
    mask_fn: Optional[Callable] = None,
    score_fn: Optional[Callable] = None,
    precision: Optional[str] = None,
) -> jnp.ndarray:
    """Flash attention on [B, S, H, D] layout (framework convention). The
    head size of v (and so of the output) may differ from that of q and k:
    all three kernels and the plan take both from the call's shapes.

    ``mask_type`` selects the block-sparsity plan (causal / sliding_window /
    prefix_lm / full / block_diffusion); ``mask_fn``/``score_fn`` override the in-tile
    predicate (flex path): ``mask_fn(row, col) -> bool``,
    ``score_fn(scores, row, col, head) -> scores``. ``block_diffusion`` is
    self-attention over the two copies of a sequence (``masks.block_diffusion``):
    ``window_size`` is its block length and the rows of a copy are half the
    call's, whatever ``prefix_len`` says.

    ``precision`` (model.matmul_precision): "bf16" casts q/k/v; "int8"
    quantizes them onto the symmetric int8 grid with per-row amax scales
    (:func:`quantize_operand_int8`) — loss-parity gated vs bf16 in the
    test suite; the backward stays full precision either way.
    """
    precision = check_matmul_precision(precision)
    if precision == "int8":
        q = quantize_operand_int8(q)
        k = quantize_operand_int8(k)
        v = quantize_operand_int8(v)
    elif precision == "bf16":
        q, k, v = (t.astype(jnp.bfloat16) for t in (q, k, v))
    B, Sq, Hq, D = q.shape
    _, Skv, Hkv, _ = k.shape
    scale = (D ** -0.5) if scale is None else scale
    if mask_type == "block_diffusion":
        if Sq != Skv or Sq % 2 or (Sq // 2) % window_size:
            raise ValueError(f"block_diffusion attends within two copies of one sequence in "
                             f"blocks of {window_size}: got {Sq} query rows, {Skv} key rows")
        prefix_len = Sq // 2

    # A score program may read the global head index, so heads stay whole
    # under one.
    part = _mesh_partition(B, Hq, Hkv, shard_heads=score_fn is None)
    if part is not None:
        mesh, spec, manual_axes = part
        local = functools.partial(
            flash_attention, mask_type=mask_type, window_size=window_size,
            prefix_len=prefix_len, scale=scale, block_q=block_q,
            block_kv=block_kv, mask_fn=mask_fn, score_fn=score_fn)
        return jax.shard_map(
            local, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            axis_names=manual_axes, check_vma=False)(q, k, v)

    # The blocks the caller names, fitted, for all three kernels; where it
    # names none, each kernel's own by ``flash_plan``. Every default is a
    # power of two that ``fit_block`` halves down to the lane width, so a
    # sequence the forward's blocks do not divide, no kernel's do.
    block_q = block_q and fit_block(block_q, Sq)
    block_kv = block_kv and fit_block(block_kv, Skv)
    no_kernel = flash_plan(Sq, Skv, D, k.dtype, block_q, block_kv,
                           Dv=v.shape[3]).path == "reference"
    if mask_type == "block_diffusion":
        # its plan counts in whole tiles: none straddles the copies or a block
        no_kernel = no_kernel or any(
            prefix_len % block or block % window_size
            for kernel in _CHUNK_TEMPS
            for block in flash_plan(Sq, Skv, D, k.dtype, block_q, block_kv, kernel, v.shape[3])[1:])

    from . import masks as M

    # Canonical = the in-tile predicate provably equals the mask_type plan:
    # either we derive it here, or the caller (flex path) passes a
    # builder-tagged mod whose _plan matches (masks.py tags every named
    # builder) — then interior tiles may skip in-tile masking.
    plan = getattr(mask_fn, "_plan", None)
    canonical = mask_fn is None or (
        plan is not None
        and plan[0] == mask_type
        and (mask_type != "sliding_window" or plan[1] == window_size)
        and (mask_type != "prefix_lm" or plan[2] == prefix_len)
        and (mask_type != "block_diffusion" or plan[1:] == (window_size, prefix_len))
    )
    if mask_fn is None:
        mask_fn = (M.block_diffusion(prefix_len, window_size) if mask_type == "block_diffusion"
                   else {"causal": M.causal(),
                         "sliding_window": M.sliding_window(window_size),
                         "prefix_lm": M.prefix_lm(prefix_len),
                         "full": None}[mask_type])

    if no_kernel or Hq % Hkv:
        # Odd sizes: reference path with the SAME mask and score program
        # (kernel-style score_fn adapted to the [B, Hkv, G, Sq, Skv] layout).
        from .attention import reference_attention

        _count_plan("reference")

        ref_score = None
        if score_fn is not None:
            G = max(Hq // max(Hkv, 1), 1)
            head_grid = jnp.arange(Hkv * G).reshape(Hkv, G)

            def ref_score(s, q_idx, k_idx):
                return score_fn(s, q_idx[None, None, None],
                                k_idx[None, None, None],
                                head_grid[None, :, :, None, None])

        return reference_attention(q, k, v, mask_mod=mask_fn, score_mod=ref_score, scale=scale)

    core = _cached_core(mask_fn, score_fn, mask_type, window_size, prefix_len,
                        block_q, block_kv, float(scale), canonical)
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    o = core(qt, kt, vt)
    return o.transpose(0, 2, 1, 3)
