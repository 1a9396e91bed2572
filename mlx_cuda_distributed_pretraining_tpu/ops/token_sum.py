"""Token-side passes of the dropless expert layer (``token_sum``, ``token_dot``).

``out[t] = sum_k scale[t, k] * buf[sel_row[t, k]]`` over the selections a
token holds on this rank is the expert layer's combine, its recomputation
and the dispatch's backward (models/moe.py ``combine_rows``,
``dispatch_rows``); ``dw[t, k] = buf[sel_row[t, k]] . other[t]`` is the gate
weights' gradient of a combine. Two forms, chosen by :func:`token_sum_plan`
from shapes and the grouped-matmul backend alone:

- XLA's (models/moe.py): gather a buffer row for every selection ``[T, K]``,
  held or not, into ``[T, K, D]``, scale in float32 (zero where the
  selection is not held) and sum over ``K``; the dots taken on the buffer's
  side and gathered. Off the chip, and where a tile's operands do not fit
  VMEM.
- the kernels here: a grid over tiles of ``bt`` tokens that builds no
  ``[T, K, D]``. A group's rows are token-major (``dispatch_plan``: a stable
  sort), so the rows one expert holds of one tile of tokens are one
  contiguous range of the buffer. A tile copies its ranges from HBM in
  pieces of 16 rows (a bfloat16 register's sublanes: every copy starts on
  the buffer's tiling; Mosaic takes no narrower slice of a tiled array in
  HBM) into a staging slot in VMEM, 32 pieces a round (16 where two slots of
  32 rows as wide as these leave a tile no room), the next round's
  copies (the next tile's first among them) in flight while this one is
  worked on, 128 staged rows at a time on the MXU. :func:`token_sum` places
  them with a one-hot product: ``O^T [128, bt]`` holds ``scale[t, k]`` where
  staged row ``j`` is selection ``(t, k)``'s and zero elsewhere, so ``out
  += O @ rows`` accumulates in float32 (in staged order, an expert after
  another, where XLA's form sums in ascending ``k``) and the tile is written
  once. The products are the float32 ones: against bfloat16 rows the scale
  goes through the MXU as its three bfloat16 parts (a 0/1 scale, the
  dispatch's backward, as one), against float32 rows at ``HIGHEST``.
  :func:`token_dot` multiplies the staged rows against all of the tile's
  tokens' rows of ``other`` and each selection picks its own dot. A
  selection that is not held is never fetched. Where each range lies, where
  a selection's row is staged and which pieces a tile copies are a few small
  XLA operations over ``[E, K, T]`` ahead of the call (:func:`_tile_pieces`);
  the loop over a tile's pieces has its bound from them, so a call's copies
  are the rows its tiles hold, rounded out to pieces.

Run under Pallas interpret mode off the chip where a test forces the
``pallas`` backend (``GMM_BACKEND``, the one switch ``gmm`` has).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import grouped_matmul as gm

__all__ = ["token_sum", "token_dot", "token_sum_plan"]

_PIECE = 16           # rows a copy: a bfloat16 register's sublanes
_CHUNK = 128          # staged rows a one-hot product
_ROUNDS = (32, 16)    # pieces in flight: a staging slot holds four chunks, or two where four leave a tile no room
_LANE_BLOCK = 512     # columns a product: its float32 result stays a few registers' worth
_BLOCK_TOKENS = 128   # tokens a tile, where a call has more than _ONE_TILE: a tile's tokens lie along whole lanes
_ONE_TILE = 64        # a call of so few tokens (a decode step) is one tile
# The call stays inside Mosaic's default scoped limit (16 MiB), and not by
# choice: XLA fuses the cotangent's sum after the dispatch's backward into the
# custom call, and the fusion is compiled under the default limit whatever the
# kernel asks for. A tile holds two staging slots of a round, its float32
# sums, two output blocks and a product's operands; the rest is the compiler's.
_VMEM_BUDGET = 12 * 2**20


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _tile_pieces_cap(bt: int, K: int, E: int) -> int:
    """Pieces a tile can need: a range of ``n`` rows takes ``(n - 1) // 16 + 2``
    at most, a tile's ranges hold ``bt K`` rows at most in ``min(E, bt K)``
    ranges."""
    return bt * K // _PIECE + 2 * min(E, bt * K)


def _vmem_bytes(bt: int, D: int, itemsize: int, pieces: int) -> int:
    """VMEM a tile of ``bt`` tokens holds: the two staging slots of ``pieces``
    pieces, the float32 sums, two output blocks, and a chunk's one-hot and product."""
    lanes = gm.round_up(D, 128)
    staging = 2 * pieces * _PIECE * lanes * itemsize
    return (staging + bt * lanes * 4 + 2 * bt * lanes * itemsize
            + _CHUNK * bt * 8 + bt * _LANE_BLOCK * 4)


def _round_pieces(bt: int, D: int, itemsize: int) -> int:
    """Pieces a round of a tile of ``bt`` tokens: the most whose two staging slots
    fit the budget beside the tile, or 0 where none does."""
    return next((r for r in _ROUNDS if _vmem_bytes(bt, D, itemsize, r) <= _VMEM_BUDGET), 0)


def token_sum_plan(T: int, K: int, D: int, rows: int, dtype, backend: Optional[str] = None) -> int:
    """Tokens a tile of the kernels takes for ``[T, K]`` selections of rows
    ``D`` wide in ``dtype`` out of a buffer of ``rows`` rows, or 0 for XLA's
    form; a pure function of its arguments and the grouped-matmul backend
    (``pallas`` on the chip, or forced). All of a call's tokens as one tile
    where they are few (a decode step); 0 off that backend, where the buffer
    is no whole number of pieces (tiles of 8 rows), and where no tile's
    operands fit the VMEM budget."""
    if (backend or gm.default_backend()) != "pallas" or rows % _PIECE:
        return 0
    if T <= _ONE_TILE:
        return gm.round_up(T, _PIECE)
    return _BLOCK_TOKENS if _round_pieces(_BLOCK_TOKENS, D, jnp.dtype(dtype).itemsize) else 0


# -- where a tile's rows lie ---------------------------------------------------
def _tile_pieces(sel_row, sel_held, group_sizes, bt: int, cap: int):
    """For tiles of ``bt`` tokens: ``pos [K, T]`` the staged row of every held
    selection (-1 where not held), ``src [n, cap]`` the buffer pieces a tile
    copies in staged order (0 past its last), ``total [n]`` how many. The
    range of expert ``e`` in tile ``i`` runs from the piece of its first row to
    the piece of its last; the ranges are staged one after another. Tokens
    lie along the last axis and experts along the first throughout, so every
    operation fills its registers' lanes."""
    T, K = sel_row.shape
    n = T // bt
    row = sel_row.T.reshape(K, n, bt)
    held = sel_held.T.reshape(K, n, bt)
    ends = jnp.cumsum(group_sizes.astype(jnp.int32))[:, None, None, None]
    mine = held & (row >= ends - group_sizes.astype(jnp.int32)[:, None, None, None]) & (row < ends)
    piece = row // _PIECE                                                           # [K, n, bt]
    first = jnp.min(jnp.where(mine, piece, jnp.iinfo(jnp.int32).max), axis=(1, 3))  # [E, n]
    last = jnp.max(jnp.where(mine, piece, -1), axis=(1, 3))
    count = jnp.where(last >= 0, last - first + 1, 0)
    base = jnp.cumsum(count, axis=0) - count
    shift = jnp.sum(jnp.where(mine, ((base - first) * _PIECE)[:, None, :, None], 0), axis=0)
    pos = jnp.where(held, row + shift, -1).reshape(K, T)
    j = jnp.arange(cap, dtype=jnp.int32)
    inside = (j >= base[..., None]) & (j < (base + count)[..., None])               # [E, n, cap]
    src = jnp.sum(jnp.where(inside, first[..., None] + j - base[..., None], 0), axis=0)
    return pos, src, jnp.sum(count, axis=0)


# -- the kernels ---------------------------------------------------------------
def _staged_chunks(total_ref, src_ref, next_ref, buf_ref, stage_ref, sem_ref, rounds_ref, chunk):
    """One tile's pieces through the two staging slots in rounds of as many pieces
    as a slot holds (``stage_ref [2, pieces * 16, D]``):
    ``chunk(slot, at, first)`` for every 128 staged rows that hold a piece,
    ``stage_ref[slot, at : at + 128]`` being the tile's staged rows ``first ..``.
    While a round is worked on the next one's copies are in flight, the next
    tile's first round among them, so ``rounds_ref`` carries the rounds so far
    (the slot's parity) from tile to tile. Every tile takes a round, with no
    piece where it holds nothing."""
    i, n = pl.program_id(0), pl.num_programs(0)
    round_pieces = stage_ref.shape[1] // _PIECE

    def piece_copy(src, j, slot):
        return pltpu.make_async_copy(
            buf_ref.at[pl.ds(pl.multiple_of(src * _PIECE, _PIECE), _PIECE), :],
            stage_ref.at[slot, pl.ds(pl.multiple_of(j * _PIECE, _PIECE), _PIECE), :],
            sem_ref.at[slot])

    def pieces_of(total, r):
        return jnp.clip(total - r * round_pieces, 0, round_pieces)

    def start(pieces_ref, total, r, slot):
        def one(j, carry):
            piece_copy(pieces_ref[0, r * round_pieces + j], j, slot).start()
            return carry
        jax.lax.fori_loop(0, pieces_of(total, r), one, 0)

    @pl.when(i == 0)
    def _first():
        # what a chunk's tail holds past a round's last piece is multiplied by
        # zero, so it has to be a number: VMEM starts as anything
        stage_ref[...] = jnp.zeros_like(stage_ref)
        rounds_ref[0] = 0
        start(src_ref, total_ref[0], 0, 0)

    total = total_ref[i]
    n_rounds = jnp.maximum(pl.cdiv(total, round_pieces), 1)
    done = rounds_ref[0]

    def one_round(r, carry):
        slot = (done + r) % 2

        @pl.when(r + 1 < n_rounds)
        def _ahead():
            start(src_ref, total, r + 1, 1 - slot)

        @pl.when(jnp.logical_and(r + 1 == n_rounds, i + 1 < n))
        def _next_tile():
            start(next_ref, total_ref[jnp.minimum(i + 1, n - 1)], 0, 1 - slot)

        pieces = pieces_of(total, r)

        def wait(j, carry):
            piece_copy(0, j, slot).wait()
            return carry
        jax.lax.fori_loop(0, pieces, wait, 0)

        def one_chunk(c, carry):
            at = pl.multiple_of(c * _CHUNK, _CHUNK)
            chunk(slot, at, r * (round_pieces * _PIECE) + at)
            return carry
        jax.lax.fori_loop(0, pl.cdiv(pieces * _PIECE, _CHUNK), one_chunk, 0)
        return carry
    jax.lax.fori_loop(0, n_rounds, one_round, 0)
    rounds_ref[0] = done + n_rounds


def _staged_row(first, bt):
    """``[128, bt]``: the staged row each sublane of a chunk holds."""
    return first + jax.lax.broadcasted_iota(jnp.int32, (_CHUNK, bt), 0)


def _token_sum_kernel(total_ref, src_ref, next_ref, pos_ref, scale_ref, buf_ref, out_ref,
                      stage_ref, sem_ref, rounds_ref, acc_ref, *, parts):
    K, bt = pos_ref.shape
    D = out_ref.shape[1]
    acc_ref[...] = jnp.zeros_like(acc_ref)
    contract = (((0,), (0,)), ((), ()))

    def chunk(slot, at, first):
        staged = _staged_row(first, bt)
        o = jnp.zeros((_CHUNK, bt), jnp.float32)
        for k in range(K):   # a token's selections are staged at different rows
            o = jnp.where(staged == pos_ref[k:k + 1, :], scale_ref[k:k + 1, :], o)
        if parts:   # the float32 scale as bfloat16 parts: every product exact
            split = []
            for _ in range(parts):
                split.append(o.astype(jnp.bfloat16))
                o = o - split[-1].astype(jnp.float32)
        for d in range(0, D, _LANE_BLOCK):
            lanes = pl.ds(d, min(_LANE_BLOCK, D - d))
            rows = stage_ref[slot, pl.ds(at, _CHUNK), lanes]
            if parts:
                part = sum(jax.lax.dot_general(piece, rows, contract,
                                               preferred_element_type=jnp.float32)
                           for piece in split)
            else:
                part = jax.lax.dot_general(o, rows.astype(jnp.float32), contract,
                                           precision=jax.lax.Precision.HIGHEST,
                                           preferred_element_type=jnp.float32)
            acc_ref[:, lanes] += part
    _staged_chunks(total_ref, src_ref, next_ref, buf_ref, stage_ref, sem_ref, rounds_ref, chunk)
    out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _token_dot_kernel(total_ref, src_ref, next_ref, pos_ref, other_ref, buf_ref, out_ref,
                      stage_ref, sem_ref, rounds_ref):
    K, bt = pos_ref.shape
    out_ref[...] = jnp.zeros_like(out_ref)
    exact = None if stage_ref.dtype == jnp.bfloat16 else jax.lax.Precision.HIGHEST

    def chunk(slot, at, first):
        # every staged row against every token of the tile, then each selection's own
        dots = jax.lax.dot_general(stage_ref[slot, pl.ds(at, _CHUNK), :], other_ref[...],
                                   (((1,), (1,)), ((), ())), precision=exact,
                                   preferred_element_type=jnp.float32)
        staged = _staged_row(first, bt)
        for k in range(K):
            out_ref[k:k + 1, :] += jnp.sum(jnp.where(staged == pos_ref[k:k + 1, :], dots, 0),
                                           axis=0, keepdims=True)
    _staged_chunks(total_ref, src_ref, next_ref, buf_ref, stage_ref, sem_ref, rounds_ref, chunk)


def _tokens_on_rows(bt, width):
    return pl.BlockSpec((bt, width), lambda i, total: (i, 0))


def _tokens_on_lanes(rows, bt):
    return pl.BlockSpec((rows, bt), lambda i, total: (0, i))


def _tiles(buf, sel_row, sel_held, group_sizes, bt, operand_spec, out_spec, scratch=()):
    """What both kernels' calls share: ``(call, operands)`` where ``call`` are
    ``pallas_call``'s grid and compiler arguments for tiles of ``bt`` tokens (the
    tiles' pieces and staged rows (:func:`_tile_pieces`) ahead of one more operand
    blocked by tile and the buffer left in HBM) and ``operands`` the first four."""
    T, K = sel_row.shape
    n = T // bt
    cap = _tile_pieces_cap(bt, K, group_sizes.shape[0])
    pos, src, total = _tile_pieces(sel_row, sel_held, group_sizes, bt, cap)
    pieces = _round_pieces(bt, buf.shape[1], buf.dtype.itemsize) or _ROUNDS[0]
    src = src.reshape(n, 1, cap)
    pieces_spec = functools.partial(pl.BlockSpec, (None, 1, cap), memory_space=pltpu.SMEM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n,),
        in_specs=[
            pieces_spec(lambda i, total: (i, 0, 0)),
            pieces_spec(lambda i, total: (jnp.minimum(i + 1, n - 1), 0, 0)),
            _tokens_on_lanes(K, bt),
            operand_spec,
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=out_spec,
        scratch_shapes=[
            pltpu.VMEM((2, pieces * _PIECE, buf.shape[1]), buf.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),
            *scratch,
        ],
    )
    # a tile starts the next one's copies: the grid runs in order
    params = None if _interpret() else pltpu.CompilerParams(dimension_semantics=("arbitrary",))
    return dict(grid_spec=grid_spec, compiler_params=params), (total, src, src, pos)


def _whole_tiles(bt, *token_major):
    """The arrays padded along their tokens to whole tiles: a padded token holds nothing."""
    pad = -token_major[0].shape[0] % bt
    return [jnp.pad(a, ((0, pad), (0, 0))) if pad else a for a in token_major]


def token_sum(buf, sel_row, sel_held, scale, group_sizes, bt: int, *, exact_scale: bool = False):
    """``out[t] = sum_k scale[t, k] * buf[sel_row[t, k]]`` over the selections
    with ``sel_held[t, k]`` → ``[T, D]`` in ``buf``'s dtype, the sum in float32
    and cast once, in tiles of ``bt`` tokens (:func:`token_sum_plan`).
    ``buf [rows, D]`` is the expert buffer of a ``DispatchPlan`` whose groups
    have ``group_sizes`` rows, each group's rows token-major; ``exact_scale``
    says the scale is a bfloat16 number as it stands (0 or 1), which saves two
    of the three products against bfloat16 rows."""
    (T, K), D = sel_row.shape, buf.shape[1]
    sel_row, sel_held, scale = _whole_tiles(bt, sel_row, sel_held, scale)
    parts = 0 if buf.dtype != jnp.bfloat16 else 1 if exact_scale else 3
    call, tiles = _tiles(buf, sel_row, sel_held, group_sizes, bt, _tokens_on_lanes(K, bt),
                         _tokens_on_rows(bt, D), scratch=[pltpu.VMEM((bt, D), jnp.float32)])
    out = pl.pallas_call(
        functools.partial(_token_sum_kernel, parts=parts),
        out_shape=jax.ShapeDtypeStruct((sel_row.shape[0], D), buf.dtype),
        **call,
        interpret=_interpret(),
        name="token_sum",  # also the innermost scope of its ops
    )(*tiles, jnp.where(sel_held, scale, 0).astype(jnp.float32).T, buf)
    return out[:T]


def token_dot(buf, sel_row, sel_held, other, group_sizes, bt: int):
    """``out[t, k] = sum_d buf[sel_row[t, k], d] * other[t, d]`` where
    ``sel_held[t, k]``, else 0 → ``[T, K]`` float32, summed in float32: the
    gate weights' gradient of a combine, a selection's row against its token's
    cotangent, through the same tiles and copies as :func:`token_sum`. A chunk
    of staged rows is multiplied against all of the tile's tokens at once on
    the MXU and each selection picks its own product."""
    T, K = sel_row.shape
    sel_row, sel_held, other = _whole_tiles(bt, sel_row, sel_held, other)
    call, tiles = _tiles(buf, sel_row, sel_held, group_sizes, bt,
                         _tokens_on_rows(bt, buf.shape[1]), _tokens_on_lanes(K, bt))
    out = pl.pallas_call(
        _token_dot_kernel,
        out_shape=jax.ShapeDtypeStruct((K, sel_row.shape[0]), jnp.float32),
        **call,
        interpret=_interpret(),
        name="token_dot",
    )(*tiles, other.astype(buf.dtype), buf)
    return out.T[:T]
