"""A short causal depthwise convolution, SiLU and a head's L2 norm in one pass
over a projection, forward and backward (a KDA mixer's ``q``, ``k``, ``v``
prologue, ``models/kimi_linear.py``).

``short_conv(a, w)`` with ``a [B, S, D]`` (the projection as its matmul gives
it) and taps ``w [D, K]``::

    c[t, i] = sum_j w[i, j] a[t - (K - 1) + j, i]  (+ bias[i])     zeros before t = 0
    u = silu(c)
    y = u * rsqrt(sum over each head's d channels of u^2 + 1e-6) * scale   where heads is given
    return y.astype(out_dtype)

the convolution as ``models/stack.py::causal_depthwise_conv`` defines it (taps
in the same order), everything between the load and the cast float32.
Differentiable in ``a``, ``w`` and ``bias``; the residuals are the operands
alone and the backward rebuilds the convolution.

Two paths behind one entry point, :func:`short_conv_plan` choosing from what
the trace can see and :func:`plan_counts` tallying what a step traced:

- ``kernel`` -- two TPU kernels, ``short_conv_fwd`` and ``short_conv_bwd``, the
  default on a TPU (interpret mode in the tests). Grid (batch, lane blocks, row
  blocks); a block is ``[rows, lanes]`` of the ``[B, S, D]`` operand in place,
  ``lanes`` whole heads, so a head's norm is a lane reduction a row and no ``[B,
  S, H, d]`` array exists. The ``K - 1`` rows before a block come as a second
  view of the same operand: the one tile of rows (8 at four bytes, 16 at two:
  Mosaic takes no narrower block of a packed array) that ends where the block
  starts, zeroed at ``t = 0``; no padded copy. Inside a block a kernel takes
  one head (or 128 lanes) at a time, a trip of a loop over aligned lane
  offsets, and walks its rows in tiles of ``_TILE_ROWS``, so a tile's values
  stay in registers from the load to the store and **a body holds its tile
  routine once**, however many heads and tiles a block has (what a run pays to
  trace and lower it: PERF.md section 6, PR 53). The backward's grid walks the
  row axis last block first, so the ``K - 1`` rows of ``dL/dc`` after a block
  are what the grid step before it left in scratch and no view after a block
  is read; inside a block it walks the tiles last to first, the next tile's
  first rows of ``dL/dc`` riding the loop, and writes ``da`` once. **The tap
  gradient accumulates in a float32 output block that stays resident over the
  row axis** (8 sublanes a tap, summed with the batch outside: ``2 x 8 x K x
  D`` numbers), so no product over ``[B, S, D]`` ever leaves VMEM. ``scale``
  is a scalar operand (SMEM), not a constant of the body: q and k are one
  kernel.
- ``xla`` -- ``causal_depthwise_conv``, ``jax.nn.silu`` and the head norm as the
  mixer had them; differentiates itself. Off the TPU, under a device mesh of
  more than one device (GSPMD cannot partition a Mosaic kernel), and wherever
  ``D`` or a head's ``d`` is no multiple of 128 or ``S`` no multiple of 8.

The override is the mixer's own, ``KDA_BACKEND`` (``kernel`` | ``xla``,
``ops/kda.py``): the prologue and the core are one mixer's kernels.
"""

from __future__ import annotations

import collections
import functools
import threading
from typing import Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .kda import default_backend

__all__ = ["short_conv", "short_conv_plan", "plan_counts", "l2_heads", "L2_EPS"]

L2_EPS = 1e-6
_LANES = 128
_SUBLANES = 8          # rows of a float32 register: what a tap's gradient keeps of the row axis
# The block and the trip at the cell's call (2 x 8,192 x 4,096 bfloat16; PERF.md section 6, PR 53, whose
# first three readings are of PR 52's bodies): a block of 512 lanes takes the normed forward 1.02 ms for
# 0.77, rows 256 to 2,048 read alike; 64 rows a trip 0.67 | 0.91 ms forward | backward against 0.77 | 0.92
# at 32 and 1.06 | 1.15 at 16; these bodies at that block and trip 0.69 | 0.91.
_BLOCK_ROWS = 512      # rows a block
_BLOCK_LANES = 1024    # lanes a block, at most
_TILE_ROWS = 64        # rows a trip of the walk inside a block
_F32 = jnp.float32


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def l2_heads(a: jnp.ndarray, scale: float = 1.0) -> jnp.ndarray:
    """``a [..., d]`` float32 over its last axis: ``a / sqrt(sum a^2 + eps)``, times ``scale``."""
    return a * (jax.lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True) + L2_EPS) * scale)


# -- the plan -------------------------------------------------------------------------
class ShortConvPlan(NamedTuple):
    path: str     # "kernel" | "xla"
    rows: int     # rows a block (0 on the xla path)
    lanes: int    # lanes a block
    tile: int     # rows a trip inside a block


def _halo_rows(itemsize: int) -> int:
    """Rows of the narrowest block Mosaic takes of an array of this item size."""
    return _SUBLANES * max(1, 4 // itemsize)


def short_conv_plan(S: int, D: int, d: Optional[int], itemsize: int,
                    backend: Optional[str] = None) -> ShortConvPlan:
    """Which path a call over ``S`` rows of ``D`` channels takes (``d``: a head's
    channels where there is a norm, ``itemsize``: ``a``'s), and the kernels' block;
    a pure function of its arguments and the backend."""
    return _plan(S, D, d, itemsize, backend, _BLOCK_ROWS, _BLOCK_LANES)


def _plan(S: int, D: int, d: Optional[int], itemsize: int, backend: Optional[str],
          rows: int, lanes: int) -> ShortConvPlan:
    """:func:`short_conv_plan` at a block the tests and ``scripts/bench_kda.py`` name:
    ``rows`` fitted to the halo's tile and to ``S``, ``lanes`` to whole heads that
    divide ``D``."""
    backend = backend or default_backend()
    if backend not in ("kernel", "xla"):
        raise ValueError(f"unknown KDA backend {backend!r} (kernel | xla)")
    halo = _halo_rows(itemsize)
    group = d or _LANES
    if backend != "kernel" or D % _LANES or group % _LANES or D % group or S % _SUBLANES or S < halo:
        return ShortConvPlan("xla", 0, 0, 0)
    rows = max(halo, min(rows, S) // halo * halo)
    tile = next(t for t in (_TILE_ROWS, 32, 16, 8) if rows % t == 0 and t % halo == 0)
    n = D // group
    m = max((k for k in range(1, n + 1) if n % k == 0 and k * group <= max(lanes, group)), default=1)
    return ShortConvPlan("kernel", rows, m * group, tile)


# Chosen while tracing, so this counts traces (as ops/kda.py does).
_plan_counts: Dict[str, int] = collections.Counter()
_plan_counts_lock = threading.Lock()
_FORMS = ("conv_kernel", "conv_xla")


def plan_counts() -> Dict[str, int]:
    """Calls traced so far in this process by form: ``conv_kernel`` (a
    differentiable call of the two kernels), ``conv_xla``."""
    with _plan_counts_lock:
        return {k: _plan_counts[k] for k in _FORMS}


# -- the XLA form ---------------------------------------------------------------------
def _short_conv_xla(a, w, bias, heads: Optional[int], scale: float, out_dtype):
    from ..models import stack

    B, S, D = a.shape
    y = jax.nn.silu(stack.causal_depthwise_conv(a, w, bias))
    if heads:
        y = l2_heads(y.reshape(B, S, heads, D // heads), scale).reshape(B, S, D)
    return y.astype(out_dtype)


# -- a tile of rows, on values: what both kernels compute -------------------------------
def _rows_behind(x, s: int, at: int, n: int):
    """Rows ``at - s .. at - s + n`` of ``x [N, L]`` (``0 <= s <= at``)."""
    return x[at:at + n] if s == 0 else pltpu.roll(x, s, 0)[at:at + n]


def _rows_ahead(x, s: int, n: int):
    """Rows ``s .. s + n`` of ``x [N, L]`` (``s + n <= N``)."""
    return x[:n] if s == 0 else pltpu.roll(x, x.shape[0] - s, 0)[:n]


def _taps(ext, at: int, n: int, K: int):
    """``[a[t - (K - 1) + j] for j]`` for the ``n`` rows of ``ext`` from ``at``."""
    return [_rows_behind(ext, K - 1 - j, at, n) for j in range(K)]


def _conv(xs, w, bias):
    """``sum_j w[j] xs[j]`` in the order ``causal_depthwise_conv`` adds (``w [K, L]``)."""
    c = sum(w[j:j + 1] * x for j, x in enumerate(xs))
    return c if bias is None else c + bias


def _sigmoid(c):
    """``1 / (1 + exp(-c))``: the EUP's reciprocal estimate and two Newton steps, float32 to a
    last place from an estimate of eight bits at half a division's instructions (the exponent
    is held where ``1 + exp`` is a number, so that a step is one too)."""
    den = 1.0 + jnp.exp(jnp.minimum(-c, 80.0))
    r = pl.reciprocal(den, approx=True)
    r = r * (2.0 - den * r)
    return r * (2.0 - den * r)


def _sum8(x):
    """``[n, L]`` -> ``[8, L]``: the rows summed register onto register."""
    return jnp.sum(x.reshape(x.shape[0] // _SUBLANES, _SUBLANES, x.shape[1]), axis=0)


def _each_head(L: int, group: int, body):
    """``body(lanes)`` for a block's lanes one head (128 lanes where there is no norm) at a
    time: the trips of one loop over aligned lane offsets, so a kernel holds its tile
    routine once however many heads a block has."""
    def trip(h, carry):
        body(pl.ds(pl.multiple_of(h * group, group), group))
        return carry

    jax.lax.fori_loop(0, L // group, trip, 0)


def _last8(ref, lanes, halo: int):
    """The last ``_SUBLANES`` rows of a halo view as float32 (the view is a whole tile of the
    operand's dtype: 16 rows at two bytes)."""
    return ref[:, lanes].astype(_F32)[halo - _SUBLANES:]


# Both bodies hold ONE copy of their tile routine (PERF.md section 6, PR 53): the heads of a block
# are trips of a loop (``_each_head``), the rows before a tile ride the row loop's carry (forward) or
# are chosen by a select on the trip (backward), and the backward takes the rows after a block from
# the grid step before it, walking the row axis last block first. ``scale`` is a scalar operand, so q
# and k are one body. tests/test_short_conv.py holds the equations a body may have. The row loop is
# unrolled where the body is lowered (``unroll=True``: traced once, eight copies in Mosaic's text), so
# that the scheduler fills a tile's waits with the next tile's work: 86 | 156 bundles a tile forward |
# backward for the rolled loop's 131 | 194 and PR 52's 89 | 150 (the described v5e's compiler, PR 53).
def _fwd_kernel(scale_ref, a_ref, before_ref, w_ref, *rest, K: int, norm: bool, tile: int, group: int):
    y_ref = rest[-1]
    bias_ref = rest[0] if len(rest) == 2 else None
    R, L = a_ref.shape
    halo = before_ref.shape[0]
    first = pl.program_id(2) == 0
    scale = scale_ref[0]

    def head(lanes):
        w = w_ref[:, lanes]
        bias = None if bias_ref is None else bias_ref[:, lanes]

        def trip(t, before):   # ``before``: the 8 rows ahead of this tile
            r0 = pl.multiple_of(t * tile, tile)
            cur = a_ref[pl.ds(r0, tile), lanes].astype(_F32)
            c = _conv(_taps(jnp.concatenate([before, cur], axis=0), _SUBLANES, tile, K), w, bias)
            u = c * _sigmoid(c)
            y_ref[pl.ds(r0, tile), lanes] = (l2_heads(u, scale) if norm else u).astype(y_ref.dtype)
            return cur[tile - _SUBLANES:]

        jax.lax.fori_loop(0, R // tile, trip, jnp.where(first, 0.0, _last8(before_ref, lanes, halo)), unroll=True)

    _each_head(L, group, head)


def _dconv(ext, dy, w, bias, n: int, K: int, norm: bool, scale):
    """``(dL/dc [n, L], the taps' operands)`` for the ``n`` rows of ``ext`` after its first 8,
    ``L`` one head's lanes where there is a norm."""
    xs = _taps(ext, _SUBLANES, n, K)
    c = _conv(xs, w, bias)
    s = _sigmoid(c)
    u = c * s
    du = dy
    if norm:
        inv = jax.lax.rsqrt(jnp.sum(u * u, axis=-1, keepdims=True) + L2_EPS)
        dot = jnp.sum(dy * u, axis=-1, keepdims=True)
        du = (inv * scale) * (dy - u * (inv * inv * dot))
    return du * (s + u * (1.0 - s)), xs


def _bwd_kernel(scale_ref, a_ref, before_ref, dy_ref, w_ref, *rest, K: int, norm: bool, tile: int,
                group: int, S: int, blocks: int):
    has_bias = len(rest) == 5
    bias_ref = rest[0] if has_bias else None
    da_ref, dw_ref = rest[has_bias], rest[has_bias + 1]
    db_ref = rest[3] if has_bias else None
    after = rest[-1]                        # dL/dc of the 8 rows after the block in hand
    R, L = a_ref.shape
    halo = before_ref.shape[0]
    nt = R // tile
    i = blocks - 1 - pl.program_id(2)       # the block in hand: the grid walks the row axis last block first
    ragged = S % R != 0
    scale = scale_ref[0]

    @pl.when(pl.program_id(2) == 0)
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)
        after[...] = jnp.zeros_like(after)
        if has_bias:
            db_ref[...] = jnp.zeros_like(db_ref)

    def head(lanes):
        w = w_ref[:, lanes]
        bias = None if bias_ref is None else bias_ref[:, lanes]
        outside = jnp.where(i == 0, 0.0, _last8(before_ref, lanes, halo))

        def trip(k, carry):   # tiles last to first; ``ahead``: dL/dc of the 8 rows after this one
            ahead, sums = carry[0], carry[1:]
            j = nt - 1 - k
            r0 = pl.multiple_of(j * tile, tile)
            inside = a_ref[pl.ds(pl.multiple_of(jnp.maximum(r0 - halo, 0), halo), halo), lanes]
            before = jnp.where(j == 0, outside, inside.astype(_F32)[halo - _SUBLANES:])
            ext = jnp.concatenate([before, a_ref[pl.ds(r0, tile), lanes].astype(_F32)], axis=0)
            dy = dy_ref[pl.ds(r0, tile), lanes].astype(_F32)
            if ragged:   # a short last block: what lies past the sequence is no number
                row = i * R + r0 + jax.lax.broadcasted_iota(jnp.int32, ext.shape, 0) - _SUBLANES
                ext, dy = jnp.where(row < S, ext, 0.0), jnp.where(row[_SUBLANES:] < S, dy, 0.0)
            dc, xs = _dconv(ext, dy, w, bias, tile, K, norm, scale)
            terms = [dc * x for x in xs] + ([dc] if has_bias else [])   # the taps' gradient, then the bias's
            sums = tuple(acc + _sum8(term) for acc, term in zip(sums, terms))
            full = jnp.concatenate([dc, ahead], axis=0)
            da = sum(w[t:t + 1] * _rows_ahead(full, K - 1 - t, tile) for t in range(K))
            da_ref[pl.ds(r0, tile), lanes] = da.astype(da_ref.dtype)
            return (dc[:_SUBLANES],) + sums

        zero = jnp.zeros((_SUBLANES, group), _F32)
        out = jax.lax.fori_loop(0, nt, trip, (after[:, lanes],) + (zero,) * (K + has_bias), unroll=True)
        after[:, lanes] = out[0]
        for t in range(K):
            dw_ref[t, :, lanes] += out[1 + t]
        if has_bias:
            db_ref[:, lanes] += out[1 + K]

    _each_head(L, group, head)


# -- the calls ------------------------------------------------------------------------
def _views(S: int, plan: ShortConvPlan, itemsize: int, backward: bool = False):
    """Block specs of a ``[B, S, D]`` operand of this item size: a block and the tile of rows
    before it (held inside the array), in the order a kernel's grid walks the row axis: first
    block first forward, last block first ``backward``."""
    halo = _halo_rows(itemsize)
    per, n = plan.rows // halo, pl.cdiv(S, plan.rows)
    L = plan.lanes
    at = (lambda i: n - 1 - i) if backward else (lambda i: i)
    return (pl.BlockSpec((None, plan.rows, L), lambda b, l, i: (b, at(i), l)),
            pl.BlockSpec((None, halo, L), lambda b, l, i: (b, jnp.maximum(at(i) * per - 1, 0), l)))


def _small(plan: ShortConvPlan, K: int):
    """Block specs of the scale (a scalar), the taps ``[K, D]``, a bias ``[1, D]``, and their
    gradients' partial sums ``[B, K, 8, D]`` and ``[B, 8, D]``, which stay where they are over
    the row axis."""
    L = plan.lanes
    return (pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((K, L), lambda b, l, i: (0, l)),
            pl.BlockSpec((1, L), lambda b, l, i: (0, l)),
            pl.BlockSpec((None, K, _SUBLANES, L), lambda b, l, i: (b, 0, 0, l)),
            pl.BlockSpec((None, _SUBLANES, L), lambda b, l, i: (b, 0, l)))


def _params(interpret: bool, rows: str):
    return None if interpret else pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", rows))


# Jitted (``interpret`` among the static arguments: it is the backend's, and the tests steer it), so
# that a stack of mixers traces and lowers each kernel once a shape and not three times a layer;
# ``scale`` is an operand, so q's call and k's are that one shape.
@functools.partial(jax.jit, static_argnames=("d", "out_dtype", "plan", "interpret"))
def _fwd_call(a, wt, bias, scale, d, out_dtype, plan: ShortConvPlan, interpret: bool):
    B, S, D = a.shape
    K = wt.shape[0]
    block, before = _views(S, plan, a.dtype.itemsize)
    scalar, taps, bias_spec, _, _ = _small(plan, K)
    operands = (scale, a, a, wt) + (() if bias is None else (bias,))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, K=K, norm=bool(d), tile=plan.tile, group=d or _LANES),
        grid=(B, D // plan.lanes, pl.cdiv(S, plan.rows)),
        in_specs=[scalar, block, before, taps] + ([] if bias is None else [bias_spec]),
        out_specs=_views(S, plan, jnp.dtype(out_dtype).itemsize)[0],
        out_shape=jax.ShapeDtypeStruct(a.shape, out_dtype),
        compiler_params=_params(interpret, "parallel"), interpret=interpret,
        name="short_conv_fwd",
    )(*operands)


@functools.partial(jax.jit, static_argnames=("d", "plan", "interpret"))
def _bwd_call(a, wt, bias, scale, dy, d, plan: ShortConvPlan, interpret: bool):
    B, S, D = a.shape
    K = wt.shape[0]
    block, before = _views(S, plan, a.dtype.itemsize, backward=True)
    dy_block = _views(S, plan, dy.dtype.itemsize, backward=True)[0]
    scalar, taps, bias_spec, dtaps, dbias = _small(plan, K)
    has_bias = bias is not None
    operands = (scale, a, a, dy, wt) + ((bias,) if has_bias else ())
    blocks = pl.cdiv(S, plan.rows)
    out = pl.pallas_call(
        functools.partial(_bwd_kernel, K=K, norm=bool(d), tile=plan.tile, group=d or _LANES, S=S, blocks=blocks),
        grid=(B, D // plan.lanes, blocks),
        in_specs=[scalar, block, before, dy_block, taps] + ([bias_spec] if has_bias else []),
        out_specs=[block, dtaps] + ([dbias] if has_bias else []),
        out_shape=[jax.ShapeDtypeStruct(a.shape, a.dtype),
                   jax.ShapeDtypeStruct((B, K, _SUBLANES, D), _F32)]
        + ([jax.ShapeDtypeStruct((B, _SUBLANES, D), _F32)] if has_bias else []),
        scratch_shapes=[pltpu.VMEM((_SUBLANES, plan.lanes), _F32)],
        compiler_params=_params(interpret, "arbitrary"), interpret=interpret,
        name="short_conv_bwd",
    )(*operands)
    dw = jnp.sum(out[1], axis=(0, 2)).T                                   # [D, K]
    return out[0], dw, (jnp.sum(out[2], axis=(0, 1)) if has_bias else None)


def _operands(w, bias, scale: float):
    """The taps as rows of lanes ``[K, D]``, a bias as ``[1, D]`` and the scale as ``[1]``, float32."""
    return w.astype(_F32).T, None if bias is None else bias.astype(_F32)[None], jnp.full((1,), scale, _F32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _short_conv_kernel(a, w, bias, d, scale, out_dtype, plan):
    return _fwd_call(a, *_operands(w, bias, scale), d=d, out_dtype=out_dtype, plan=plan, interpret=_interpret())


def _short_conv_kernel_fwd(a, w, bias, d, scale, out_dtype, plan):
    return _short_conv_kernel(a, w, bias, d, scale, out_dtype, plan), (a, w, bias)


def _short_conv_kernel_bwd(d, scale, out_dtype, plan, res, dy):
    a, w, bias = res
    da, dw, db = _bwd_call(a, *_operands(w, bias, scale), dy, d=d, plan=plan, interpret=_interpret())
    return da, dw.astype(w.dtype), None if bias is None else db.astype(bias.dtype)


_short_conv_kernel.defvjp(_short_conv_kernel_fwd, _short_conv_kernel_bwd)


def short_conv(a, w, *, bias=None, heads: Optional[int] = None, scale: float = 1.0, out_dtype=None,
               backend: Optional[str] = None):
    """``silu(conv(a, w) + bias)``, L2-normed over each of ``heads`` heads and times
    ``scale`` where ``heads`` is given, in ``out_dtype`` (``a``'s where none is
    named): this module's docstring. ``a [B, S, D]``, ``w [D, K]``, ``bias [D]``."""
    return _short_conv(a, w, bias, heads, scale, out_dtype, backend, _BLOCK_ROWS, _BLOCK_LANES)


def _short_conv(a, w, bias, heads, scale, out_dtype, backend, rows: int, lanes: int):
    """:func:`short_conv` at a block the tests and ``scripts/bench_kda.py`` name."""
    from ..parallel.context import current_mesh

    mesh = current_mesh()
    if mesh is not None and mesh.size > 1:
        backend = "xla"
    B, S, D = a.shape
    out_dtype = jnp.dtype(out_dtype or a.dtype)
    if heads and D % heads:
        raise ValueError(f"{D} channels are no {heads} heads")
    d = D // heads if heads else None
    plan = _plan(S, D, d, min(a.dtype.itemsize, out_dtype.itemsize), backend, rows, lanes)
    with _plan_counts_lock:
        _plan_counts[f"conv_{plan.path}"] += 1
    if plan.path == "xla":
        return _short_conv_xla(a, w, bias, heads, scale, out_dtype)
    return _short_conv_kernel(a, w, bias, d, float(scale), out_dtype, plan)
