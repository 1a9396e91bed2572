"""Kimi Delta Attention's core: a gated delta rule whose decay is a number for
every key channel, forward and backward (arXiv:2510.26692).

For one head, state ``S_t [d, d]`` (``S_0 = 0``), over time ``t``::

    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

``q, k, v [B, S, H, d]``, ``g [B, S, H, d]`` (log decay, <= 0, float32),
``beta [B, S, H]`` -> ``o [B, S, H, d]``. The state is a matrix a head (64 KB
at 128 x 128), so the sequence is cut into chunks of ``c`` steps: inside a
chunk everything is matmuls, between chunks the state is handed over. With
``G_r = sum_{i<=r} g_i`` inside the chunk and ``gam(i->r) = exp(G_r - G_i)``::

    A[r, i] = beta_r sum_ch k_r k_i gam(i->r)   (i < r)     T = (I + A)^-1
    P[r, i] = sum_ch q_r k_i gam(i->r)          (i <= r)
    V' = T Diag(beta) (V - (K * exp(G)) S)
    O  = (Q * exp(G)) S + P V'
    S' = Diag(exp(G_c)) S + (K * exp(G_c - G))^T V'

**No exponent here is ever positive.** ``exp(-G_i)`` alone overflows float32
(nothing bounds a step's decay; -1.6 a step is -100 a chunk), so ``gam`` is not
split as ``exp(G_r) exp(-G_i)`` across a chunk. Every pair ``i < r`` is
factored at a boundary that lies between its two steps, by halving: at level
``s`` (``s = c, c / 2, ..., 2``) a block of ``s`` steps has the boundary ``b``,
the last step of its lower half; a row ``r`` of the upper half takes ``exp(G_r
- G_b)``, a row ``i`` of the lower half ``exp(G_b - G_i)``, both <= 1, and one
matmul a level, masked to (same block, ``r`` above ``b``, ``i`` not), gives
every pair that boundary separates. The levels partition the pairs (the
highest bit of ``r ^ i`` names a pair's level); no pair is VPU work.
Underflow is benign: the true product is smaller still.

The solve ``T`` is block substitution by the same halving, in float32: with
``T`` the inverse of every diagonal block of ``s / 2`` steps, a block of ``s``
is ``[[T11, 0], [-T22 A21 T11, T22]]``, so a level is ``T - T A_s T`` with
``A_s`` the level's pairs of ``A``: two whole ``[c, c]`` products a level from
pairs of steps (``I - A_2``, exact) up, 12 a chunk of 128. Every factor is an
inverse already, as large as ``T`` itself and no larger, so the error does not
grow with ``|A|``: a write strength in (0, 2) (``beta = 2 sigmoid(.)``, the
transition's eigenvalue ``1 - beta`` along ``k`` in (-1, 1)) over keys that
share a direction reads 4e-6 where the product form of a nilpotent block,
``(I - D)(I + D^2)(I + D^4)(I + D^8)`` over 16 steps, whose partial products
grow like ``(1 + |D|)^16``, read 0.2 (PERF.md section 6, PR 56).

Two paths behind one differentiable entry point, :func:`kda_plan` choosing from
the shapes and :func:`plan_counts` tallying what a step traced:

- ``kernel`` -- two TPU kernels, ``kda_fwd`` and ``kda_bwd``, the default on a
  TPU (interpret mode in the tests). Grid (batch, heads ``HEADS_PER_STEP`` at a
  time, chunks), chunks innermost with the state (transposed, ``[d_v, d_k]``,
  so that its decay scales lanes) in VMEM scratch. Operands stay ``[B, S, H
  d]`` as the model makes them: a block is a chunk of some heads' lanes. The
  forward saves the state at every chunk's start when it is differentiated
  (``[B, H, S / c, d, d]`` float32); the backward walks the chunks last to
  first carrying ``dL/dS``, rebuilds a chunk's ``A``, ``P``, ``T`` and ``V'``
  from the saved state and transposes each product above. The matmuls take
  operands in ``q``'s dtype with float32 accumulation; ``g``, its running
  sums, every ``exp``, the solve and the state are float32.
- ``xla`` -- the same chunk function under ``vmap`` over (batch, head) and a
  ``lax.scan`` over chunks, each under ``jax.checkpoint``. Differentiates
  itself. The default off the TPU, and wherever no chunk of whole sub-blocks
  divides ``S`` or ``d`` is no multiple of 128.

``KDA_BACKEND`` (``kernel`` | ``xla``) overrides the default. Under a device
mesh of more than one device the core takes the XLA form whatever was asked,
as ``ops/selective_scan.py`` does (GSPMD cannot partition a Mosaic kernel).
"""

from __future__ import annotations

import collections
import functools
import threading
from typing import Dict, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import backend_from_env

__all__ = ["kda", "kda_plan", "plan_counts", "default_backend", "KERNEL_CHUNK"]

SUB = 16               # steps the kernels' chunk is a multiple of (a bfloat16 register's sublanes)
SOLVE_FORM = "halving"  # how a chunk's triangular solve is built (`_solve`); in the tally as solve_<form>
KERNEL_CHUNK = 128     # steps of a chunk: forward | backward 17.80 | 22.92 ms a call of the cell against 20.46 | 25.03 at 64 (PERF.md section 6, PR 55)
_LANES = 128
_VMEM_LIMIT = 64 * 2**20
_F32 = jnp.float32
_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def default_backend() -> str:
    """``kernel`` on a TPU, ``xla`` elsewhere; ``KDA_BACKEND`` overrides (the
    tests force ``kernel`` to run the kernels under interpret mode)."""
    return backend_from_env("KDA_BACKEND", "kernel", "xla")


class KdaPlan(NamedTuple):
    path: str    # "kernel" | "xla"
    chunk: int   # time steps a chunk


def _fit_chunk(chunk: int, S: int) -> int:
    while chunk > 1 and S % chunk:
        chunk //= 2
    return chunk


def kda_plan(S: int, d: int, backend: Optional[str] = None) -> KdaPlan:
    """Which path a core over ``S`` steps at head size ``d`` takes, and its
    chunk; a pure function of its arguments and the backend."""
    return _plan(S, d, backend, KERNEL_CHUNK)


def _plan(S: int, d: int, backend: Optional[str], chunk: int) -> KdaPlan:
    """:func:`kda_plan` at a chunk the tests and ``scripts/bench_kda.py`` name:
    fitted to ``S`` (halved until it divides); the kernels need whole sub-blocks
    and whole lanes."""
    backend = backend or default_backend()
    if backend not in ("kernel", "xla"):
        raise ValueError(f"unknown KDA backend {backend!r} (kernel | xla)")
    c = _fit_chunk(chunk, S)
    if backend == "kernel" and c % SUB == 0 and d % _LANES == 0:
        return KdaPlan("kernel", c)
    return KdaPlan("xla", c)


# Chosen while tracing, so this counts traces (as ops/selective_scan.py does).
_plan_counts: Dict[str, int] = collections.Counter()
_plan_counts_lock = threading.Lock()
_FORMS = ("kernel", "xla")


def _count(*keys: str) -> None:
    with _plan_counts_lock:
        _plan_counts.update(keys)


def plan_counts() -> Dict[str, int]:
    """Cores traced so far in this process by form (``kernel``: a differentiable
    call of the two kernels; ``xla``), each again by chunk (``kernel_chunk64``),
    and by how a chunk's pairs were built: ``levels7`` (matmuls a chunk),
    ``pair_passes0`` (partner passes over the chunk on the VPU: 16 before the
    levels reached down to single steps; a tally without the key timed those)."""
    with _plan_counts_lock:
        return {**{k: _plan_counts[k] for k in _FORMS},
                **{k: n for k, n in sorted(_plan_counts.items()) if k not in _FORMS}}


# -- one chunk of one head, on values: the kernels' bodies and the XLA form ----------
def _dot(a, b, dims, dtype=None):
    """2-D ``dot_general`` with a float32 result: operands in ``dtype``, or in
    float32 at full precision where none is named (the solve, the running sums)."""
    if dtype is None or dtype == _F32:
        return jax.lax.dot_general(a.astype(_F32), b.astype(_F32), (dims, ((), ())),
                                   precision=jax.lax.Precision.HIGHEST, preferred_element_type=_F32)
    return jax.lax.dot_general(a.astype(dtype), b.astype(dtype), (dims, ((), ())),
                               preferred_element_type=_F32)


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _tril(c: int, strict: bool = False):
    row, col = _iota((c, c), 0), _iota((c, c), 1)
    return (col < row) if strict else (col <= row)


def _levels(c: int) -> List[int]:
    """The block sizes ``c, c / 2, ..., 2`` at which a chunk's pairs are factored."""
    if c & (c - 1):
        raise ValueError(f"a chunk of {c} steps does not halve down to pairs")
    return [c >> t for t in range(c.bit_length() - 1)]


def _boundary_rows(G, s: int):
    """``[c, d]`` whose row ``r`` is ``G``'s row ``b``, the last step of the lower
    half of ``r``'s block of ``s`` steps. Blocks shorter than a register's 8
    sublanes share one: a sublane broadcast for each and a select on the row."""
    c, d = G.shape
    tile = max(s, min(8, c))
    x3 = G.reshape(c // tile, tile, d)
    at = lambda lo: jnp.broadcast_to(x3[:, lo + s // 2 - 1:lo + s // 2, :], x3.shape)
    out, sublane = at(0), _iota(x3.shape, 1)
    for lo in range(s, tile, s):
        out = jnp.where(sublane >= lo, at(lo), out)
    return out.reshape(c, d)


class _Level(NamedTuple):
    """One halving of a chunk. ``at [c, c]``: the pairs it factors (same block of
    ``s``, ``r`` in its upper half, ``i`` in its lower; over the levels they
    partition ``i < r``: the highest bit of ``r ^ i`` names a pair's level).
    ``e [c, d]``: a row of an upper half holds ``exp(G_r - G_b)``, one of a lower
    half ``exp(G_b - G_i)``, ``b`` the last step of the lower half: every
    exponent <= 0. ``ke, qe``: ``k`` and ``q`` under it."""
    at: jnp.ndarray
    e: jnp.ndarray
    ke: jnp.ndarray
    qe: jnp.ndarray


def _pair_levels(G, q, k) -> List[_Level]:
    c = G.shape[0]
    row, col = _iota((c, c), 0), _iota((c, c), 1)
    span = jnp.where(col < row, row ^ col, 0)
    out = []
    for s in _levels(c):
        e = jnp.exp(-jnp.abs(G - _boundary_rows(G, s)))
        out.append(_Level((span >> (s.bit_length() - 2)) == 1, e, k * e, q * e))
    return out


def _span_masks(c: int):
    """``[c, levels c]`` of 0 and 1, a ``[c, c]`` block a level: in row ``t`` the
    steps ``j`` of ``t``'s half of its block whose pairs at that level have ``t``
    between their two steps (``i < t <= r``): ``j >= t`` in an upper half (``j``
    is the pair's ``r``), ``j < t`` in a lower (its ``i``)."""
    row, col = _iota((c, c), 0), _iota((c, c), 1)
    later = jnp.where(col >= row, 1, 0)
    masks = [((row ^ col) < s // 2) & (later == ((row >> (s.bit_length() - 2)) & 1)) for s in _levels(c)]
    return jnp.concatenate([jnp.where(m, 1.0, 0.0) for m in masks], axis=1)


def _eye(c: int):
    return (_iota((c, c), 0) == _iota((c, c), 1)).astype(_F32)


def _solve(A, pairs=None):
    """``(I + A)^-1`` of a strictly lower triangular ``[c, c]``, float32, by halving
    (the module's docstring): ``pairs`` are the masks of :func:`_pair_levels`, the
    widest block first, made here where none are handed over."""
    c = A.shape[0]
    if pairs is None:
        span = jnp.where(_tril(c, strict=True), _iota((c, c), 0) ^ _iota((c, c), 1), 0)
        pairs = [(span >> (s.bit_length() - 2)) == 1 for s in _levels(c)]
    T = _eye(c) - jnp.where(pairs[-1], A, 0.0)
    for at in pairs[-2::-1]:
        T = T - _dot(_dot(T, jnp.where(at, A, 0.0), _NN), T, _NN)
    return T


class _Chunk(NamedTuple):
    """What both passes of a chunk compute first."""
    G: jnp.ndarray       # running sums of g
    levels: List[_Level]
    A0: jnp.ndarray      # k k^T under the decays, strictly lower
    P: jnp.ndarray       # q k^T under the decays, lower
    T: jnp.ndarray       # (I + Diag(beta) A0)^-1
    eG: jnp.ndarray      # exp(G)
    eD: jnp.ndarray      # exp(G_c - G)
    eC: jnp.ndarray      # exp(G_c) [1, d]


def _chunk_local(q, k, g, bcol, mmdt) -> _Chunk:
    c = q.shape[0]
    G = _dot(_tril(c).astype(_F32), g, _NN)
    levels = _pair_levels(G, q, k)
    A0 = P = jnp.zeros((c, c), _F32)
    for lv in levels:       # M[r, i] = sum_ch x_r k_i gam(i->r) for the level's pairs, x = k and q stacked
        prod = _dot(jnp.concatenate([lv.ke, lv.qe], axis=0), lv.ke, _NT, mmdt)
        A0, P = jnp.where(lv.at, prod[:c], A0), jnp.where(lv.at, prod[c:], P)
    P = jnp.where(_iota((c, c), 0) == _iota((c, c), 1), jnp.sum(q * k, axis=1, keepdims=True), P)
    T = _solve(bcol * A0, [lv.at for lv in levels])
    last = G[c - 1:c]
    return _Chunk(G, levels, A0, P, T, jnp.exp(G), jnp.exp(last - G), jnp.exp(last))


def _chunk_fwd(St, q, k, v, g, bcol, brow, mmdt):
    """One chunk of one head: ``St [d_v, d_k]`` (the state, transposed), ``q, k,
    v, g [c, d]``, ``beta`` as a column and as a row -> ``(o [c, d_v], St')``,
    float32."""
    q, k, v, g = (a.astype(_F32) for a in (q, k, v, g))
    z = _chunk_local(q, k, g, bcol, mmdt)
    R = v - _dot(k * z.eG, St, _NT, mmdt)
    Vp = _dot(z.T * brow, R, _NN, mmdt)
    o = _dot(q * z.eG, St, _NT, mmdt) + _dot(z.P, Vp, _NN, mmdt)
    return o, St * z.eC + _dot(Vp, k * z.eD, _TN, mmdt)


def _chunk_bwd(St, dSt_new, do, q, k, v, g, bcol, brow, mmdt):
    """:func:`_chunk_fwd` transposed -> ``(dq, dk, dv, dg [c, d], dbeta as a
    column [c, 1] and as a row [1, c] (the two add), dSt)``, float32. With
    ``f = sum x_r y_i exp(G_r - G_i)``: ``df/dG_r = x_r df/dx_r`` and ``df/dG_i =
    -y_i df/dy_i``, so ``dG`` is ``q dq`` plus ``k`` times (``dk`` where ``k``
    stands on the left, less ``dk`` where it stands on the right); the pairs'
    part of ``dg`` is summed over the steps between a pair's two (below)."""
    q, k, v, g, do = (a.astype(_F32) for a in (q, k, v, g, do))
    c = q.shape[0]
    z = _chunk_local(q, k, g, bcol, mmdt)
    Kg, Qg, Kd = k * z.eG, q * z.eG, k * z.eD
    Tb = z.T * brow
    R = v - _dot(Kg, St, _NT, mmdt)
    Vp = _dot(Tb, R, _NN, mmdt)

    dVp = _dot(z.P, do, _TN, mmdt) + _dot(Kd, dSt_new, _NT, mmdt)
    dP = jnp.where(_tril(c), _dot(do, Vp, _NT, mmdt), 0.0)
    dR = _dot(Tb, dVp, _TN, mmdt)
    dTb = _dot(dVp, R, _NT, mmdt)
    dbrow = jnp.sum(dTb * z.T, axis=0, keepdims=True)
    dA = -_dot(_dot(z.T, dTb * brow, _TN), z.T, _NT)
    dA = jnp.where(_tril(c, strict=True), dA, 0.0)
    dbcol = jnp.sum(dA * z.A0, axis=1, keepdims=True)
    dA0 = dA * bcol
    dSt = _dot(do, Qg, _TN, mmdt) + dSt_new * z.eC - _dot(dR, Kg, _TN, mmdt)
    d_end = jnp.sum(St * dSt_new, axis=0, keepdims=True) * z.eC     # to G_c, from the state's decay

    dq = _dot(do, St, _NN, mmdt) * z.eG                 # through Q exp(G)
    dk_left = -_dot(dR, St, _NN, mmdt) * z.eG           # through K exp(G)
    dk_end = _dot(Vp, dSt_new, _NN, mmdt) * z.eD        # through K exp(G_c - G)
    on_diag = jnp.sum(_eye(c) * dP, axis=1, keepdims=True)   # exp(0): no decay, nothing to G
    dq_pairs, dk_pairs, spans = on_diag * k, on_diag * q, []
    for lv in z.levels:
        rows = jnp.concatenate([jnp.where(lv.at, dP, 0.0), jnp.where(lv.at, dA0, 0.0)], axis=0)
        lefts = _dot(rows, lv.ke, _NN, mmdt)                                        # rows of upper halves
        right = _dot(rows, jnp.concatenate([lv.qe, lv.ke], axis=0), _TN, mmdt)      # rows of lower halves
        to_q, to_k = lefts[:c] * lv.e, (lefts[c:] + right) * lv.e
        dq_pairs, dk_pairs = dq_pairs + to_q, dk_pairs + to_k
        spans.append(q * to_q + k * to_k)

    # A pair's product x_r k_i gam(i->r) depends on g_t for i < t <= r alone. Through G its gradient
    # would be +X at r and -X at i from two matmuls whose roundings differ, and the running sum would
    # carry every later pair's difference down the chunk; so the steps between are summed directly.
    dG = q * dq + k * (dk_left - dk_end)
    d_end = d_end + jnp.sum(k * dk_end, axis=0, keepdims=True)
    dG = dG + jnp.where(_iota(dG.shape, 0) == c - 1, d_end, 0.0)
    dg = _dot(_tril(c).astype(_F32), dG, _TN)
    dg = dg + _dot(_span_masks(c), jnp.concatenate(spans, axis=0), _NN, mmdt)
    return dq + dq_pairs, dk_left + dk_end + dk_pairs, dR, dg, dbcol, dbrow, dSt


# -- the XLA form -------------------------------------------------------------------
def _kda_xla(q, k, v, g, beta, chunk: int):
    B, S, H, d = q.shape
    n = S // chunk
    mmdt = q.dtype
    by_chunk = lambda a: jnp.moveaxis(a.reshape(B, n, chunk, H, -1), (1, 3), (0, 2))  # [n, B, H, c, .]
    bc = by_chunk(beta.astype(_F32)[..., None])
    one = jax.vmap(jax.vmap(functools.partial(_chunk_fwd, mmdt=mmdt)))

    @jax.checkpoint
    def step(St, xs):
        qc, kc, vc, gc, b = xs
        o, St = one(St, qc, kc, vc, gc, b, jnp.swapaxes(b, -1, -2))
        return St, o

    _, o = jax.lax.scan(step, jnp.zeros((B, H, v.shape[-1], d), _F32),
                        (by_chunk(q), by_chunk(k), by_chunk(v), by_chunk(g.astype(_F32)), bc))
    return jnp.moveaxis(o, (0, 2), (1, 3)).reshape(B, S, H, -1).astype(q.dtype)


# -- the kernels --------------------------------------------------------------------
# Heads a grid step: their chunks are independent chains of small matmuls (the solve alone is a
# dozen dependent float32 products), so the scheduler fills one head's waits with another's work:
# forward | backward 17.87 | 23.18 ms at 2, 17.80 | 22.92 at 4, 17.70 | 22.83 at 8, whose bodies hold twice the equations (PR 55).
HEADS_PER_STEP = 4


def _heads_per_step(H: int, g: int = HEADS_PER_STEP) -> int:
    while H % g:
        g -= 1
    return g


def _head(ref, h: int, d: int):
    """Head ``h``'s lanes of a ``[c, G d]`` block."""
    return ref[:, h * d:(h + 1) * d]


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, bc_ref, br_ref, o_ref, *rest, mmdt, save: bool, G: int, d: int):
    st_scr = rest[-1]

    @pl.when(pl.program_id(2) == 0)
    def _():
        st_scr[...] = jnp.zeros_like(st_scr)

    for h in range(G):
        St = st_scr[h]
        if save:
            rest[0][h] = St          # the state this chunk starts from, for the backward
        o, St = _chunk_fwd(St, _head(q_ref, h, d), _head(k_ref, h, d), _head(v_ref, h, d),
                           _head(g_ref, h, d), bc_ref[h], br_ref[h], mmdt)
        o_ref[:, h * d:(h + 1) * d] = o.astype(o_ref.dtype)
        st_scr[h] = St


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, bc_ref, br_ref, do_ref, st_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, dbc_ref, dbr_ref, dst_scr, *, mmdt, G: int, d: int):
    # the grid's last axis walks the chunks last to first (the index maps turn it round)
    @pl.when(pl.program_id(2) == 0)
    def _():
        dst_scr[...] = jnp.zeros_like(dst_scr)

    for h in range(G):
        lanes = slice(h * d, (h + 1) * d)
        dq, dk, dv, dg, dbc, dbr, dSt = _chunk_bwd(
            st_ref[h], dst_scr[h], _head(do_ref, h, d), _head(q_ref, h, d), _head(k_ref, h, d),
            _head(v_ref, h, d), _head(g_ref, h, d), bc_ref[h], br_ref[h], mmdt)
        dq_ref[:, lanes] = dq.astype(dq_ref.dtype)
        dk_ref[:, lanes] = dk.astype(dk_ref.dtype)
        dv_ref[:, lanes] = dv.astype(dv_ref.dtype)
        dg_ref[:, lanes] = dg
        dbc_ref[h] = dbc
        dbr_ref[h] = dbr
        dst_scr[h] = dSt


def _params(interpret: bool):
    return None if interpret else pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT)


def _specs(c: int, d: int, n: int, G: int, reverse: bool):
    """Block specs of a ``[B, S, H d]`` operand (``G`` heads' lanes of one chunk),
    of ``beta`` as a column and as a row, and of the saved states."""
    at = (lambda i: n - 1 - i) if reverse else (lambda i: i)
    seq = pl.BlockSpec((None, c, G * d), lambda b, h, i: (b, at(i), h))
    col = pl.BlockSpec((None, G, None, c, 1), lambda b, h, i: (b, h, at(i), 0, 0))
    row = pl.BlockSpec((None, G, None, 1, c), lambda b, h, i: (b, h, at(i), 0, 0))
    state = pl.BlockSpec((None, G, None, d, d), lambda b, h, i: (b, h, at(i), 0, 0))
    return seq, col, row, state


def _beta_blocks(beta, c: int):
    """``beta [B, S, H]`` -> ``[B, H, S / c, c, 1]`` and ``[B, H, S / c, 1, c]``, float32."""
    B, S, H = beta.shape
    b = jnp.swapaxes(beta.astype(_F32), 1, 2).reshape(B, H, S // c, c)
    return b[..., None], b[..., None, :]


# Jitted (``interpret`` among the static arguments: it is the backend's, and the tests steer it), so
# that a stack of layers traces and lowers each kernel once a shape and not once a layer.
@functools.partial(jax.jit, static_argnames=("H", "c", "G", "save", "interpret"))
def _fwd_call(q, k, v, g, bcol, brow, H: int, c: int, G: int, save: bool, interpret: bool):
    B, S, Hd = q.shape
    d, n = Hd // H, S // c
    seq, col, row, state = _specs(c, d, n, G, False)
    out_specs, out_shape = [seq], [jax.ShapeDtypeStruct(q.shape, q.dtype)]
    if save:
        out_specs.append(state)
        out_shape.append(jax.ShapeDtypeStruct((B, H, n, d, d), _F32))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, mmdt=q.dtype, save=save, G=G, d=d),
        grid=(B, H // G, n), in_specs=[seq, seq, seq, seq, col, row],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((G, d, d), _F32)],
        compiler_params=_params(interpret), interpret=interpret,
        name="kda_fwd",
    )(q, k, v, g, bcol, brow)


@functools.partial(jax.jit, static_argnames=("H", "c", "G", "interpret"))
def _bwd_call(q, k, v, g, bcol, brow, do, states, H: int, c: int, G: int, interpret: bool):
    B, S, Hd = q.shape
    d, n = Hd // H, S // c
    seq, col, row, state = _specs(c, d, n, G, True)
    like = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, mmdt=q.dtype, G=G, d=d),
        grid=(B, H // G, n), in_specs=[seq, seq, seq, seq, col, row, seq, state],
        out_specs=[seq, seq, seq, seq, col, row],
        out_shape=[like(q), like(k), like(v), like(g), like(bcol), like(brow)],
        scratch_shapes=[pltpu.VMEM((G, d, d), _F32)],
        compiler_params=_params(interpret), interpret=interpret,
        name="kda_bwd",
    )(q, k, v, g, bcol, brow, do, states)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _kda_kernel(q, k, v, g, beta, H, c, G):
    """``q, k, v, g [B, S, H d]``, ``beta [B, S, H]`` -> ``o [B, S, H d]``; ``G`` heads a grid step."""
    return _fwd_call(q, k, v, g, *_beta_blocks(beta, c), H=H, c=c, G=G, save=False,
                     interpret=_interpret())[0]


def _kda_kernel_fwd(q, k, v, g, beta, H, c, G):
    bcol, brow = _beta_blocks(beta, c)
    o, states = _fwd_call(q, k, v, g, bcol, brow, H=H, c=c, G=G, save=True, interpret=_interpret())
    return o, (q, k, v, g, bcol, brow, states, jnp.zeros((), beta.dtype))


def _kda_kernel_bwd(H, c, G, res, do):
    q, k, v, g, bcol, brow, states, beta_like = res
    dq, dk, dv, dg, dbc, dbr = _bwd_call(q, k, v, g, bcol, brow, do.astype(q.dtype), states,
                                         H=H, c=c, G=G, interpret=_interpret())
    B, S = q.shape[:2]
    dbeta = jnp.swapaxes((dbc[..., 0] + dbr[..., 0, :]).reshape(B, H, S), 1, 2)
    return dq, dk, dv, dg, dbeta.astype(beta_like.dtype)


_kda_kernel.defvjp(_kda_kernel_fwd, _kda_kernel_bwd)


def kda(q, k, v, g, beta, *, backend: Optional[str] = None):
    """``o [B, S, H, d]`` (in ``q``'s dtype) of the recurrence in this module's
    docstring from ``q, k, v [B, S, H, d]``, ``g [B, S, H, d]`` and ``beta [B, S,
    H]``; differentiable in all five. ``q`` and ``k`` come normed and scaled:
    the core applies neither."""
    return _kda(q, k, v, g, beta, backend, KERNEL_CHUNK, HEADS_PER_STEP)


def _kda(q, k, v, g, beta, backend: Optional[str], chunk: int, heads: int):
    """:func:`kda` at a chunk and a count of heads a grid step that the tests
    and ``scripts/bench_kda.py`` name; the program's are the two constants."""
    from ..parallel.context import current_mesh

    mesh = current_mesh()
    if mesh is not None and mesh.size > 1:
        backend = "xla"
    B, S, H, d = q.shape
    plan = _plan(S, d, backend, chunk)
    _count(plan.path, f"{plan.path}_chunk{plan.chunk}", f"levels{len(_levels(plan.chunk))}", "pair_passes0")
    if plan.path == "xla":
        return _kda_xla(q, k, v, g, beta, plan.chunk)
    flat = lambda a: a.reshape(B, S, H * d)
    o = _kda_kernel(flat(q), flat(k), flat(v.astype(q.dtype)), flat(g.astype(_F32)), beta, H, plan.chunk,
                    _heads_per_step(H, heads))
    return o.reshape(B, S, H, d)
