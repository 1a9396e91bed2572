"""Selective scan of a Mamba-1 layer: forward and backward.

For channel ``i`` and state ``n``, over time ``t`` (``h_{-1} = 0``)::

    h_t[i, n] = exp(delta_t[i] A[i, n]) h_{t-1}[i, n] + delta_t[i] B_t[n] c_t[i]
    y_t[i]    = sum_n C_t[n] h_t[i, n] + D[i] c_t[i]

``c, delta [Bt, S, Di]``, ``A [Di, N]`` (negative), ``B, C [Bt, S, N]``,
``D [Di]`` → ``y [Bt, S, Di]`` float32. Written out, ``h`` is ``[S, Di, N]``
float32 (5.4 GB a sequence of 16,384 at 5,120 x 16): no path here lets it, or
anything of that size, reach HBM. Two paths behind one differentiable entry
point, :func:`ssm_plan` choosing from the shapes and :func:`plan_counts`
tallying what a step traced:

- ``kernel`` — two TPU kernels, ``ssm_scan_fwd`` and ``ssm_scan_bwd``, the
  default on a TPU (interpret mode in the tests). The work is elementwise
  over (channel, state) and sequential in time, so it runs on the VPU and the
  EUP (one ``exp`` a state update) and not on the MXU. A block is 1,024
  channels laid out as one float32 register ``[8, 128]``, so a state ``h[n]``
  is a register, ``N`` of them the block's whole state, and the sum over
  ``n`` in ``y`` is ``N`` register adds with no shuffle. ``c``, ``delta`` and
  ``y`` are handed over as ``[Bt, S, Di / 1024, 8, 128]`` (XLA makes the
  copy), ``B_t[n]`` and ``C_t[n]`` are scalars in SMEM. The grid is (batch,
  channel blocks, chunks of ``T`` steps), time innermost with ``h`` in VMEM
  scratch across chunks. The forward saves the state at each chunk's start
  (``[S / T, Di, N]``: 42 MB at ``T`` 128). The backward walks the chunks
  last to first: it rebuilds a chunk's ``T + 1`` states into VMEM from the
  saved one, then walks the chunk backwards carrying ``dL/dh``; ``dA`` and
  ``dD`` accumulate over time in blocks that stay in VMEM, ``dB_t[n]`` and
  ``dC_t[n]`` are a block's sum over its channels (one register reduced to a
  scalar each, written to SMEM) and are summed over the channel blocks
  outside. Channels are padded with zeros to a multiple of 1,024.
- ``xla`` — the same chunking in plain XLA: a ``lax.scan`` over chunks, each
  under ``jax.checkpoint`` (so its backward holds one chunk's states), inside
  it a ``lax.scan`` over the chunk's steps. Differentiates itself. The
  default off the TPU, and wherever no chunk divides ``S``.

``SSM_BACKEND`` (``kernel`` | ``xla``) overrides the default, as
``GMM_BACKEND`` does for the grouped matmul. Under a device mesh of more than
one device the scan takes the XLA form whatever was asked: GSPMD cannot
partition a Mosaic kernel, and the kernels have no ``shard_map`` over the
batch yet (``ops/flash_attention.py::_mesh_partition`` is the pattern).
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

from .backend import backend_from_env

__all__ = ["selective_scan", "ssm_plan", "plan_counts", "default_backend"]

_SUB, _LANES = 8, 128
_TILE = _SUB * _LANES      # channels of a block: one float32 register a state
_KERNEL_CHUNK = 128        # time steps a grid step; the backward holds T + 1 states in VMEM
_XLA_CHUNK = 256
# The backward's rebuilt states are (T + 1) * N registers: 8.3 MiB at 128 x 16,
# beside the double-buffered blocks of five [T, 8, 128] operands.
_VMEM_LIMIT = 48 * 2**20


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def default_backend() -> str:
    """``kernel`` on a TPU, ``xla`` elsewhere; ``SSM_BACKEND`` overrides (the
    tests force ``kernel`` to run the kernels under interpret mode)."""
    return backend_from_env("SSM_BACKEND", "kernel", "xla")


class SsmPlan(NamedTuple):
    path: str    # "kernel" | "xla"
    chunk: int   # time steps a chunk


def _fit_chunk(chunk: int, S: int) -> int:
    while chunk > 1 and S % chunk:
        chunk //= 2
    return chunk


def ssm_plan(S: int, backend: Optional[str] = None, chunk: Optional[int] = None) -> SsmPlan:
    """Which path a scan over ``S`` steps takes, and its chunk; a pure function
    of its arguments and the backend. A chunk the caller names is fitted to
    ``S`` (halved until it divides); the kernels need one of at least 8."""
    backend = backend or default_backend()
    if backend not in ("kernel", "xla"):
        raise ValueError(f"unknown selective-scan backend {backend!r} (kernel | xla)")
    if backend == "kernel":
        t = _fit_chunk(chunk or _KERNEL_CHUNK, S)
        if t >= 8:
            return SsmPlan("kernel", t)
    return SsmPlan("xla", _fit_chunk(chunk or _XLA_CHUNK, S))


# Chosen while tracing, so this counts traces (as ops/flash_attention.py does).
_plan_counts: Dict[str, int] = collections.Counter()
_plan_counts_lock = threading.Lock()


def _count(*keys: str) -> None:
    with _plan_counts_lock:
        _plan_counts.update(keys)


def plan_counts() -> Dict[str, int]:
    """Scans traced so far in this process: ``fwd_kernel`` / ``bwd_kernel``
    calls of the two kernels, ``xla`` scans in the XLA form (forward; its
    backward is autodiff's), and each again by chunk (``fwd_kernel_chunk128``)."""
    with _plan_counts_lock:
        return {**{k: _plan_counts[k] for k in ("fwd_kernel", "bwd_kernel", "xla")},
                **{k: n for k, n in sorted(_plan_counts.items())
                   if k not in ("fwd_kernel", "bwd_kernel", "xla")}}


# -- the XLA form -----------------------------------------------------------------
def _scan_xla(c, delta, A, B, C, D, chunk: int):
    Bt, S, Di = c.shape
    N = A.shape[1]
    f32 = lambda a: a.astype(jnp.float32)
    c, delta, A, B, C, D = (f32(a) for a in (c, delta, A, B, C, D))

    def step(h, s):
        x_t, dt_t, b_t, c_t = s                                   # [Bt, Di], [Bt, N]
        h = jnp.exp(dt_t[..., None] * A) * h + (dt_t * x_t)[..., None] * b_t[:, None, :]
        return h, jnp.einsum("bdn,bn->bd", h, c_t) + D * x_t

    @jax.checkpoint
    def one_chunk(h, xs):
        return jax.lax.scan(step, h, xs)

    split = lambda a: a.swapaxes(0, 1).reshape((S // chunk, chunk) + (Bt, a.shape[-1]))
    _, ys = jax.lax.scan(one_chunk, jnp.zeros((Bt, Di, N), jnp.float32),
                         tuple(split(a) for a in (c, delta, B, C)))
    return ys.reshape(S, Bt, Di).swapaxes(0, 1)


# -- the kernels --------------------------------------------------------------------
_UNROLL = 2   # time steps a loop trip (Mosaic's own unroll takes 1 or the whole loop)


def _walk(T: int, step, init):
    """``step(t, carry)`` for ``t`` in ``0 .. T - 1``, ``_UNROLL`` a trip."""
    u = _UNROLL if T % _UNROLL == 0 else 1

    def trip(i, carry):
        for j in range(u):
            carry = step(i * u + j, carry)
        return carry

    return jax.lax.fori_loop(0, T // u, trip, init)


def _fwd_kernel(b_s, c_s, x_ref, dt_ref, a_ref, d_ref, y_ref, hb_ref, h_scr, *, T, N):
    @pl.when(pl.program_id(2) == 0)
    def _():
        h_scr[...] = jnp.zeros_like(h_scr)

    hb_ref[...] = h_scr[...]          # the state this chunk starts from, for the backward
    d = d_ref[...]

    def step(t, h):
        dt, x = dt_ref[t], x_ref[t]
        dbx = dt * x
        y = d * x
        new = []
        for n in range(N):
            hn = jnp.exp(dt * a_ref[n]) * h[n] + dbx * b_s[0, t * N + n]
            y = y + hn * c_s[0, t * N + n]
            new.append(hn)
        y_ref[t] = y
        return tuple(new)

    h = _walk(T, step, tuple(h_scr[n] for n in range(N)))
    for n in range(N):
        h_scr[n] = h[n]


def _bwd_kernel(b_s, c_s, x_ref, dt_ref, dy_ref, a_ref, d_ref, hb_ref,
                dx_ref, ddt_ref, db_s, dc_s, da_ref, dd_ref, hist, g_scr, *, T, N):
    # the grid's last axis walks the chunks last to first (the index maps turn it round)
    @pl.when(pl.program_id(2) == 0)
    def _():
        g_scr[...] = jnp.zeros_like(g_scr)
        da_ref[...] = jnp.zeros_like(da_ref)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    def rebuild(t, h):                 # hist[t] = h_{t-1}, hist[T] = the chunk's last state
        dt = dt_ref[t]
        dbx = dt * x_ref[t]
        new = []
        for n in range(N):
            hist[t, n] = h[n]
            new.append(jnp.exp(dt * a_ref[n]) * h[n] + dbx * b_s[0, t * N + n])
        return tuple(new)

    h = _walk(T, rebuild, tuple(hb_ref[n] for n in range(N)))
    for n in range(N):
        hist[T, n] = h[n]
    d = d_ref[...]

    def back(i, carry):
        g, dd = carry                   # g[n] = exp(delta_{t+1} A) dL/dh_{t+1}
        t = T - 1 - i
        dt, x, dy = dt_ref[t], x_ref[t], dy_ref[t]
        dtx = dt * x
        acc = jnp.zeros_like(dy)        # sum_n dL/dh_t[n] B_t[n]
        acc_dt = jnp.zeros_like(dy)     # sum_n dL/dh_t[n] A[n] a_t[n] h_{t-1}[n]
        new = []
        for n in range(N):
            a_n = a_ref[n]
            a = jnp.exp(dt * a_n)
            gn = g[n] + dy * c_s[0, t * N + n]
            dc_s[0, t * N + n] = jnp.sum(dy * hist[t + 1, n])
            db_s[0, t * N + n] = jnp.sum(gn * dtx)
            acc = acc + gn * b_s[0, t * N + n]
            ga = gn * a
            tmp = ga * hist[t, n]
            da_ref[n] = da_ref[n] + tmp * dt
            acc_dt = acc_dt + tmp * a_n
            new.append(ga)
        dx_ref[t] = d * dy + dt * acc
        ddt_ref[t] = x * acc + acc_dt
        return tuple(new), dd + dy * x

    g, dd = _walk(T, back, (tuple(g_scr[n] for n in range(N)), jnp.zeros_like(d)))
    for n in range(N):
        g_scr[n] = g[n]
    dd_ref[...] = dd_ref[...] + dd


def _spec(block, index_map, smem: bool = False):
    if _interpret():
        return pl.BlockSpec(block, index_map)
    return pl.BlockSpec(block, index_map,
                        memory_space=pltpu.SMEM if smem else pltpu.VMEM)


def _params():
    return None if _interpret() else pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT)


def _blocked(c, delta, A, B, C, D):
    """The operands as the kernels take them: channels padded to whole blocks
    and laid out a register a block, ``A`` state-major, ``B`` and ``C`` flat."""
    Bt, S, Di = c.shape
    N = A.shape[1]
    pad = -Di % _TILE
    nD = (Di + pad) // _TILE
    f32 = lambda a: a.astype(jnp.float32)
    chan = lambda a: jnp.pad(f32(a), [(0, 0)] * (a.ndim - 1) + [(0, pad)])
    x5 = chan(c).reshape(Bt, S, nD, _SUB, _LANES)
    dt5 = chan(delta).reshape(Bt, S, nD, _SUB, _LANES)
    a4 = chan(A.T).reshape(N, nD, _SUB, _LANES).swapaxes(0, 1)
    d3 = chan(D).reshape(nD, _SUB, _LANES)
    return x5, dt5, a4, f32(B).reshape(Bt, S * N), f32(C).reshape(Bt, S * N), d3


def _by_chunk(flat, T: int, N: int):
    """``[..., S * N]`` → ``[..., S / T, 1, T * N]``: a chunk's scalars as one SMEM
    block whose last two dimensions are the array's own."""
    return flat.reshape(flat.shape[:-1] + (-1, 1, T * N))


def _fwd_call(x5, dt5, a4, b2, c2, d3, T: int):
    Bt, S, nD = x5.shape[:3]
    N = a4.shape[1]
    nC = S // T
    time = _spec((None, T, None, _SUB, _LANES), lambda b, d, k: (b, k, d, 0, 0))
    flat = _spec((None, None, 1, T * N), lambda b, d, k: (b, k, 0, 0), smem=True)
    _count("fwd_kernel", f"fwd_kernel_chunk{T}")
    return pl.pallas_call(
        functools.partial(_fwd_kernel, T=T, N=N),
        grid=(Bt, nD, nC),
        in_specs=[flat, flat, time, time,
                  _spec((None, N, _SUB, _LANES), lambda b, d, k: (d, 0, 0, 0)),
                  _spec((None, _SUB, _LANES), lambda b, d, k: (d, 0, 0))],
        out_specs=[time, _spec((None, None, None, N, _SUB, _LANES),
                               lambda b, d, k: (b, k, d, 0, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(x5.shape, jnp.float32),
                   jax.ShapeDtypeStruct((Bt, nC, nD, N, _SUB, _LANES), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((N, _SUB, _LANES), jnp.float32)],
        compiler_params=_params(),
        interpret=_interpret(),
        name="ssm_scan_fwd",
    )(_by_chunk(b2, T, N), _by_chunk(c2, T, N), x5, dt5, a4, d3)


def _bwd_call(x5, dt5, dy5, a4, b2, c2, d3, hb, T: int):
    Bt, S, nD = x5.shape[:3]
    N = a4.shape[1]
    nC = S // T
    rev = lambda k: nC - 1 - k
    time = _spec((None, T, None, _SUB, _LANES), lambda b, d, k: (b, rev(k), d, 0, 0))
    flat = _spec((None, None, 1, T * N), lambda b, d, k: (b, rev(k), 0, 0), smem=True)
    flat_out = _spec((None, None, None, 1, T * N), lambda b, d, k: (b, d, rev(k), 0, 0), smem=True)
    _count("bwd_kernel", f"bwd_kernel_chunk{T}")
    return pl.pallas_call(
        functools.partial(_bwd_kernel, T=T, N=N),
        grid=(Bt, nD, nC),
        in_specs=[flat, flat, time, time, time,
                  _spec((None, N, _SUB, _LANES), lambda b, d, k: (d, 0, 0, 0)),
                  _spec((None, _SUB, _LANES), lambda b, d, k: (d, 0, 0)),
                  _spec((None, None, None, N, _SUB, _LANES),
                        lambda b, d, k: (b, rev(k), d, 0, 0, 0))],
        out_specs=[time, time, flat_out, flat_out,
                   _spec((None, None, N, _SUB, _LANES), lambda b, d, k: (b, d, 0, 0, 0)),
                   _spec((None, None, _SUB, _LANES), lambda b, d, k: (b, d, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(x5.shape, jnp.float32),
                   jax.ShapeDtypeStruct(x5.shape, jnp.float32),
                   jax.ShapeDtypeStruct((Bt, nD, nC, 1, T * N), jnp.float32),
                   jax.ShapeDtypeStruct((Bt, nD, nC, 1, T * N), jnp.float32),
                   jax.ShapeDtypeStruct((Bt, nD, N, _SUB, _LANES), jnp.float32),
                   jax.ShapeDtypeStruct((Bt, nD, _SUB, _LANES), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((T + 1, N, _SUB, _LANES), jnp.float32),
                        pltpu.VMEM((N, _SUB, _LANES), jnp.float32)],
        compiler_params=_params(),
        interpret=_interpret(),
        name="ssm_scan_bwd",
    )(_by_chunk(b2, T, N), _by_chunk(c2, T, N), x5, dt5, dy5, a4, d3, hb)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _scan_kernel(c, delta, A, B, C, D, T):
    return _scan_kernel_fwd(c, delta, A, B, C, D, T)[0]


def _scan_kernel_fwd(c, delta, A, B, C, D, T):
    Bt, S, Di = c.shape
    ops = _blocked(c, delta, A, B, C, D)
    y5, hb = _fwd_call(*ops, T)
    # dtype-only stand-ins, so the cotangents come back in the operands' dtypes
    like = tuple(jnp.zeros((), a.dtype) for a in (c, delta, A, B, C, D))
    return y5.reshape(Bt, S, -1)[..., :Di], (ops, hb, like)


def _scan_kernel_bwd(T, res, dy):
    (x5, dt5, a4, b2, c2, d3), hb, like = res
    Bt, S, nD = x5.shape[:3]
    N = a4.shape[1]
    Di = dy.shape[-1]
    pad = nD * _TILE - Di
    dy5 = jnp.pad(dy.astype(jnp.float32), [(0, 0), (0, 0), (0, pad)]).reshape(x5.shape)
    dx5, ddt5, db, dc, da, dd = _bwd_call(x5, dt5, dy5, a4, b2, c2, d3, hb, T)
    chan = lambda a5: a5.reshape(Bt, S, -1)[..., :Di]
    dA = da.sum(0).swapaxes(0, 1).reshape(N, -1)[:, :Di].T
    out = (chan(dx5), chan(ddt5), dA, db.sum(1).reshape(Bt, S, N), dc.sum(1).reshape(Bt, S, N),
           dd.sum(0).reshape(-1)[:Di])
    return tuple(g.astype(z.dtype) for g, z in zip(out, like))


_scan_kernel.defvjp(_scan_kernel_fwd, _scan_kernel_bwd)


def selective_scan(c, delta, A, B, C, D, *, backend: Optional[str] = None,
                   chunk: Optional[int] = None):
    """``y [Bt, S, Di]`` float32 of the recurrence in this module's docstring,
    differentiable in all six operands. The arithmetic is float32 whatever the
    operands' dtypes."""
    from ..parallel.context import current_mesh

    mesh = current_mesh()
    if mesh is not None and mesh.size > 1:
        backend = "xla"
    plan = ssm_plan(c.shape[1], backend, chunk)
    if plan.path == "kernel":
        return _scan_kernel(c, delta, A, B, C, D, plan.chunk)
    _count("xla", f"xla_chunk{plan.chunk}")
    return _scan_xla(c, delta, A, B, C, D, plan.chunk)
