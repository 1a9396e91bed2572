"""Chunked (fused) cross-entropy over the vocabulary projection.

The naive LM loss materializes fp32 logits ``[B, S, V]`` — at a bench-scale
shape (B16 x S2048 x V32768) that is 4.3 GB of HBM written by the forward,
read by the softmax, and re-touched by the backward: the single largest
memory consumer in the whole step, and pure bandwidth (the reference pays
the same cost: core/training.py compute_loss materializes full logits).

This is the standard TPU trick instead: fold the output projection INTO the
loss and walk the rows in chunks inside a ``lax.scan``, so that nothing of
size ``[N, V]`` outlives its chunk. :func:`fused_cross_entropy` is a
``jax.custom_vjp``; JAX, by differentiating it or not, picks what a chunk
does (:func:`plan_counts` tallies which was traced):

- not differentiated (evaluation, validation): one ``[N, D] @ [D, V]`` MXU
  matmul a chunk (operands in the compute dtype, fp32 accumulation) ->
  logsumexp + gold-logit gather -> scalar partial sum. Peak logits memory
  is ``chunk x V`` fp32 instead of ``B*S x V``.
- differentiated: the same matmul and loss terms, and from the same logits
  the gradient of the loss with respect to them, ``(softmax - onehot) *
  mask``, which needs nothing the chunk does not already hold; two more
  matmuls turn it into the chunk's ``dX`` rows and add its share to ``dW``.
  The backward pass only scales ``dX``, ``dW`` and ``dbias`` by the loss's
  scalar cotangent. Three matmuls a chunk, the FLOPs of the naive path; no
  ``jax.checkpoint``, so no logits are computed twice.

Between the logits matmul and the two gradient matmuls the differentiated
walk runs **one Pallas kernel** (``ce_softmax_grad``) that reads the chunk's
fp32 logits from HBM once and writes ``d`` once, in the compute dtype: a
block of whole rows in VMEM, and for 16 rows at a time the row max, the sum
of ``exp(x - max)`` with the exponentials kept in VMEM, ``logz``, then
``d = (e / sum - onehot [+ 2 z_weight logz e / sum]) * mask [* logit_scale]``
with the gold logit picked on the way. XLA's chain for the same (it takes the
row max in the matmul's epilogue, then reads the 1.6 GB of a 2,048 x 200,064
chunk once for the sum of exponentials and once more for ``d``, the
exponential taken twice: 5.7 ms a chunk on a v5e against the kernel's 3.6;
PERF.md section 6, PR 45) runs where the shapes keep the kernel out, and
shapes alone decide (:func:`softmax_grad_rows`): a vocabulary that is no
whole number of 128-lane registers (25,024 is 195.5), one so wide that 16
whole rows do not fit the VMEM the call asks for, a ``bias_v`` (its gradient
is summed from the fp32 ``d``), and the forward-only walk, which has no
``d``; and a walk that GSPMD shards over a mesh (it cannot partition a Mosaic
kernel; inside :func:`fused_cross_entropy_sp`'s ``shard_map`` the kernel
runs). :func:`plan_counts` says which a traced walk took
(``softmax_grad_kernel``, ``softmax_grad_xla``).

Only reverse mode is defined: ``jax.jvp``, ``jax.linearize`` and
``jax.jacfwd`` through :func:`fused_cross_entropy` raise (nothing in the
package takes them). Under an enclosing ``jax.checkpoint`` the forward pass
runs the one-matmul walk and the backward pass the three-matmul one: four
matmuls a chunk. A call inside a scan that is differentiated from outside
wants that wrapper (parallel/pipeline.py's head has it): bare, every
iteration keeps its fp32 ``dW`` for the backward pass.

Exactness: identical math to ``logsumexp(logits) - logits[target]`` in fp32
(same reduction, same dtype), verified against the unfused path by
tests/test_fused_ce.py and tests/test_model.py. The kernel's ``d`` differs
from the chain's in the last place of fp32 before its one rounding
(``e / sum`` against ``exp(x - logz)``), nothing coarser.
"""

from __future__ import annotations

import collections
import functools
import threading
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Counted while tracing, so this counts traces, not calls of the compiled
# step: what a jitted program runs is what its one trace counted.
_PLAN_KEYS = ("grad_in_forward", "forward_only", "softmax_grad_kernel", "softmax_grad_xla")
_plan_counts: Dict[str, int] = collections.Counter()
_plan_counts_lock = threading.Lock()


def _count_plan(key: str) -> None:
    with _plan_counts_lock:
        _plan_counts[key] += 1


def plan_counts() -> Dict[str, int]:
    """Chunk walks traced so far in this process: ``grad_in_forward`` with
    the head's gradients computed in the walk (the call was differentiated),
    ``forward_only`` with the loss alone; and what each ``grad_in_forward``
    walk does between its matmuls: ``softmax_grad_kernel`` (the Pallas
    kernel) or ``softmax_grad_xla`` (XLA's chain)."""
    with _plan_counts_lock:
        return {key: _plan_counts[key] for key in _PLAN_KEYS}


def _chunk_rows(N: int, chunk: int) -> int:
    return max(min(chunk, N), 1)


def _row_chunks(hidden, targets, mask, chunk):
    """Rows flattened, zero-padded to whole chunks (a padded row's mask is
    0) and split: ``xs [n, chunk, D]``, ``ts``, ``ms [n, chunk]``."""
    B, S, D = hidden.shape
    N = B * S
    x = hidden.reshape(N, D)
    t = targets.reshape(N).astype(jnp.int32)
    m = mask.reshape(N).astype(jnp.float32)
    chunk = _chunk_rows(N, chunk)
    n_chunks = -(-N // chunk)
    pad = n_chunks * chunk - N
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
        t = jnp.pad(t, (0, pad))
        m = jnp.pad(m, (0, pad))
    return (x.reshape(n_chunks, chunk, D), t.reshape(n_chunks, chunk),
            m.reshape(n_chunks, chunk))


def _chunk_logits(xc, w_vd, bias_v, logit_scale):
    """One chunk's fp32 logits ``[chunk, V]``, bias and scale applied."""
    logits = jax.lax.dot_general(
        xc, w_vd, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    if bias_v is not None:
        logits = logits + bias_v.astype(jnp.float32)
    if logit_scale:
        logits = logits * logit_scale
    return logits


def _loss_terms(logits, tc, mc, z_weight):
    """A chunk's logsumexp and its masked loss sum."""
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, tc[:, None], axis=-1)[:, 0]
    terms = logz - gold
    if z_weight:  # trace-time constant
        terms = terms + z_weight * jnp.square(logz)
    return logz, jnp.sum(terms * mc)


# -- between the matmuls of the differentiated walk --------------------------
# Either function: a chunk's fp32 logits, targets and mask -> its masked loss
# sum and d loss / d logits in ``dtype`` (fp32 from XLA's chain where a bias
# sums it before it is rounded).
def _softmax_grad_xla(logits, tc, mc, logit_scale, z_weight, dtype=jnp.float32):
    logz, loss_c = _loss_terms(logits, tc, mc, z_weight)
    p = jnp.exp(logits - logz[:, None])
    onehot = jax.lax.broadcasted_iota(jnp.int32, p.shape, 1) == tc[:, None]
    d = p - onehot.astype(jnp.float32)
    if z_weight:
        d = d + (2.0 * z_weight) * logz[:, None] * p
    d = d * mc[:, None]
    if logit_scale:
        d = d * logit_scale
    return loss_c, d.astype(dtype)


_LANES = 128
_GROUP = 16          # rows the kernel works on at a time: a bf16 register's sublanes
_LANE_UNROLL = 8     # lane registers a trip of the kernel's loops over V
# Mosaic's scoped limit is raised for this call (a v5e core has 128 MiB; the
# default scope is 16), as the flash kernels' resident paths do: a block of
# whole rows at a vocabulary of 200,064 is 12.8 MB of logits and 6.4 MB of d
# for 16 rows, each double-buffered by the pipeline, beside one group's
# exponentials (12.8 MB). The rest is for what the compiler spills.
_VMEM_LIMIT = 96 * 2**20
_VMEM_BUDGET = 64 * 2**20
_MAX_BLOCK_ROWS = 256   # a chunk of 2,048 stays 8 grid steps or more for the pipeline to overlap


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _whole_groups(rows: int) -> int:
    return -(-rows // _GROUP) * _GROUP


def _block_vmem_bytes(rows: int, V: int, itemsize: int) -> int:
    """VMEM a block of ``rows`` whole rows takes: logits in and d out, two
    buffers each, and the scratch that keeps one group's exponentials."""
    return 2 * rows * V * (4 + itemsize) + _GROUP * V * 4


def softmax_grad_rows(rows: int, V: int, dtype) -> int:
    """Rows of the kernel's block for a chunk of ``rows`` at a vocabulary of
    ``V`` with d in ``dtype``, a pure function of its arguments: the most
    groups of 16 that fit the budget and divide the chunk (padded to whole
    groups), and 0 (XLA's chain) where ``V`` is not whole 128-lane registers
    or 16 whole rows of it do not fit."""
    itemsize = jnp.dtype(dtype).itemsize
    if V % _LANES:
        return 0
    rows = _whole_groups(rows)
    fit = [r for r in range(_GROUP, min(rows, _MAX_BLOCK_ROWS) + 1, _GROUP)
           if rows % r == 0 and _block_vmem_bytes(r, V, itemsize) <= _VMEM_BUDGET]
    return max(fit, default=0)


def _softmax_grad_body(x_ref, t_ref, m_ref, d_ref, logz_ref, term_ref, e_scr, *,
                       logit_scale, z_weight):
    """A block of whole rows, 16 at a time, V walked three times in VMEM a
    lane register at a time: the row max; ``e = exp(x - max)`` kept and
    summed; then d from ``e``, with the gold logit picked on the way."""
    R, V = x_ref.shape
    n_lane = V // _LANES
    lane = jax.lax.broadcasted_iota(jnp.int32, (_GROUP, _LANES), 1)

    def over_v(fn, carry):
        """``carry = fn(carry, c)`` for every lane register's first column c."""
        def trip(i, carry):
            c0 = pl.multiple_of(i * (_LANE_UNROLL * _LANES), _LANES)
            for j in range(_LANE_UNROLL):
                carry = fn(carry, c0 + j * _LANES)
            return carry

        whole = n_lane // _LANE_UNROLL
        carry = jax.lax.fori_loop(0, whole, trip, carry)
        for j in range(whole * _LANE_UNROLL, n_lane):
            carry = fn(carry, j * _LANES)
        return carry

    def group(g, carry):
        rows = pl.ds(pl.multiple_of(g * _GROUP, _GROUP), _GROUP)
        at = lambda ref, c: ref[rows, pl.ds(c, _LANES)]  # noqa: E731
        across = lambda col: jnp.broadcast_to(col, (_GROUP, _LANES))  # noqa: E731
        zeros = jnp.zeros((_GROUP, _LANES), jnp.float32)

        top = over_v(lambda acc, c: jnp.maximum(acc, at(x_ref, c)),
                     jnp.full_like(zeros, -jnp.inf))
        top = jnp.max(top, axis=-1, keepdims=True)
        top_b = across(top)

        def exps(acc, c):
            e = jnp.exp(at(x_ref, c) - top_b)
            e_scr[:, pl.ds(c, _LANES)] = e
            return acc + e

        total = jnp.sum(over_v(exps, zeros), axis=-1, keepdims=True)
        logz = top + jnp.log(total)
        # d = (p - onehot + 2 z logz p) mask scale, p = e / total
        to_p = 1.0 / total
        if z_weight:
            to_p = to_p * (1.0 + (2.0 * z_weight) * logz)
        out = m_ref[rows, :] * logit_scale if logit_scale else m_ref[rows, :]
        to_p_b, out_b, from_gold = across(to_p), across(out), lane - across(t_ref[rows, :])

        def grads(gold, c):
            hit = from_gold == -c
            p = e_scr[:, pl.ds(c, _LANES)] * to_p_b
            d_ref[rows, pl.ds(c, _LANES)] = (
                jnp.where(hit, p - 1.0, p) * out_b).astype(d_ref.dtype)
            return gold + jnp.where(hit, at(x_ref, c), 0.0)

        gold = jnp.sum(over_v(grads, zeros), axis=-1, keepdims=True)
        term = logz - gold
        if z_weight:
            term = term + z_weight * jnp.square(logz)
        logz_ref[rows, :] = logz
        term_ref[rows, :] = term
        return carry

    jax.lax.fori_loop(0, R // _GROUP, group, 0)


def _softmax_grad_call(logits, tc, mc, logit_scale, z_weight, dtype):
    """One Pallas kernel that reads the logits from HBM once and writes d
    once, already rounded to ``dtype``: ``(d, logz, loss term a row)``.
    Shapes :func:`softmax_grad_rows` refuses are not for this function; rows
    that are no whole groups of 16 (no chunk of a training step) are padded."""
    n, V = logits.shape
    block = softmax_grad_rows(n, V, dtype)
    rows = _whole_groups(n)
    if rows != n:
        logits, tc, mc = (jnp.pad(a, ((0, rows - n),) + ((0, 0),) * (a.ndim - 1))
                          for a in (logits, tc, mc))
    whole = lambda i: (i, 0)  # noqa: E731
    spec = lambda width: pl.BlockSpec(  # noqa: E731
        (block, width), whole, **({} if _interpret() else {"memory_space": pltpu.VMEM}))
    d, logz, terms = pl.pallas_call(
        functools.partial(_softmax_grad_body, logit_scale=logit_scale, z_weight=z_weight),
        grid=(rows // block,),
        in_specs=[spec(V), spec(1), spec(1)],
        out_specs=[spec(V), spec(1), spec(1)],
        out_shape=[jax.ShapeDtypeStruct((rows, V), dtype),
                   jax.ShapeDtypeStruct((rows, 1), jnp.float32),
                   jax.ShapeDtypeStruct((rows, 1), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((_GROUP, V), jnp.float32)],
        compiler_params=None if _interpret() else pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interpret(),
        name="ce_softmax_grad",
    )(logits, tc[:, None], mc[:, None])
    return d[:n], logz[:n, 0], terms[:n, 0]


def _softmax_grad_kernel(logits, tc, mc, logit_scale, z_weight, dtype):
    """The same as :func:`_softmax_grad_xla` through the kernel."""
    d, _, terms = _softmax_grad_call(logits, tc, mc, logit_scale, z_weight, dtype)
    return jnp.sum(terms * mc), d


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _fused(hidden, w_vd, targets, mask, bias_v, logit_scale, chunk, z_weight):
    _count_plan("forward_only")

    def body(acc, inp):
        xc, tc, mc = inp
        logits = _chunk_logits(xc, w_vd, bias_v, logit_scale)
        return acc + _loss_terms(logits, tc, mc, z_weight)[1], None

    acc, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32),
                          _row_chunks(hidden, targets, mask, chunk))
    return acc


def _grad_walk(hidden, w_vd, targets, mask, bias_v, logit_scale, chunk, z_weight,
               softmax_grad: Callable):
    """The differentiated walk: a chunk's logits, ``softmax_grad`` of them
    (the kernel or XLA's chain), ``dX`` of its rows and its share of ``dW``.
    Returns the loss sum and the fp32 ``(dx, dw, db)``."""
    B, S, D = hidden.shape
    V = w_vd.shape[0]

    def body(carry, inp):
        acc, dw, db = carry
        xc, tc, mc = inp
        logits = _chunk_logits(xc, w_vd, bias_v, logit_scale)
        # d loss / d logits, from the logits and logsumexp the loss uses; a
        # bias takes it in fp32: no matmul, summed before rounding
        loss_c, d = softmax_grad(logits, tc, mc, logit_scale, z_weight,
                                 xc.dtype if bias_v is None else jnp.float32)
        if bias_v is not None:
            db = db + jnp.sum(d, axis=0)
        # Operands in the compute dtype, fp32 accumulation: what the MXU makes
        # of autodiff's fp32 d as well. Behind the barrier d is written once
        # and read by both matmuls; left to itself XLA recomputes it, exp and
        # all, inside each (98.8 against 90.8 ms for the head at 16,384 x 4,096
        # x 32,768 on a v5e; PERF.md section 6, PR 30). dW is summed across
        # chunks in fp32 and rounded once, in the backward pass.
        d = jax.lax.optimization_barrier(d.astype(xc.dtype))
        dx = jax.lax.dot_general(d, w_vd, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        dw = dw + jax.lax.dot_general(d, xc, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        return (acc + loss_c, dw, db), dx

    db0 = None if bias_v is None else jnp.zeros((V,), jnp.float32)
    (acc, dw, db), dxs = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((V, D), jnp.float32), db0),
        _row_chunks(hidden, targets, mask, chunk))
    return acc, (dxs.reshape(-1, D)[:B * S].reshape(B, S, D), dw, db)


def _left_to_gspmd() -> bool:
    """A mesh of more than one device is active and this trace is not manual
    over it (as it is inside :func:`fused_cross_entropy_sp`'s ``shard_map``):
    GSPMD cannot partition a Mosaic kernel, so the walk it shards takes XLA's
    chain (``ops/flash_attention.py::_mesh_partition`` asks the same)."""
    from ..parallel.context import current_mesh

    mesh = current_mesh()
    if mesh is None or mesh.size == 1:
        return False
    manual = set(jax.sharding.get_abstract_mesh().manual_axes)
    return any(mesh.shape[a] > 1 and a not in manual for a in mesh.axis_names)


def _fused_fwd(hidden, w_vd, targets, mask, bias_v, logit_scale, chunk, z_weight):
    _count_plan("grad_in_forward")
    # Shapes decide: whole 128-lane registers of V, 16 whole rows of it in
    # VMEM, and no bias (its gradient is summed from the fp32 d).
    rows = _chunk_rows(hidden.shape[0] * hidden.shape[1], chunk)
    kernel = (bias_v is None and not _left_to_gspmd()
              and softmax_grad_rows(rows, w_vd.shape[0], hidden.dtype) > 0)
    _count_plan("softmax_grad_kernel" if kernel else "softmax_grad_xla")
    acc, grads = _grad_walk(hidden, w_vd, targets, mask, bias_v, logit_scale, chunk, z_weight,
                            _softmax_grad_kernel if kernel else _softmax_grad_xla)
    # fp32 residuals; an empty array of each input's dtype says what the
    # backward pass rounds to, once, after scaling
    like = tuple(None if a is None else jnp.zeros((0,), a.dtype)
                 for a in (hidden, w_vd, bias_v))
    return acc, (grads, like)


def _fused_bwd(logit_scale, chunk, z_weight, res, g):
    (dx, dw, db), like = res
    dx, dw, db = (None if r is None else (g * r).astype(a.dtype)
                  for r, a in zip((dx, dw, db), like))
    return dx, dw, None, None, db  # integer targets and the mask get none


_fused.defvjp(_fused_fwd, _fused_bwd)


def fused_cross_entropy(
    hidden: jnp.ndarray,
    w_vd: jnp.ndarray,
    targets: jnp.ndarray,
    mask: jnp.ndarray,
    bias_v: Optional[jnp.ndarray] = None,
    logit_scale: Optional[float] = None,
    chunk: int = 2048,
    z_weight: float = 0.0,
):
    """Masked NLL sum without materializing full logits.

    hidden  [B, S, D]  final hidden states (compute dtype, e.g. bf16)
    w_vd    [V, D]     output embedding (same dtype as hidden for the MXU)
    targets [B, S]     int32
    mask    [B, S]     0/1
    bias_v  [V]        optional output-projection bias
    Returns the fp32 scalar ``sum(mask * (nll + z_weight * logsumexp^2))``
    (caller divides by the token count): the masked token NLLs plus, for
    ``z_weight > 0``, the z-loss regularizer (PaLM-style logit-drift
    control) from the same per-chunk logsumexp at zero extra memory.
    ``logit_scale``, ``chunk`` and ``z_weight`` are Python numbers.

    Differentiable in reverse mode only, with respect to ``hidden``,
    ``w_vd`` and ``bias_v``; ``targets`` and ``mask`` get no gradient.
    """
    return _fused(hidden, w_vd, targets, mask, bias_v, logit_scale or None,
                  int(chunk), float(z_weight))


def auto_chunk(batch: int, seq: int, vocab: int) -> int:
    """Chunk-size policy for ``fused_ce_chunk: -1`` (auto).

    Fused CE costs the naive path's three projection matmuls and a scan
    (and reverse-mode differentiation only: no ``jvp`` through it); it wins
    when the full logits tensor is HBM-significant. Threshold: enable when
    ``B*S*V`` fp32 exceeds 256 MB, with 2048-row chunks (a 2048 x 32k fp32
    chunk is 256 MB peak — comfortably resident)."""
    if batch * seq * vocab * 4 < 256 * 1024 * 1024:
        return 0
    return 2048


def fused_cross_entropy_sp(
    hidden: jnp.ndarray,
    w_vd: jnp.ndarray,
    targets: jnp.ndarray,
    mask: jnp.ndarray,
    mesh,
    bias_v: Optional[jnp.ndarray] = None,
    logit_scale: Optional[float] = None,
    chunk: int = 2048,
    z_weight: float = 0.0,
):
    """Sequence-sharded fused CE for sp (context-parallel) meshes.

    The flat-row reshape in :func:`fused_cross_entropy` has no valid GSPMD
    sharding when the sequence dim is sharded, which previously forced sp
    runs back to full [B, S, V] logits — the exact memory hog fused CE
    exists to avoid, and sp runs are where S is LONGEST. This variant
    drops to ``shard_map``: every device runs the chunked fused CE on its
    own local [B_local, S_local] block (chunking over local rows), and one
    ``psum`` reduces the masked loss sums. Requires the vocab projection
    replicated — i.e. ``tp == 1`` (with tp, the projection is
    vocab-sharded and GSPMD's own vocab-parallel handling of the unfused
    path applies instead).

    Exactness: identical math to the single-device path — the row chunks
    are just distributed; the psum is the same fp32 sum re-associated per
    device (tests assert loss AND grad parity on a dp x sp mesh). Reverse
    mode only, like :func:`fused_cross_entropy`.
    """
    from jax.sharding import PartitionSpec as P

    def size(a):
        return mesh.shape.get(a, 1)

    assert size("tp") == 1, (
        "fused_cross_entropy_sp needs a replicated vocab projection "
        "(tp == 1); with tp the unfused path is already vocab-parallel")
    data_axes = tuple(a for a in ("dp", "fsdp", "ep") if size(a) > 1)
    b_axes = data_axes if data_axes else None
    seq_axis = "sp" if size("sp") > 1 else None

    in_specs = [P(b_axes, seq_axis, None), P(None, None),
                P(b_axes, seq_axis), P(b_axes, seq_axis)]
    args = [hidden, w_vd, targets, mask]
    if bias_v is not None:
        in_specs.append(P(None))
        args.append(bias_v)

    def local(h, w, t, m, *rest):
        b = rest[0] if rest else None
        loss = fused_cross_entropy(h, w, t, m, bias_v=b, logit_scale=logit_scale,
                                   chunk=chunk, z_weight=z_weight)
        return jax.lax.psum(loss, tuple(mesh.axis_names))

    fn = jax.shard_map(local, mesh=mesh, in_specs=tuple(in_specs),
                       out_specs=P(), check_vma=False)
    return fn(*args)
