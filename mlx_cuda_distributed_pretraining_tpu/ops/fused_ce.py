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

Only reverse mode is defined: ``jax.jvp``, ``jax.linearize`` and
``jax.jacfwd`` through :func:`fused_cross_entropy` raise (nothing in the
package takes them). Under an enclosing ``jax.checkpoint`` the forward pass
runs the one-matmul walk and the backward pass the three-matmul one: four
matmuls a chunk. A call inside a scan that is differentiated from outside
wants that wrapper (parallel/pipeline.py's head has it): bare, every
iteration keeps its fp32 ``dW`` for the backward pass.

Exactness: identical math to ``logsumexp(logits) - logits[target]`` in fp32
(same reduction, same dtype), verified against the unfused path by
tests/test_fused_ce.py and tests/test_model.py.
"""

from __future__ import annotations

import collections
import functools
import threading
from typing import Dict, Optional

import jax
import jax.numpy as jnp

# Counted while tracing, so this counts traces, not calls of the compiled
# step: what a jitted program runs is what its one trace counted.
_PLAN_KEYS = ("grad_in_forward", "forward_only")
_plan_counts: Dict[str, int] = collections.Counter()
_plan_counts_lock = threading.Lock()


def _count_plan(key: str) -> None:
    with _plan_counts_lock:
        _plan_counts[key] += 1


def plan_counts() -> Dict[str, int]:
    """Chunk walks traced so far in this process: ``grad_in_forward`` with
    the head's gradients computed in the walk (the call was differentiated),
    ``forward_only`` with the loss alone."""
    with _plan_counts_lock:
        return {key: _plan_counts[key] for key in _PLAN_KEYS}


def _row_chunks(hidden, targets, mask, chunk):
    """Rows flattened, zero-padded to whole chunks (a padded row's mask is
    0) and split: ``xs [n, chunk, D]``, ``ts``, ``ms [n, chunk]``."""
    B, S, D = hidden.shape
    N = B * S
    x = hidden.reshape(N, D)
    t = targets.reshape(N).astype(jnp.int32)
    m = mask.reshape(N).astype(jnp.float32)
    chunk = max(min(chunk, N), 1)
    n_chunks = -(-N // chunk)
    pad = n_chunks * chunk - N
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
        t = jnp.pad(t, (0, pad))
        m = jnp.pad(m, (0, pad))
    return (x.reshape(n_chunks, chunk, D), t.reshape(n_chunks, chunk),
            m.reshape(n_chunks, chunk))


def _chunk_loss(xc, tc, mc, w_vd, bias_v, logit_scale, z_weight):
    """One chunk's fp32 logits, their logsumexp, and its masked loss sum."""
    logits = jax.lax.dot_general(
        xc, w_vd, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    if bias_v is not None:
        logits = logits + bias_v.astype(jnp.float32)
    if logit_scale:
        logits = logits * logit_scale
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, tc[:, None], axis=-1)[:, 0]
    terms = logz - gold
    if z_weight:  # trace-time constant
        terms = terms + z_weight * jnp.square(logz)
    return logits, logz, jnp.sum(terms * mc)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _fused(hidden, w_vd, targets, mask, bias_v, logit_scale, chunk, z_weight):
    _count_plan("forward_only")

    def body(acc, inp):
        xc, tc, mc = inp
        _, _, loss_c = _chunk_loss(xc, tc, mc, w_vd, bias_v, logit_scale, z_weight)
        return acc + loss_c, None

    acc, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32),
                          _row_chunks(hidden, targets, mask, chunk))
    return acc


def _fused_fwd(hidden, w_vd, targets, mask, bias_v, logit_scale, chunk, z_weight):
    _count_plan("grad_in_forward")
    B, S, D = hidden.shape
    V = w_vd.shape[0]

    def body(carry, inp):
        acc, dw, db = carry
        xc, tc, mc = inp
        logits, logz, loss_c = _chunk_loss(xc, tc, mc, w_vd, bias_v, logit_scale,
                                           z_weight)
        # d loss / d logits, from the logits and logsumexp the loss just used
        p = jnp.exp(logits - logz[:, None])
        onehot = jax.lax.broadcasted_iota(jnp.int32, p.shape, 1) == tc[:, None]
        d = p - onehot.astype(jnp.float32)
        if z_weight:
            d = d + (2.0 * z_weight) * logz[:, None] * p
        d = d * mc[:, None]
        if logit_scale:
            d = d * logit_scale
        # Operands in the compute dtype, fp32 accumulation: what the MXU makes
        # of autodiff's fp32 d as well. Behind the barrier d is written once
        # and read by both matmuls; left to itself XLA recomputes it, exp and
        # all, inside each (98.8 against 90.8 ms for the head at 16,384 x 4,096
        # x 32,768 on a v5e; PERF.md section 6, PR 30). dW is summed across
        # chunks in fp32 and rounded once, in the backward pass.
        if bias_v is not None:
            db = db + jnp.sum(d, axis=0)  # no matmul: summed before rounding
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
    dx = dxs.reshape(-1, D)[:B * S].reshape(B, S, D)
    # fp32 residuals; an empty array of each input's dtype says what the
    # backward pass rounds to, once, after scaling
    like = tuple(None if a is None else jnp.zeros((0,), a.dtype)
                 for a in (hidden, w_vd, bias_v))
    return acc, ((dx, dw, db), like)


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
