"""Chunked (fused) cross-entropy over the vocabulary projection.

The naive LM loss materializes fp32 logits ``[B, S, V]`` — at a bench-scale
shape (B16 x S2048 x V32768) that is 4.3 GB of HBM written by the forward,
read by the softmax, and re-touched by the backward: the single largest
memory consumer in the whole step, and pure bandwidth (the reference pays
the same cost: core/training.py compute_loss materializes full logits).

This is the standard TPU trick instead: fold the output projection INTO the
loss and compute it in row chunks under ``jax.checkpoint`` inside a
``lax.scan``:

- forward: for each chunk of N rows, one ``[N, D] @ [D, V]`` MXU matmul
  (bf16 operands, fp32 accumulation) -> logsumexp + gold-logit gather ->
  scalar partial sum. Peak logits memory is ``chunk x V`` fp32 (a few
  hundred MB at most) instead of ``B*S x V``.
- backward: ``jax.checkpoint`` recomputes each chunk's logits, so the
  softmax Jacobian never exists whole either; the scan accumulates dW
  across chunks and emits per-chunk dX. FLOPs are identical to the naive
  path + one extra forward matmul per chunk (the remat), traded for ~3x
  less HBM traffic at the projection.

Exactness: identical math to ``logsumexp(logits) - logits[target]`` in fp32
(same reduction, same dtype), verified against the unfused path by
tests/test_model.py.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def fused_cross_entropy(
    hidden: jnp.ndarray,
    w_vd: jnp.ndarray,
    targets: jnp.ndarray,
    mask: jnp.ndarray,
    bias_v: Optional[jnp.ndarray] = None,
    logit_scale: Optional[float] = None,
    chunk: int = 2048,
    with_z: bool = False,
):
    """Masked NLL sum without materializing full logits.

    hidden  [B, S, D]  final hidden states (compute dtype, e.g. bf16)
    w_vd    [V, D]     output embedding (same dtype as hidden for the MXU)
    targets [B, S]     int32
    mask    [B, S]     0/1
    bias_v  [V]        optional output-projection bias
    Returns the fp32 scalar sum of masked token NLLs (caller divides by
    the token count); with ``with_z`` returns ``(nll_sum, z_sum)`` where
    z_sum is the masked sum of logsumexp(logits)^2 — the z-loss
    regularizer's numerator (PaLM-style logit-drift control), computed
    from the same per-chunk logsumexp at zero extra memory.
    """
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
    xs = x.reshape(n_chunks, chunk, D)
    ts = t.reshape(n_chunks, chunk)
    ms = m.reshape(n_chunks, chunk)

    def body(acc, inp):
        xc, tc, mc = inp
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
        nll_c = jnp.sum((logz - gold) * mc)
        if with_z:  # trace-time constant: pure-CE callers keep one carry
            nll_acc, z_acc = acc
            return (nll_acc + nll_c, z_acc + jnp.sum(jnp.square(logz) * mc)), None
        return acc + nll_c, None

    init = ((jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32))
            if with_z else jnp.zeros((), jnp.float32))
    acc, _ = jax.lax.scan(jax.checkpoint(body), init, (xs, ts, ms))
    return acc  # (nll_sum, z_sum) when with_z, else the nll_sum scalar


def auto_chunk(batch: int, seq: int, vocab: int) -> int:
    """Chunk-size policy for ``fused_ce_chunk: -1`` (auto).

    Fused CE pays one extra projection matmul per chunk (the remat); it wins
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
    with_z: bool = False,
):
    """Sequence-sharded fused CE for sp (context-parallel) meshes.

    The flat-row reshape in :func:`fused_cross_entropy` has no valid GSPMD
    sharding when the sequence dim is sharded, which previously forced sp
    runs back to full [B, S, V] logits — the exact memory hog fused CE
    exists to avoid, and sp runs are where S is LONGEST. This variant
    drops to ``shard_map``: every device runs the chunked fused CE on its
    own local [B_local, S_local] block (chunking over local rows), and one
    ``psum`` reduces the masked NLL sums. Requires the vocab projection
    replicated — i.e. ``tp == 1`` (with tp, the projection is
    vocab-sharded and GSPMD's own vocab-parallel handling of the unfused
    path applies instead).

    Exactness: identical math to the single-device path — the row chunks
    are just distributed; the psum is the same fp32 sum re-associated per
    device (tests assert loss AND grad parity on a dp x sp mesh).
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
        nll, z = fused_cross_entropy(h, w, t, m, bias_v=b,
                                     logit_scale=logit_scale, chunk=chunk,
                                     with_z=True)
        return jax.lax.psum((nll, z), tuple(mesh.axis_names))

    fn = jax.shard_map(local, mesh=mesh, in_specs=tuple(in_specs),
            out_specs=(P(), P()), check_vma=False)
    nll_sum, z_sum = fn(*args)
    if with_z:
        return nll_sum, z_sum
    return nll_sum
