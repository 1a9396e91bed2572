"""Manual comm/compute overlap for the fsdp layer stack (shard_map).

Where XLA's latency-hiding scheduler won't overlap on its own (and on
XLA:CPU, where every GSPMD collective is a synchronous rendezvous), this
module schedules the fsdp collectives by hand, Megatron-style:

- **Bucketed param all-gather, one per layer.** Each layer's
  fsdp-sharded leaves are flattened and packed into a handful of
  size-bounded buckets, so un-sharding a layer is a few large
  all-gathers instead of seven small ones (bucket reconstruction is a
  pure reshape/moveaxis — no data movement beyond the collective).
- **Double-buffered prefetch through the layer scan.** The carry holds
  the *current* layer's gathered params while the *next* layer's gather
  is issued before the current layer's matmuls — the two are dataflow-
  independent, so the scheduler (or the CPU thread pool) runs the
  gather behind the compute.
- **Gradient reduce-scatter drains behind the backward pass.** The
  bucketed gather's transpose IS a bucketed reduce-scatter, and because
  the gather happens per layer inside the scan, the backward emits one
  bucketed reduce-scatter per layer as soon as that layer's param
  cotangents exist — instead of one monolithic sync after the whole
  backward. Under a remat policy the checkpoint encloses the gather
  (models/stack.py layer_checkpoint), so the backward
  re-gathers shards rather than keeping full per-layer params alive.

Scope: pure dp×fsdp meshes, dense uniform layers, no int8 leaves
(:func:`can_overlap`). Everything else falls back to GSPMD.
"""

from __future__ import annotations

import math
from typing import Any, Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from .sharding_rules import batch_pspec, param_pspec

# One bucket ≈ 4 MiB of shard bytes: large enough to amortize collective
# launch overhead, small enough that a layer still drains as several
# independent transfers the scheduler can interleave with compute.
DEFAULT_BUCKET_BYTES = 4 * 1024 * 1024


def _axis_dim(spec: P, axis: str) -> Optional[int]:
    """Index of the dim a PartitionSpec shards over ``axis`` (None if
    unsharded there)."""
    for i, entry in enumerate(spec):
        names = entry if isinstance(entry, tuple) else (entry,)
        if axis in names:
            return i
    return None


def layer_gather_dims(layer: Any, mesh: Mesh, axis: str = "fsdp") -> Any:
    """Pytree matching one layer's leaves → fsdp-sharded dim index or None.

    Derived from the same parallel/sharding_rules.py patterns GSPMD uses,
    so the manual schedule and the compiler agree on placement. Paths are
    matched with a ``layers.0.`` prefix — the rules are suffix regexes.
    """
    def dim_of(path, leaf):
        key = "layers.0." + ".".join(
            str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
        return _axis_dim(param_pspec(key, np.shape(leaf), mesh), axis)

    return jax.tree_util.tree_map_with_path(dim_of, layer)


def can_overlap(mesh: Optional[Mesh], layers: Sequence[Any],
                batch: int, axis: str = "fsdp") -> bool:
    """True when the manual overlap schedule applies: a >1 ``fsdp`` axis,
    every model-parallel axis trivial (tp/sp/ep/pp — their matmul
    semantics are GSPMD's job), a batch the data axes divide, uniform
    non-int8 layers, and every fsdp-sharded dim divisible by the axis."""
    if mesh is None or axis not in mesh.axis_names or mesh.shape[axis] <= 1:
        return False
    for other in ("tp", "sp", "ep", "pp"):
        if mesh.shape.get(other, 1) > 1:
            return False
    data = mesh.shape.get("dp", 1) * mesh.shape[axis]
    if batch % data != 0:
        return False
    if not layers:
        return False
    structs = {jax.tree_util.tree_structure(l) for l in layers}
    if len(structs) != 1:
        return False
    n = mesh.shape[axis]
    dims = layer_gather_dims(layers[0], mesh, axis)
    for leaf, d in zip(jax.tree_util.tree_leaves(layers[0]),
                       jax.tree_util.tree_leaves(
                           dims, is_leaf=lambda x: x is None)):
        if leaf.dtype == jnp.int8:
            return False
        if d is not None and leaf.shape[d] % n != 0:
            return False
    return True


# -- bucket layout -----------------------------------------------------------
class _Bucket:
    """A group of fsdp-sharded leaves gathered as ONE collective.

    ``entries`` = [(flat_index, full_shape, shard_dim)]; reconstruction
    from the gathered ``[n, total]`` payload is reshape + moveaxis only.
    """

    __slots__ = ("entries", "dtype", "shard_elems")

    def __init__(self, dtype):
        self.entries: List[Tuple[int, Tuple[int, ...], int]] = []
        self.dtype = dtype
        self.shard_elems = 0


def bucket_layout(leaves: Sequence[jnp.ndarray], dims: Sequence[Optional[int]],
                  n: int, bucket_bytes: int = DEFAULT_BUCKET_BYTES
                  ) -> List[_Bucket]:
    """Greedy size-bounded bucketing of the sharded leaves (by dtype)."""
    buckets: List[_Bucket] = []
    open_by_dtype = {}
    for i, (leaf, d) in enumerate(zip(leaves, dims)):
        if d is None:
            continue
        shard_elems = leaf.size // n
        b = open_by_dtype.get(leaf.dtype)
        if (b is None or (b.shard_elems + shard_elems) * leaf.dtype.itemsize
                > bucket_bytes and b.entries):
            b = _Bucket(leaf.dtype)
            buckets.append(b)
            open_by_dtype[leaf.dtype] = b
        b.entries.append((i, tuple(leaf.shape), d))
        b.shard_elems += shard_elems
    return buckets


def _gather_layer(shards: List[jnp.ndarray], dims: Sequence[Optional[int]],
                  buckets: List[_Bucket], n: int, axis: str
                  ) -> List[jnp.ndarray]:
    """Un-shard one layer inside the shard_map body.

    ``shards``: local leaf shards (full arrays for unsharded leaves).
    One tiled-flat all-gather per bucket; its transpose is one bucketed
    reduce-scatter per bucket.
    """
    out = list(shards)
    for b in buckets:
        flat = jnp.concatenate(
            [shards[i].reshape(-1) for i, _, _ in b.entries])
        gathered = jax.lax.all_gather(flat, axis)  # [n, bucket_elems]
        off = 0
        for i, full_shape, d in b.entries:
            shard_shape = list(full_shape)
            shard_shape[d] //= n
            size = math.prod(shard_shape)
            seg = gathered[:, off:off + size].reshape((n, *shard_shape))
            # [n, *shard] -> tiled concat along d == moveaxis + merge
            out[i] = jnp.moveaxis(seg, 0, d).reshape(full_shape)
            off += size
    return out


def overlapped_layer_scan(
    body: Callable[..., Tuple[jnp.ndarray, jnp.ndarray]],
    x: jnp.ndarray,
    layers: Sequence[Any],
    mesh: Mesh,
    consts: Sequence[jnp.ndarray] = (),
    *,
    axis: str = "fsdp",
    wrap: Optional[Callable] = None,
    n_wrapped: int = 0,
    bucket_bytes: int = DEFAULT_BUCKET_BYTES,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Run ``x`` through the layer stack with the manual overlap schedule.

    ``body(layer_params, x, *consts) -> (x, aux_scalar)`` computes one
    layer given FULL (gathered) params. ``consts`` are replicated array
    inputs (e.g. RoPE positions). ``wrap`` is an optional
    ``jax.checkpoint``-style wrapper applied to the first ``n_wrapped``
    layers' ``(shards, x, *consts)`` functions — the gather sits inside
    the checkpoint, so those layers re-gather in the backward.

    Returns ``(x, aux_sum)``. The non-checkpointed segment double-buffers:
    layer i+1's bucketed gather is issued before layer i's compute.
    """
    L = len(layers)
    n = int(mesh.shape[axis])
    dims_tree = layer_gather_dims(layers[0], mesh, axis)
    leaves0, treedef = jax.tree_util.tree_flatten(layers[0])
    dims = list(jax.tree_util.tree_leaves(
        dims_tree, is_leaf=lambda v: v is None))
    buckets = bucket_layout(leaves0, dims, n, bucket_bytes)

    # Stacked [L, ...] per leaf; in_specs place the fsdp dim exactly as
    # sharding_rules would for the unstacked leaf (leading L unsharded).
    stacked = [jnp.stack([jax.tree_util.tree_leaves(l)[i] for l in layers])
               for i in range(len(leaves0))]
    param_specs = [
        P(None, *[axis if j == d else None
                  for j in range(len(leaves0[i].shape))])
        if d is not None else P(*([None] * (1 + len(leaves0[i].shape))))
        for i, d in enumerate(dims)]
    bp = batch_pspec(mesh)
    x_spec = P(bp[0] if len(bp) else None,
               bp[1] if len(bp) > 1 else None, None)
    const_specs = [P(*([None] * c.ndim)) for c in consts]

    def _gather_then_body(shards, h, *cs):
        full = _gather_layer(shards, dims, buckets, n, axis)
        return body(jax.tree_util.tree_unflatten(treedef, full), h, *cs)

    # checkpoint encloses the gather: backward re-gathers shards instead
    # of keeping the full per-layer params as residuals.
    f_ckpt = (wrap(_gather_then_body)
              if (wrap is not None and n_wrapped > 0) else None)

    def run(h, consts_in, *stacked_in):
        def take(i):
            return [jax.lax.dynamic_index_in_dim(s, i, 0, keepdims=False)
                    for s in stacked_in]

        aux = jnp.zeros((), jnp.float32)

        # Checkpointed prefix: gather inside the checkpoint (no cross-
        # layer prefetch — the backward replays the gather per layer,
        # which is where the per-layer reduce-scatter drain comes from).
        n_ck = n_wrapped if f_ckpt is not None else 0
        if n_ck:
            def ck_step(carry, i):
                h, aux = carry
                h, a = f_ckpt(take(i), h, *consts_in)
                return (h, aux + a), None
            (h, aux), _ = jax.lax.scan(
                ck_step, (h, aux), jnp.arange(0, n_ck, dtype=jnp.int32))

        # Plain suffix: double-buffered — gather layer i+1 before layer
        # i's compute (dataflow-independent, so it overlaps).
        if n_ck < L:
            gathered = _gather_layer(take(jnp.int32(n_ck)), dims, buckets,
                                     n, axis)

            def db_step(carry, i):
                h, aux, gathered = carry
                nxt = _gather_layer(take(jnp.minimum(i + 1, L - 1)),
                                    dims, buckets, n, axis)
                h, a = f_plain_from_gathered(gathered, h, *consts_in)
                return (h, aux + a, nxt), None

            def f_plain_from_gathered(full, h, *cs):
                return body(jax.tree_util.tree_unflatten(treedef, full),
                            h, *cs)

            (h, aux, _), _ = jax.lax.scan(
                db_step, (h, aux, gathered),
                jnp.arange(n_ck, L, dtype=jnp.int32))
        return h, aux

    specs_in = (x_spec, tuple(const_specs), *param_specs)
    mapped = jax.shard_map(
        run, mesh=mesh, in_specs=specs_in, out_specs=(x_spec, P()),
        # The body is validated by parity tests (tests/test_overlap.py);
        # replication checking can't see through the manual bucket
        # reconstruction.
        check_vma=False,
    )
    return mapped(x, tuple(consts), *stacked)
