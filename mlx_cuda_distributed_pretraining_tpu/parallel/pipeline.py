"""Pipeline parallelism (pp mesh axis) — GPipe-style microbatching.

The reference has no pipeline parallelism (SURVEY.md §2.4: absent). This is
the TPU-native design, not a port of any GPU schedule:

- Layer parameters are **stacked** into a ``[L, ...]`` pytree whose leading
  dim is sharded over the ``pp`` mesh axis — each stage owns a contiguous
  slab of layers. Within a stage, layers run under ``lax.scan``. With
  ``interleave = V > 1`` the stacked tree is ``[V, L/V, ...]`` instead: dim 0
  is the virtual-stage (circuit) index, dim 1 is sharded over ``pp``, so each
  device owns V round-robin chunks of ``L/(P*V)`` layers.
- The schedule is a single ``lax.scan`` over ``V*M + P - 1`` ticks: each tick
  every stage applies one layer chunk to its current activation and the
  results rotate one stage forward via ``jax.lax.ppermute`` over ICI.
  Stage 0 feeds microbatch ``t``; the last stage computes token-level NLL
  for microbatch ``t - (P-1)`` of the final circuit. Warmup/drain ticks where
  a stage holds no live microbatch skip the chunk application entirely via
  ``lax.cond`` on the ``working`` predicate (``compute_skip``), so per-step
  chunk applications are exactly ``P*V*M`` — the bubble is idle time, not
  garbage FLOPs, and interleaving shrinks it from ``P-1`` slab-times to
  ``(P-1)/V`` (each tick is 1/V of a slab).
- ``jax.shard_map(..., axis_names={'pp'})`` is manual **only over pp**; all
  other mesh axes (dp/fsdp/tp/ep) stay in GSPMD auto mode, so the usual
  sharding rules (parallel/sharding_rules.py) keep partitioning the batch
  and the within-stage weights. Pipeline composes with DP/TP/EP by
  construction instead of by hand-written schedules.
- Backward is just ``jax.grad`` through the scan + ppermute + cond (all
  differentiable); XLA re-emits the reverse rotations, and the cond VJP
  skips the backward chunk FLOPs on exactly the ticks the forward skipped.

Limits (documented, enforced): ring (sp) attention inside a pipeline stage
is not supported — sp and pp are alternative scale-out axes for now — and
``interleave > 1`` requires ``num_microbatches >= pp`` (the wrap-around
activation of circuit v must have left the ring before stage 0 re-feeds
that microbatch for circuit v+1).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .sharding_rules import _axis, batch_pspec, param_pspec
from ..utils.tree import flatten_dict, unflatten_dict

Params = Dict[str, Any]

def _scan_indexed(body, carry, n, index_xs):
    """``lax.scan`` of ``body`` over ``range(n)``; ``index_xs(i)`` produces
    the per-iteration operand for the traced index ``i``."""
    def step(c, i):
        c, _ = body(c, index_xs(i))
        return c, None

    carry, _ = jax.lax.scan(step, carry, jnp.arange(n, dtype=jnp.int32))
    return carry


# -- stacked layer layout ----------------------------------------------------
def stack_layers(params: Params, interleave: int = 1) -> Params:
    """list-of-layer-dicts → single tree with leading layer dim [L, ...].

    ``interleave = V > 1`` reshapes the leading dim to ``[V, L/V, ...]``:
    ``stacked[v, j]`` is global layer ``v*(L/V) + j``. Sharding dim 1 over
    ``pp`` then hands device p the round-robin chunks ``{v*P + p : v}`` of
    ``L/(P*V)`` layers each — the Megatron interleaved virtual-stage layout —
    without the stacking step ever needing to know P.
    """
    layers = params["layers"]
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs, axis=0), *layers)
    if interleave > 1:
        L = len(layers)
        if L % interleave != 0:
            raise ValueError(
                f"num_layers {L} must be divisible by pipeline_interleave "
                f"{interleave}")
        stacked = jax.tree_util.tree_map(
            lambda x: x.reshape(interleave, L // interleave, *x.shape[1:]),
            stacked)
    out = {k: v for k, v in params.items() if k != "layers"}
    out["layers"] = stacked
    return out


def unstack_layers(params: Params, num_layers: int, interleave: int = 1) -> Params:
    """Inverse of :func:`stack_layers` (e.g. for checkpoint compatibility)."""
    stacked = params["layers"]
    if interleave > 1:
        stacked = jax.tree_util.tree_map(
            lambda x: x.reshape(num_layers, *x.shape[2:]), stacked)
    layers = [
        jax.tree_util.tree_map(lambda x: x[i], stacked) for i in range(num_layers)
    ]
    out = {k: v for k, v in params.items() if k != "layers"}
    out["layers"] = layers
    return out


def _is_stacked_layers(node: Any, num_layers: int, interleave: int = 1) -> bool:
    leaves = jax.tree_util.tree_leaves(node)
    if not leaves:
        return False
    if interleave > 1:
        lead = (interleave, num_layers // interleave)
        return all(
            getattr(l, "ndim", 0) >= 2 and tuple(l.shape[:2]) == lead
            for l in leaves
        )
    return all(
        getattr(l, "ndim", 0) >= 1 and l.shape[0] == num_layers for l in leaves
    )


def unstack_opt_state(opt_state: Any, num_layers: int, interleave: int = 1) -> Any:
    """Convert stacked ``layers`` subtrees inside an optimizer-state pytree to
    the canonical list-of-layers layout (checkpoint compatibility: a pipeline
    run's optimizer state must resume on a non-pp mesh and vice versa)."""

    def unstack_one(v):
        if interleave > 1:
            v = jax.tree_util.tree_map(
                lambda x: x.reshape(num_layers, *x.shape[2:]), v)
        return [
            jax.tree_util.tree_map(lambda x, i=i: x[i], v)
            for i in range(num_layers)
        ]

    def walk(node):
        if isinstance(node, dict):
            out = {}
            for k, v in node.items():
                if k == "layers" and _is_stacked_layers(v, num_layers, interleave):
                    out[k] = unstack_one(v)
                else:
                    out[k] = walk(v)
            return out
        if isinstance(node, tuple) and hasattr(node, "_fields"):
            return type(node)(*[walk(v) for v in node])
        if isinstance(node, (list, tuple)):
            vals = [walk(v) for v in node]
            return vals if isinstance(node, list) else tuple(vals)
        return node

    return walk(opt_state)


def stack_opt_state(opt_state: Any, num_layers: int, interleave: int = 1) -> Any:
    """Inverse of :func:`unstack_opt_state`."""

    def stack_one(v):
        stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs, axis=0), *v)
        if interleave > 1:
            stacked = jax.tree_util.tree_map(
                lambda x: x.reshape(
                    interleave, num_layers // interleave, *x.shape[1:]),
                stacked)
        return stacked

    def walk(node):
        if isinstance(node, dict):
            out = {}
            for k, v in node.items():
                if k == "layers" and isinstance(v, list) and len(v) == num_layers:
                    out[k] = stack_one(v)
                else:
                    out[k] = walk(v)
            return out
        if isinstance(node, tuple) and hasattr(node, "_fields"):
            return type(node)(*[walk(v) for v in node])
        if isinstance(node, (list, tuple)):
            vals = [walk(v) for v in node]
            return vals if isinstance(node, list) else tuple(vals)
        return node

    return walk(opt_state)


def stacked_param_pspec(path: str, shape, mesh: Mesh, interleave: int = 1) -> P:
    """Sharding spec for a stacked-params leaf.

    ``layers.*`` leaves: leading layer dim over ``pp`` (with ``interleave``
    the layout is ``[V, L/V, ...]`` — the virtual-stage dim stays replicated
    and dim 1 carries ``pp``), remaining dims by the standard rules.
    Non-layer leaves (embed/norm/head): standard rules.
    """
    pp = _axis(mesh, "pp")
    if path.startswith("layers."):
        lead_dims = 2 if interleave > 1 else 1
        inner = param_pspec(path[len("layers.") :], shape[lead_dims:], mesh)
        dims = list(inner) + [None] * (len(shape) - lead_dims - len(inner))
        layer_dim = shape[lead_dims - 1]
        lead = pp if (pp is not None and layer_dim % mesh.shape[pp] == 0) else None
        if interleave > 1:
            return P(None, lead, *dims)
        return P(lead, *dims)
    return param_pspec(path, shape, mesh)


def stacked_tree_pspecs(stacked: Params, mesh: Mesh, interleave: int = 1) -> Any:
    flat = flatten_dict(stacked)
    specs = {
        k: stacked_param_pspec(k, np.shape(v), mesh, interleave=interleave)
        for k, v in flat.items()
    }
    return unflatten_dict(specs)


def pipeline_state_sharding(state: Any, mesh: Mesh, zero_level: int = 0,
                            interleave: int = 1) -> Any:
    """NamedShardings for {params(stacked), opt_state, step} (ZeRO-1 over dp
    for still-replicated opt-state dims, mirroring sharding_rules)."""
    dp = _axis(mesh, "dp")
    param_specs: dict = {}
    param_shapes: dict = {}

    def record(path, leaf):
        k = _path_str(path)
        param_specs[k] = stacked_param_pspec(
            k, np.shape(leaf), mesh, interleave=interleave)
        param_shapes[k] = np.shape(leaf)
        return NamedSharding(mesh, param_specs[k])

    params_sh = jax.tree_util.tree_map_with_path(record, state["params"])
    ordered = sorted(param_specs, key=len, reverse=True)

    def opt_leaf(path, leaf):
        from .sharding_rules import match_opt_leaf_spec

        k = _path_str(path)
        shape = np.shape(leaf)
        spec = P()
        if len(shape) > 0:
            matched = match_opt_leaf_spec(k, shape, ordered, param_specs, param_shapes)
            if matched is not None:
                spec = matched
            if zero_level >= 1 and dp is not None:
                dims = list(spec) + [None] * (len(shape) - len(spec))
                for i, d in enumerate(dims):
                    if d is None and shape[i] % mesh.shape[dp] == 0 and shape[i] > 1:
                        dims[i] = dp
                        break
                spec = P(*dims)
        return NamedSharding(mesh, spec)

    return {
        "params": params_sh,
        "opt_state": jax.tree_util.tree_map_with_path(opt_leaf, state["opt_state"]),
        "step": NamedSharding(mesh, P()),
    }


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return ".".join(parts)


# -- the pipelined loss ------------------------------------------------------
def make_pipeline_loss(
    args: Any,
    mesh: Mesh,
    num_microbatches: int,
    compute_dtype=jnp.float32,
    remat: Optional[str] = None,
    include_aux: bool = True,
    ce_chunk: int = -1,
    z_loss_weight: float = 0.0,
    interleave: int = 1,
    compute_skip: bool = True,
    with_moe_stats: bool = False,
    with_slab_count: bool = False,
) -> Callable:
    """Build ``loss(stacked_params, batch) -> (loss, token_count)`` running
    the GPipe schedule over the mesh's pp axis.

    ``batch`` leaves are [B, S(+1)]-shaped like the standard loss; B must be
    divisible by ``num_microbatches``. ``ce_chunk`` selects the fused
    chunked CE for the last stage's vocab head (ops/fused_ce.py semantics:
    0 = full logits, -1 = auto by microbatch logits size, >0 = fixed).

    ``interleave = V > 1`` runs Megatron-style interleaved virtual stages:
    the stacked params are ``[V, L/V, ...]`` (see :func:`stack_layers`),
    activations make V circuits of the ring, and the bubble shrinks from
    ``P-1`` slab-times to ``(P-1)/V``. Requires ``num_microbatches >= pp``.
    V=1 keeps today's single-circuit schedule bit-identically.

    ``compute_skip`` wraps the chunk application (and stage-0's full-vocab
    embed gather) in ``lax.cond`` on the ``working`` predicate, so
    warmup/drain ticks execute no slab FLOPs — forward and, through the
    scanned VJP, backward. Numerics are unchanged: non-working outputs were
    already masked out of the loss, so skip on/off differ only in wasted
    compute. ``compute_skip=False`` reproduces the original schedule (every
    tick applies the chunk to masked garbage) for apples-to-apples comparisons.

    ``with_moe_stats`` threads MoE routing stats (``moe_load`` [E] /
    ``moe_dropped``) through the tick carries and returns
    ``(loss, (token_count, stats))`` — the same contract as
    ``llama.loss_fn(with_moe_stats=True)``, so pp runs report the same
    routing gauges as non-pp runs.

    ``with_slab_count`` appends to the aux the number of chunk applications
    the schedule EXECUTED, summed over stages: an int32 carried through the
    ticks and incremented inside the cond's work branch, so it is evidence
    that compute-skip skips (``P*(V*M + P-1)`` falls to ``P*V*M``), not the
    formula restated. The tests ask for it; training does not.
    """
    if getattr(args, "attention_type", "simple") == "ring":
        raise ValueError("ring (sp) attention inside a pipeline stage is not supported")
    P_stages = mesh.shape["pp"]
    M = num_microbatches
    V = int(interleave)
    if V < 1:
        raise ValueError(f"pipeline_interleave must be >= 1, got {V}")
    if V > 1 and M < P_stages:
        raise ValueError(
            f"pipeline_interleave={V} needs pipeline_microbatches >= pp "
            f"({M} < {P_stages}): the wrap-around activation of circuit v "
            f"must leave the ring before stage 0 re-feeds that microbatch "
            f"for circuit v+1")
    from ..models.llama import transformer_block, rms_norm, _linear
    from ..ops import fused_ce

    if with_moe_stats and not getattr(args, "is_moe", False):
        with_moe_stats = False
    num_experts = int(getattr(args, "num_local_experts", 0) or 0)

    def zero_moe_stats():
        from ..models.moe import zero_stats

        return zero_stats(num_experts)

    def stage_apply(layers_loc, x, positions):
        # layers_loc: one chunk [L/(P*V), ...] (V=1: the whole stage slab).
        cast = partial(jax.tree_util.tree_map, lambda a: a.astype(compute_dtype))

        def one_layer(p_layer, h):
            ret = transformer_block(cast(p_layer), h, args, positions, None, None)
            if with_moe_stats:
                y, _, aux, stats = ret
                return y, aux, stats
            y, _, aux = ret
            return y, aux, None

        if remat:
            one_layer = jax.checkpoint(one_layer)

        def body(carry, p_layer):
            h, aux_sum, stats_sum = carry
            y, aux, stats = one_layer(p_layer, h)
            if with_moe_stats:
                stats_sum = {k: stats_sum[k] + stats[k] for k in stats_sum}
            return (y, aux_sum + aux, stats_sum), None

        stats0 = zero_moe_stats() if with_moe_stats else None
        n_loc = jax.tree_util.tree_leaves(layers_loc)[0].shape[0]
        x, aux, stats = _scan_indexed(
            body, (x, jnp.zeros((), jnp.float32), stats0), n_loc,
            lambda i: jax.tree_util.tree_map(
                lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False),
                layers_loc))
        return x, aux, stats

    def inner(ce_rows, layers_loc, embed_w, norm_w, out_w, tokens, targets, mask):
        # layers_loc: stage slab [L/P, ...] (V>1: [V, L/(P*V), ...]);
        # everything else replicated w.r.t. pp (GSPMD may still shard over
        # tp/fsdp).
        p = jax.lax.axis_index("pp")
        B, S = tokens.shape
        mb = B // M
        tok_m = tokens.reshape(M, mb, S)
        tgt_m = targets.reshape(M, mb, S)
        msk_m = mask.reshape(M, mb, S)
        positions = jnp.arange(S, dtype=jnp.int32)
        is_first = (p == 0).astype(compute_dtype)

        perm = [(i, (i + 1) % P_stages) for i in range(P_stages)]

        def head_nll(out, tgt, msk):
            h = rms_norm(out, norm_w, args.rms_norm_eps)
            if ce_rows > 0:
                # The head runs inside the tick scan, which is differentiated
                # from outside: left bare, each tick would keep the fused
                # CE's float32 dW ([V, D]) for the backward pass. Under
                # jax.checkpoint a tick keeps its inputs, and the backward
                # pass computes the loss and the gradients in one walk.
                fused = jax.checkpoint(partial(
                    fused_ce.fused_cross_entropy, logit_scale=args.logit_scale,
                    chunk=ce_rows, z_weight=z_loss_weight))
                return fused(h, out_w.astype(compute_dtype).T, tgt, msk), msk.sum()
            # fp32-accumulated projection — matches the non-pp loss exactly.
            logits = jax.lax.dot_general(
                h, out_w.astype(compute_dtype), (((2,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            if args.logit_scale:
                logits = logits * args.logit_scale
            logz = jax.nn.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(logits, tgt[..., None], axis=-1)[..., 0]
            nll_sum = ((logz - gold) * msk).sum()
            if z_loss_weight > 0.0:
                nll_sum = nll_sum + z_loss_weight * jnp.sum(jnp.square(logz) * msk)
            return nll_sum, msk.sum()

        def embed_feed(m_idx):
            return embed_w.astype(compute_dtype)[
                jax.lax.dynamic_index_in_dim(tok_m, m_idx, keepdims=False)
            ]

        def apply_chunk(chunk, inp, working):
            """Chunk application, skipped entirely on non-working ticks when
            compute_skip: the cond's pass branch is the identity, and its VJP
            is too, so forward AND backward slab FLOPs drop out."""
            applied = jnp.ones((), jnp.int32)
            if compute_skip:
                def work(x):
                    return (*stage_apply(chunk, x, positions), applied)

                def idle(x):
                    stats0 = zero_moe_stats() if with_moe_stats else None
                    return (x, jnp.zeros((), jnp.float32), stats0,
                            jnp.zeros((), jnp.int32))

                return jax.lax.cond(working, work, idle, inp)
            return (*stage_apply(chunk, inp, positions), applied)

        def head_cond(pred, out, m_idx):
            tgt = jax.lax.dynamic_index_in_dim(tgt_m, m_idx, keepdims=False)
            msk = jax.lax.dynamic_index_in_dim(
                msk_m, m_idx, keepdims=False).astype(jnp.float32)
            return jax.lax.cond(
                pred,
                head_nll,
                lambda out, tgt, msk: (jnp.zeros((), jnp.float32),
                                       jnp.zeros((), jnp.float32)),
                out, tgt, msk,
            )

        def mask_stats(stats, working):
            if not with_moe_stats:
                return None
            w = working.astype(jnp.float32)
            return {k: v * w for k, v in stats.items()}

        def acc_stats(acc, stats):
            if not with_moe_stats:
                return None
            return {k: acc[k] + stats[k] for k in acc}

        def tick_v1(carry, t):
            # Single-circuit GPipe tick. With compute_skip=False this is the
            # original schedule, bit for bit.
            state, nll_sum, tok_sum, aux_sum, stats_sum, apps = carry
            my_idx = t - p
            working = (my_idx >= 0) & (my_idx < M)
            if compute_skip:
                # stage-0 working ticks gather microbatch t's embeddings;
                # everyone else (and the drain ticks) passes state through —
                # no [mb,S] full-vocab gather off the working path.
                inp = jax.lax.cond(
                    (p == 0) & (t < M),
                    lambda: embed_feed(jnp.clip(t, 0, M - 1)),
                    lambda: state,
                )
            else:
                # stage-0 injects microbatch t (clamped when t >= M; masked)
                feed_idx = jnp.clip(t, 0, M - 1)
                x0 = embed_feed(feed_idx)
                feed_valid = (t < M).astype(compute_dtype)
                inp = is_first * feed_valid * x0 + (1.0 - is_first) * state
            out, aux, stats, applied = apply_chunk(layers_loc, inp, working)
            aux_sum = aux_sum + aux * working.astype(jnp.float32)
            stats_sum = acc_stats(stats_sum, mask_stats(stats, working))
            # Only the last working stage runs the vocab head (lax.cond:
            # the other P-1 stages skip the [mb,S,D]x[D,V] matmul entirely).
            li = jnp.clip(my_idx, 0, M - 1)
            nll_c, tok_c = head_cond((p == P_stages - 1) & working, out, li)
            nll_sum = nll_sum + nll_c
            tok_sum = tok_sum + tok_c
            # rotate activations one stage forward
            state_next = jax.lax.ppermute(out, "pp", perm)
            return (state_next, nll_sum, tok_sum, aux_sum, stats_sum,
                    apps + applied), None

        def tick_circular(carry, t):
            # Interleaved circuits: work item j = t - p is (circuit v,
            # microbatch m) = (j // M, j % M); chunk v of this stage applies.
            # Stage 0's input for circuit v > 0 is the wrap-around output of
            # the last stage for circuit v-1, buffered per microbatch until
            # its re-feed tick comes up (arrives at (v-1)M+m+P, consumed at
            # vM+m — hence the M >= P requirement).
            state, wrap_buf, nll_sum, tok_sum, aux_sum, stats_sum, apps = carry
            # Store the activation that rotated in at the end of the last
            # tick: stage P-1's output for item j_in = t - P. All stages run
            # the same store (SPMD); only stage 0 ever reads the buffer.
            j_in = t - P_stages
            j_in_c = jnp.clip(j_in, 0, M * V - 1)
            v_in = j_in_c // M
            m_in = j_in_c % M
            is_wrap = (j_in >= 0) & (j_in < M * V) & (v_in < V - 1)
            wrap_buf = jax.lax.cond(
                is_wrap,
                lambda buf: jax.lax.dynamic_update_index_in_dim(
                    buf, state, m_in, 0),
                lambda buf: buf,
                wrap_buf,
            )
            j = t - p
            working = (j >= 0) & (j < M * V)
            j_c = jnp.clip(j, 0, M * V - 1)
            v = j_c // M
            m = j_c % M

            def stage0_inp():
                return jax.lax.cond(
                    v == 0,
                    lambda: embed_feed(m),
                    lambda: jax.lax.dynamic_index_in_dim(
                        wrap_buf, m, keepdims=False),
                )

            inp = jax.lax.cond(p == 0, stage0_inp, lambda: state)
            chunk = jax.tree_util.tree_map(
                lambda a: jax.lax.dynamic_index_in_dim(a, v, keepdims=False),
                layers_loc,
            )
            out, aux, stats, applied = apply_chunk(chunk, inp, working)
            aux_sum = aux_sum + aux * working.astype(jnp.float32)
            stats_sum = acc_stats(stats_sum, mask_stats(stats, working))
            # The vocab head fires on the last stage's final-circuit items.
            nll_c, tok_c = head_cond(
                (p == P_stages - 1) & working & (v == V - 1), out, m)
            nll_sum = nll_sum + nll_c
            tok_sum = tok_sum + tok_c
            state_next = jax.lax.ppermute(out, "pp", perm)
            return (state_next, wrap_buf, nll_sum, tok_sum, aux_sum,
                    stats_sum, apps + applied), None

        D = embed_w.shape[1]
        state0 = jnp.zeros((mb, S, D), compute_dtype)
        zero = jnp.zeros((), jnp.float32)
        stats0 = zero_moe_stats() if with_moe_stats else None
        apps0 = jnp.zeros((), jnp.int32)
        if V == 1:
            state, nll, toks, aux, stats, apps = _scan_indexed(
                tick_v1, (state0, zero, zero, zero, stats0, apps0),
                M + P_stages - 1, lambda t: t,
            )
        else:
            wrap0 = jnp.zeros((M, mb, S, D), compute_dtype)
            state, wrap, nll, toks, aux, stats, apps = _scan_indexed(
                tick_circular, (state0, wrap0, zero, zero, zero, stats0, apps0),
                M * V + P_stages - 1, lambda t: t,
            )
        outs = [jax.lax.psum(nll, "pp"), jax.lax.psum(toks, "pp"),
                jax.lax.psum(aux, "pp")]
        if with_moe_stats:
            outs.append({k: jax.lax.psum(v, "pp") for k, v in stats.items()})
        if with_slab_count:
            outs.append(jax.lax.psum(apps, "pp"))
        return tuple(outs)

    def loss(stacked_params: Params, batch: Dict[str, jnp.ndarray]):
        layers = stacked_params["layers"]
        embed_w = stacked_params["tok_embeddings"]["weight"]
        norm_w = stacked_params["norm"]["weight"]
        if args.tie_word_embeddings or "output" not in stacked_params:
            out_w = embed_w.T
        else:
            out_w = stacked_params["output"]["weight"]

        B, S = batch["inputs"].shape
        ce_rows = ce_chunk
        if ce_rows < 0:
            ce_rows = fused_ce.auto_chunk(B // M, S, args.vocab_size)
        lead = P(None, "pp") if V > 1 else P("pp")
        layer_in_specs = jax.tree_util.tree_map(lambda _: lead, layers)
        bspec = P()  # batch enters replicated w.r.t. pp (auto axes may shard)
        out_like = [0.0, 0.0, 0.0]
        if with_moe_stats:
            out_like.append(dict.fromkeys(zero_moe_stats(), 0.0))
        if with_slab_count:
            out_like.append(0)
        sm = jax.shard_map(
            partial(inner, ce_rows),
            mesh=mesh,
            in_specs=(layer_in_specs, P(), P(), P(), bspec, bspec, bspec),
            out_specs=jax.tree_util.tree_map(lambda _: P(), tuple(out_like)),
            axis_names={"pp"},
            check_vma=False,
        )
        operands = (layers, embed_w, norm_w, out_w,
                    batch["inputs"], batch["targets"], batch["mask"])
        if with_moe_stats:
            from ..models.moe import routing_stats_tap

            # An active tap at trace time makes transformer_block re-emit
            # routing stats as return values (models/llama.py) — the tick
            # carries then thread them across the scan/cond boundaries.
            with routing_stats_tap():
                nll, toks, aux, *extra = sm(*operands)
        else:
            nll, toks, aux, *extra = sm(*operands)
        loss_val = nll / jnp.maximum(toks, 1.0)
        if getattr(args, "is_moe", False) and include_aux:
            loss_val = loss_val + aux / M  # aux is pre-scaled per microbatch
        # aux: token_count, then the routing stats and the slab count in
        # that order, each only when asked for.
        return loss_val, ((toks, *extra) if extra else toks)

    return loss


# -- the pipelined train step ------------------------------------------------
def make_pipeline_train_step(
    args: Any,
    optimizer: Any,
    mesh: Mesh,
    num_microbatches: int,
    compute_dtype=jnp.float32,
    remat: Optional[str] = None,
    zero_level: int = 0,
    params_like: Optional[Params] = None,
    log_grad_norm: bool = False,
    ce_chunk: int = -1,
    z_loss_weight: float = 0.0,
    interleave: int = 1,
    compute_skip: bool = True,
    moe_stats_experts: int = 0,
) -> Tuple[Callable, Any]:
    """Jitted ``step(state, batch) -> (state, metrics)`` with stacked params
    sharded over pp (plus the usual auto axes). ``params_like`` is the
    standard (list-of-layers) param tree used to derive shapes.

    ``moe_stats_experts > 0`` mirrors train_step.make_train_step: the loss
    threads routing stats and the metrics dict carries ``moe_load`` [E] /
    ``moe_dropped``."""
    from ..optim.base import apply_updates, global_norm
    from ..train.train_step import init_train_state

    assert params_like is not None
    moe_stats = moe_stats_experts > 0
    loss_fn = make_pipeline_loss(
        args, mesh, num_microbatches, compute_dtype=compute_dtype, remat=remat,
        ce_chunk=ce_chunk, z_loss_weight=z_loss_weight, interleave=interleave,
        compute_skip=compute_skip, with_moe_stats=moe_stats,
    )

    def train_step(state, batch):
        params = state["params"]
        (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, batch)
        toks, stats = aux if moe_stats else (aux, None)
        updates, opt_state = optimizer.update(grads, state["opt_state"], params)
        new_params = apply_updates(params, updates)
        metrics = {
            "loss": loss,
            "toks": toks,
            "nonfinite": jnp.logical_not(jnp.isfinite(loss)).astype(jnp.int32),
        }
        if moe_stats:
            metrics.update(stats)  # models/moe.py::STAT_KEYS, as train_step.py has them
        if log_grad_norm:
            # grads are the global stacked tree; global_norm is exact under
            # GSPMD (XLA inserts the cross-shard reductions).
            metrics["grad_norm"] = global_norm(grads)
        return {"params": new_params, "opt_state": opt_state, "step": state["step"] + 1}, metrics

    stacked_like = jax.eval_shape(
        partial(stack_layers, interleave=interleave), params_like)
    probe = jax.eval_shape(
        lambda p: init_train_state(p, optimizer), stacked_like
    )
    shardings = pipeline_state_sharding(probe, mesh, zero_level,
                                        interleave=interleave)
    b_shard = NamedSharding(mesh, batch_pspec(mesh))
    batch_shardings = {"inputs": b_shard, "targets": b_shard, "mask": b_shard}
    step_fn = jax.jit(
        train_step,
        donate_argnums=(0,),
        in_shardings=(shardings, batch_shardings),
        out_shardings=(shardings, None),
    )
    return step_fn, shardings
