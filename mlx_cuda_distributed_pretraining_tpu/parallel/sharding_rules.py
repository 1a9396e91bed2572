"""Parameter/batch partition rules → NamedSharding.

Megatron-style tensor parallelism expressed as sharding annotations (the
reference's ``model_parallel`` flag is a placeholder — core/training.py:
1186-1193; here it is real): column-parallel up-projections shard their
output dim over ``tp``, row-parallel down-projections shard their input dim,
embeddings are vocab-parallel. XLA inserts the all-reduces.

ZeRO-1 (reference's ``zero_optimization_level`` — core/training.py:121,
chunked optimizer update modal/modal_cuda_utils.py:399-517): optimizer-state
leaves inherit their param's spec, then shard the first still-replicated
dim over the ``dp`` axis when divisible.
"""

from __future__ import annotations

import re
from typing import Any, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# (path regex, spec builder). fsdp shards the non-tp dim of every matrix.
# Expert-parallel (MoE, models/moe.py): stacked [E, ...] expert tensors lead
# with the ep axis so expert compute and weights partition together.
# Weight-only quantized leaves (models/quantize.py): ``weight_q`` (int8) and
# ``weight_q4`` (packed int4, contraction dim halved — the divisibility
# fallback in param_pspec handles the halving) shard exactly like the fp
# ``weight`` they replace; per-output-channel ``weight_s`` scales shard with
# the OUT dim of their matrix so each tp/fsdp shard holds the scales for
# exactly the output features it computes.
_RULES = [
    (r"tok_embeddings\.weight$", ("tp", "fsdp")),  # [V, D] vocab-parallel
    (r"output\.weight$", ("fsdp", "tp")),          # [D, V]
    # wg: the output gate's projection (models/afmoe.py, models/solar_open2.py), column-parallel over heads like wq
    (r"attention\.w[qkvg]\.weight(_q4?)?$", ("fsdp", "tp")),  # [D, H*Dh] column
    (r"attention\.w[qkv]\.weight_s$", ("tp",)),              # [H*Dh]
    (r"attention\.wo\.weight(_q4?)?$", ("tp", "fsdp")),      # [H*Dh, D] row
    (r"attention\.wo\.weight_s$", ("fsdp",)),                # [D]
    # Latent attention (models/xing.py): the down-projections to the latents
    # are replicated over tp (every head reads the whole latent), the
    # up-projections are column-parallel over heads like wq/wk/wv.
    (r"attention\.w(q|kv)_a\.weight$", ("fsdp", None)),      # [D, rank]
    (r"attention\.w(q|kv)_b\.weight$", ("fsdp", "tp")),      # [rank, H*d]
    (r"experts\.w_(gate|up)\.weight(_q4?)?$", ("ep", "fsdp", "tp")),  # [E, D, I]
    (r"experts\.w_(gate|up)\.weight_s$", ("ep", "tp")),               # [E, I]
    (r"experts\.w_down\.weight(_q4?)?$", ("ep", "tp", "fsdp")),       # [E, I, D]
    (r"experts\.w_down\.weight_s$", ("ep", "fsdp")),                  # [E, D]
    (r"feed_forward\.router\.weight$", ("fsdp", None)),        # [D, E]
    (r"shared\.w_(gate|up)\.weight$", ("fsdp", "tp")),         # shared expert, as a dense MLP
    (r"shared\.w_down\.weight$", ("tp", "fsdp")),
    (r"_hc\.phi\.weight$", ("fsdp", None)),                   # [n*D, n+n+n*n] mixing map
    (r"eh_proj\.weight$", ("fsdp", None)),                    # [2D, D] MTP joining projection
    # models/sambay.py. The fused q, k, v projection of differential attention is
    # column-parallel like wq; a Mamba mixer's projections split over fsdp alone
    # (its channels stay whole: the scan's kernels are not partitioned), its
    # per-channel leaves and the attention's lambda vectors are replicated; a gated
    # memory unit's two matrices are a column- and a row-parallel pair.
    (r"attention\.wqkv\.weight$", ("fsdp", "tp")),            # [D, (H + 2G)*Dh]
    (r"attention\.lambda_[qk][12]$", (None,)),                # [Dh]
    (r"attention\.subln\.weight$", (None,)),                  # [2 Dh] the difference norm's gain
    (r"ssm\.(in|x|dt)_proj\.weight$", ("fsdp", None)),        # [D, 2Di], [Di, R+2N], [R, Di]
    (r"ssm\.out_proj\.weight$", (None, "fsdp")),              # [Di, D]
    (r"ssm\.conv\.weight$", (None, None)),                    # [Di, K] depthwise taps
    (r"ssm\.(A_log|D)$", (None, None)),                        # [Di, N], [Di]
    (r"gmu\.w1\.weight$", ("fsdp", "tp")),                    # [D, Di] column
    (r"gmu\.w2\.weight$", ("tp", "fsdp")),                    # [Di, D] row
    # models/kimi_linear.py. A delta-rule mixer's three projections are column-parallel
    # over heads and its output projection row-parallel, like wq/wk/wv and wo (under a
    # mesh its core takes the XLA form, which GSPMD partitions by head); the two low-rank
    # pairs are a replicated down- and a column-parallel up-projection, as latent
    # attention's; its per-channel and per-head leaves are replicated. Its latent layers'
    # leaves are models/xing.py's (wq straight to the heads: the attention.wq rule). models/solar_open2.py
    # has the same kda leaves (twice as wide as its residual stream) beside gated attention's wq, wk, wv, wg, wo.
    (r"kda\.w[qkv]\.weight$", ("fsdp", "tp")),                # [D, H*d] column
    (r"kda\.wo\.weight$", ("tp", "fsdp")),                    # [H*d, D] row
    (r"kda\.[fg]_down\.weight$", ("fsdp", None)),             # [D, d]
    (r"kda\.[fg]_up\.weight$", (None, "tp")),                 # [d, H*d]
    (r"kda\.wb\.weight$", ("fsdp", None)),                    # [D, H] the write strength
    (r"kda\.conv_[qkv]\.weight$", (None, None)),              # [H*d, K] depthwise taps
    (r"kda\.(A_log|dt_bias)$", (None,)),                      # [H], [H*d]
    (r"feed_forward\.w_(gate|up)\.weight(_q4?)?$", ("fsdp", "tp")),  # [D, I] column
    (r"feed_forward\.w_(gate|up)\.weight_s$", ("tp",)),              # [I]
    (r"feed_forward\.w_down\.weight(_q4?)?$", ("tp", "fsdp")),       # [I, D] row
    (r"feed_forward\.w_down\.weight_s$", ("fsdp",)),                 # [D]
    (r"\.bias$", (None,)),
    (r"norm\.weight$", (None,)),
]


def _axis(mesh: Mesh, name: Optional[str]) -> Optional[str]:
    return name if (name is not None and name in mesh.axis_names and mesh.shape[name] > 1) else None


def param_pspec(path: str, shape, mesh: Mesh) -> P:
    for pattern, dims in _RULES:
        if re.search(pattern, path):
            out = []
            for i, d in enumerate(dims[: len(shape)]):
                ax = _axis(mesh, d)
                if ax is not None and shape[i] % mesh.shape[ax] == 0:
                    out.append(ax)
                else:
                    out.append(None)
            out += [None] * (len(shape) - len(out))
            return P(*out)
    return P()  # replicated default (1-D norms etc.)


def batch_pspec(mesh: Mesh) -> P:
    """Batch dim over dp×fsdp×ep; sequence dim over sp (context parallel).

    ep doubles as a data axis for non-expert compute — the dispatch einsum
    re-shards tokens expert-major (the all-to-all)."""
    data_axes = tuple(a for a in ("dp", "fsdp", "ep") if _axis(mesh, a))
    seq_axis = _axis(mesh, "sp")
    return P(data_axes if data_axes else None, seq_axis)


def moe_dispatch_specs(mesh: Mesh) -> dict:
    """PartitionSpecs for the grouped-MoE shard_map dispatch (models/moe.py).

    The sorted dispatch drops below GSPMD, so the boundary specs are built
    here next to the parameter rules they must agree with: activations and
    router outputs (gate indices/weights, and with them the derived
    group-offset tensors) are batch-sharded like ``batch_pspec``; stacked
    expert weights split their leading dim over ``ep`` exactly as the
    ``experts.*`` parameter rules above; the dropped-token count is
    replicated (psum over every mesh axis inside the body).
    """
    data_axes = tuple(a for a in ("dp", "fsdp", "ep") if _axis(mesh, a))
    batch = data_axes if data_axes else None
    ep = _axis(mesh, "ep")
    return {
        "batch_axes": data_axes,
        "activation": P(batch, None, None),   # x [B, S, D] / out [B, S, D]
        "gate": P(batch, None, None),         # gate idx/weights [B, S, K]
        "expert_weight": P(ep, None, None),   # [E, D, I] / [E, I, D]
        "replicated": P(),
    }


def tree_pspecs(params: Any, mesh: Mesh) -> Any:
    """PartitionSpec tree for a param pytree (paths joined with '.')."""
    from ..utils.tree import flatten_dict, unflatten_dict

    flat = flatten_dict(params)
    specs = {k: param_pspec(k, np.shape(v), mesh) for k, v in flat.items()}
    nested = unflatten_dict(specs)
    return _match_structure(params, nested)


def _match_structure(like: Any, nested: Any) -> Any:
    if isinstance(like, dict):
        return {k: _match_structure(v, nested[k]) for k, v in like.items()}
    if isinstance(like, (list, tuple)):
        vals = [_match_structure(v, nested[str(i)]) for i, v in enumerate(like)]
        return type(like)(vals) if isinstance(like, tuple) else vals
    return nested


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


def match_opt_leaf_spec(k: str, shape, ordered_paths, param_specs, param_shapes) -> Optional[P]:
    """Match an optimizer-state leaf to its parameter's spec by path suffix.

    Tried against both the leaf path and its parent (optimizers that nest
    per-param dicts — e.g. shampoo's ``...wq.weight.stats_l`` — match via
    the parent ``...wq.weight``). Exact-shape matches inherit the full spec;
    bank-statistics leaves like shampoo's ``[*lead, m, m]`` that only share
    the leading (ep/pp-sharded) dim inherit that leading axis, keeping
    per-expert/per-stage stats sharded with their bank instead of
    replicated.
    """
    candidates = (k, k.rsplit(".", 1)[0])
    for cand in candidates:
        for p in ordered_paths:
            if (cand == p or cand.endswith("." + p)) and param_shapes[p] == shape:
                return param_specs[p]
    for cand in candidates:
        for p in ordered_paths:
            if cand == p or cand.endswith("." + p):
                pspec = list(param_specs[p])
                pshape = param_shapes[p]
                if (pspec and pspec[0] is not None and len(shape) >= 1
                        and len(pshape) >= 1 and shape[0] == pshape[0]):
                    return P(pspec[0], *([None] * (len(shape) - 1)))
                return None
    return None


def state_sharding(state: Any, mesh: Mesh, zero_level: int = 0) -> Any:
    """Shardings for {params, opt_state, step}-style train state.

    Optimizer-state leaves are matched to their parameter **by path
    suffix** (e.g. ``1.mu.layers.0.attention.wq.weight`` matches param
    ``layers.0.attention.wq.weight``) — shape-based matching would collide
    for same-shape params with transposed specs (wq vs wo when
    num_heads*head_dim == hidden_size). With ``zero_level >= 1`` a
    still-unsharded axis of each matched leaf is additionally sharded over
    ``dp`` when divisible (optimizer-state partitioning à la ZeRO-1).
    """
    dp = _axis(mesh, "dp")

    param_specs: dict = {}
    param_shapes: dict = {}

    def record(path, leaf):
        k = _path_str(path)
        param_specs[k] = param_pspec(k, np.shape(leaf), mesh)
        param_shapes[k] = np.shape(leaf)
        return NamedSharding(mesh, param_specs[k])

    params_shardings = jax.tree_util.tree_map_with_path(record, state["params"])
    # longest param paths first so the most specific suffix wins
    ordered_paths = sorted(param_specs, key=len, reverse=True)

    def opt_leaf(path, leaf):
        k = _path_str(path)
        shape = np.shape(leaf)
        spec = P()
        if len(shape) > 0:
            matched = match_opt_leaf_spec(k, shape, ordered_paths, param_specs, param_shapes)
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
        "params": params_shardings,
        "opt_state": jax.tree_util.tree_map_with_path(opt_leaf, state["opt_state"]),
        "step": NamedSharding(mesh, P()),
    }
