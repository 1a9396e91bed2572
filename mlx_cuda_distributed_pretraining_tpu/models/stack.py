"""The training scaffold the model modules share: how a layer's weights reach
the compute dtype and what is rematerialised with them, how a stack of layers
is run, and the head and the loss tail. A model module holds what is its own
(its ``Args``, ``init_params``, its mixers and its block) and calls these;
which kernel runs an attention core is ``ops/attention.py::attention_core``.
Imports no model module.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Dict, Optional, Sequence

import jax
import jax.numpy as jnp

from ..ops import fused_ce

# -- named remat policies ----------------------------------------------------
# Activation sites are tagged with jax.ad_checkpoint.checkpoint_name so a
# policy trades exactly the FLOPs we choose instead of blanket replay:
#   "qkv"      — q/k/v projections (pre-RoPE)
#   "attn_out" — the attention output (flash/flex/ring/reference), pre-wo
#   "ffn_up"   — silu(gate) * up, the SwiGLU elementwise product
#   "ffn_down" — the MLP down-projection output
# REMAT_POLICIES maps model.remat_policy names to what the backward pass
# may keep; anything unnamed is recomputed.
SAVE_ATTN_NAMES = ("qkv", "attn_out")
REMAT_POLICIES = ("none", "dots", "full", "save_attn")


def normalize_remat(remat: Optional[str]) -> Optional[str]:
    """"none"/"" → None; unknown names raise (a typo'd policy must not
    silently train without remat)."""
    if remat is None or remat == "":
        return None
    name = str(remat).lower()
    if name == "none":
        return None
    if name not in REMAT_POLICIES:
        raise ValueError(
            f"unknown remat policy {remat!r} (expected one of "
            f"{REMAT_POLICIES})")
    return name


def layer_checkpoint(remat: Optional[str], static_argnums: Sequence[int] = ()) -> Callable:
    """Per-layer ``jax.checkpoint`` wrapper for a named policy; the identity
    for none.

    - "full": replay everything (minimum memory, maximum recompute);
    - "dots": keep matmul outputs (checkpoint_dots_with_no_batch_dims);
    - "save_attn": keep only the tagged attention activations (qkv +
      attention output) — the backward never replays the O(S²) attention
      kernel, only the cheap FFN/elementwise work.

    Without ``static_argnums`` the layer's function closes over its static
    configuration, so the checkpoint encloses whatever it does to its
    parameters first (a cast; a gather of shards, parallel/overlap.py) and the
    backward does it again instead of keeping the result alive."""
    remat = normalize_remat(remat)
    if remat is None:
        return lambda f: f
    policies = jax.checkpoint_policies
    policy = {"full": None, "dots": policies.checkpoint_dots_with_no_batch_dims,
              "save_attn": policies.save_only_these_names(*SAVE_ATTN_NAMES)}[remat]
    return partial(jax.checkpoint, policy=policy, static_argnums=tuple(static_argnums))


def cast_layer(tree, dtype):
    """A layer's weights in the compute dtype; int8 (quantized) leaves stay int8."""
    with jax.named_scope("layer"):  # a layer's cast weights are the layer's cost
        return jax.tree_util.tree_map(
            lambda a: a if a.dtype == jnp.int8 else a.astype(dtype), tree)


# -- a stack of layers --------------------------------------------------------------
def own_layer(block: Callable, dtype, remat: Optional[str]) -> Callable:
    """``block(p, *xs)`` as a layer outside a scan runs it: it casts its weights
    inside its rematerialised function, so the backward pass casts them again
    and the step does not hold the copies in between."""
    return layer_checkpoint(remat)(lambda p, *xs: block(cast_layer(p, dtype), *xs))


def run_layers(block: Callable, x, layers: Sequence[Any], dtype, remat: Optional[str],
               scan: bool = False, flags: Optional[Sequence[Any]] = None,
               zero: Optional[Dict[str, jnp.ndarray]] = None):
    """``x`` through ``block(p, x, flag) -> (x', out)`` for each tree of
    ``layers`` (one structure) → ``(x, outs summed onto zero)``; without
    ``zero`` the outs are dropped. ``flags`` holds one Python value a layer.
    A loop runs each layer as :func:`own_layer` with its flag static. A scan
    stacks the cast layers and checkpoints its body; its flag is scanned beside
    the weights and traced, unless the layers agree on it: one kind alone needs
    no flag, and is traced with the static one."""
    if not scan:
        total = zero
        for i, layer in enumerate(layers):
            flag = None if flags is None else flags[i]
            x, out = own_layer(lambda p, x, flag=flag: block(p, x, flag), dtype, remat)(layer, x)
            if zero is not None:
                total = {k: total[k] + out[k] for k in total}
        return x, total
    first = None if flags is None else flags[0]
    scanned = jnp.asarray(flags) if flags is not None and len(set(flags)) > 1 else None
    with jax.named_scope("layer"):  # the scan's stacking and slicing too
        stacked = jax.tree_util.tree_map(
            lambda *ls: jnp.stack(ls), *(cast_layer(l, dtype) for l in layers))
        x, ys = jax.lax.scan(
            layer_checkpoint(remat)(
                lambda x, lf: block(lf[0], x, first if lf[1] is None else lf[1])),
            x, (stacked, scanned))
    return x, None if zero is None else {k: ys[k].sum(axis=0) for k in zero}


# -- a mixer's short convolution ------------------------------------------------------
def causal_depthwise_conv(a: jnp.ndarray, weight: jnp.ndarray, bias: Optional[jnp.ndarray] = None):
    """``a [B, S, D]`` -> float32 ``[B, S, D]``: channel ``i`` at time ``t`` is ``sum_j
    weight[i, j] a[t - (K - 1) + j, i]`` (``weight [D, K]``; zeros before ``t = 0``),
    plus ``bias [D]`` where there is one. The activation is the caller's."""
    taps, S = weight.shape[1], a.shape[1]
    w = weight.astype(jnp.float32)
    padded = jnp.pad(a.astype(jnp.float32), ((0, 0), (taps - 1, 0), (0, 0)))
    out = sum(w[:, j] * padded[:, j:j + S] for j in range(taps))
    return out if bias is None else out + bias.astype(jnp.float32)


# -- the head and the loss tail -------------------------------------------------------
def head_weight(weight: jnp.ndarray, vocab_axis: int, dtype) -> jnp.ndarray:
    """The head's ``[V, C]`` operand in the compute dtype, from a weight whose
    vocabulary is axis ``vocab_axis`` (0: a tied table; 1: an output matrix)."""
    with jax.named_scope("lm_head_ce"):
        w = weight.astype(dtype)
        return w.T if vocab_axis else w


def head_logits(h: jnp.ndarray, weight: jnp.ndarray, vocab_axis: int, dtype) -> jnp.ndarray:
    """``h [B, S, C]`` → logits ``[B, S, V]`` in float32."""
    with jax.named_scope("lm_head_ce"):
        return jnp.einsum("bsc,cv->bsv" if vocab_axis else "bsc,vc->bsv", h, weight.astype(dtype),
                          preferred_element_type=jnp.float32)


def ce_chunk_rows(ce_chunk: int, batch: int, seq: int, vocab_size: int) -> int:
    """Rows a chunk of the fused CE's walk; ``ce_chunk < 0`` is automatic (these
    losses have no unfused form, so where ``auto_chunk`` would not fuse: 2,048)."""
    if ce_chunk < 0:
        return fused_ce.auto_chunk(batch, seq, vocab_size) or 2048
    return ce_chunk


def mean_weights(mask: jnp.ndarray) -> jnp.ndarray:
    """Row weights under which the fused CE's weighted sum is the masked mean."""
    return mask / jnp.maximum(mask.sum(), 1.0)


def head_ce(h, w_vd, targets, weights, chunk: int, z_loss_weight: float):
    with jax.named_scope("lm_head_ce"):
        return fused_ce.fused_cross_entropy(h, w_vd, targets, weights, chunk=chunk,
                                            z_weight=z_loss_weight)


def masked_ce(h, weight, vocab_axis: int, batch: Dict[str, jnp.ndarray], vocab_size: int,
              ce_chunk: int, z_loss_weight: float, dtype):
    """``(masked mean cross-entropy of the head on h, token count)`` through the
    fused CE."""
    targets, mask = batch["targets"], batch["mask"].astype(jnp.float32)
    chunk = ce_chunk_rows(ce_chunk, *targets.shape, vocab_size)
    w_vd = head_weight(weight, vocab_axis, dtype)
    with jax.named_scope("lm_head_ce"):
        weights = mean_weights(mask)
    return head_ce(h, w_vd, targets, weights, chunk, z_loss_weight), mask.sum()


def band_positions(seq_len: int, window: int) -> int:
    """(query, key) pairs a sliding-window layer attends to in one sequence."""
    w = min(window, seq_len)
    return w * (w + 1) // 2 + (seq_len - w) * w
