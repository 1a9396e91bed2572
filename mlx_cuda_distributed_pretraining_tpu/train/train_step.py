"""The jitted train step — the whole inner loop is one XLA program.

Where the reference's hot loop interleaves Python between device ops
(reference: core/training.py:1637-1768 — batch fetch, value_and_grad,
clip, accumulate, optimizer update, ``mx.eval`` sync), here everything from
gradient to optimizer update compiles into a single donated-buffer XLA
executable:

- gradient accumulation is a ``lax.scan`` over microbatches (reference:
  tree_map adds per step, :1669-1696);
- mixed precision: params stay fp32 (master), forward runs in
  ``compute_dtype`` (bf16), RMSNorm/softmax/CE in fp32;
- rematerialization via per-layer ``jax.checkpoint`` policies replaces the
  reference's inert ``GradientCheckpointer`` (core/training.py:584-618);
- under a mesh, in/out shardings implement DP/FSDP/TP/ZeRO-1; XLA emits the
  gradient psum over ICI (replacing hybrid_distributed.py's
  ``_aggregate_gradients`` thread);
- non-finite guard: the metrics carry a ``nonfinite`` flag (the numerics
  analogue of the reference's absent sanitizers, SURVEY.md §5).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding

from ..optim.base import Transform, apply_updates, global_norm
from ..optim.fused import fused_apply_of
from ..ops.donation import donate_argnums
from ..parallel.sharding_rules import batch_pspec, state_sharding

TrainState = Dict[str, Any]  # {"params", "opt_state", "step"}


def init_train_state(params: Any, optimizer: Transform) -> TrainState:
    return {
        "params": params,
        "opt_state": optimizer.init(params),
        "step": jnp.zeros((), jnp.int32),
    }


def make_train_step(
    loss_fn: Callable,
    optimizer: Transform,
    accum_steps: int = 1,
    mesh: Optional[Mesh] = None,
    zero_level: int = 0,
    log_grad_norm: bool = False,
    params_like: Optional[Any] = None,
    moe_stats_experts: int = 0,
) -> Tuple[Callable, Optional[Any]]:
    """Build the jitted step.

    ``loss_fn(params, batch) -> (loss, token_count)``.
    Returns ``(step_fn, state_shardings)``; state_shardings is None off-mesh.
    ``step_fn(state, batch) -> (state, metrics)`` with donated state.

    ``moe_stats_experts > 0`` declares that loss_fn was built
    ``with_moe_stats`` and returns ``(loss, (token_count, stats))``
    (models/llama.py loss_fn / models/moe.py): the layer-summed expert-load
    vector and dropped-selection count then ride the metrics dict as
    ``moe_load`` [E] / ``moe_dropped``, with whatever else the model's
    stats hold (a second head's ``main_loss`` / ``mtp_loss``: models/xing.py).
    """
    moe_stats = moe_stats_experts > 0

    def grads_of(params, batch):
        (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, batch)
        if moe_stats:
            toks, stats = aux
        else:
            toks, stats = aux, None
        return loss, toks, stats, grads

    def accumulate(params, batch):
        # batch leaves [A*b, L] -> scan over A microbatches of [b, L]
        def reshape(x):
            return x.reshape(accum_steps, x.shape[0] // accum_steps, *x.shape[1:])

        micro = jax.tree_util.tree_map(reshape, batch)
        zero_g = jax.tree_util.tree_map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        # every key the loss_fn reports, which may be more than the routing pair
        zero_s = jax.tree_util.tree_map(
            lambda a: jnp.zeros(a.shape, a.dtype),
            jax.eval_shape(lambda: grads_of(params, jax.tree_util.tree_map(
                lambda x: x[0], micro))[2])) if moe_stats else None

        def body(carry, mb):
            acc_loss, acc_toks, acc_s, acc_g = carry
            loss, toks, stats, g = grads_of(params, mb)
            with jax.named_scope("grad_accum"):
                acc_g = jax.tree_util.tree_map(lambda a, b: a + b, acc_g, g)
            if moe_stats:
                acc_s = {k: acc_s[k] + stats[k] for k in acc_s}
            return (acc_loss + loss, acc_toks + toks, acc_s, acc_g), None

        (loss_sum, toks, stats, grads), _ = jax.lax.scan(
            body,
            (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32), zero_s, zero_g),
            micro,
        )
        with jax.named_scope("grad_accum"):
            inv = 1.0 / accum_steps
            if moe_stats:  # loss terms average over microbatches, counts add up
                stats = {k: v * inv if k.endswith("_loss") else v for k, v in stats.items()}
            return loss_sum * inv, toks, stats, jax.tree_util.tree_map(lambda g: g * inv, grads)

    def train_step(state: TrainState, batch: Dict[str, jnp.ndarray]):
        params = state["params"]
        if accum_steps > 1:
            loss, toks, stats, grads = accumulate(params, batch)
        else:
            loss, toks, stats, grads = grads_of(params, batch)
        fused = fused_apply_of(optimizer)
        with jax.named_scope("optimizer"):
            if fused is not None:
                # Single-pass update+apply (optim/fused.py): bitwise equal to
                # the chain below, but with no intermediate updates tree, so
                # the donated params/moments alias input->output cleanly
                # (graftaudit donation-gap 0 on this program).
                new_params, opt_state = fused(grads, state["opt_state"], params)
            else:
                updates, opt_state = optimizer.update(grads, state["opt_state"], params)
                new_params = apply_updates(params, updates)
        metrics = {
            "loss": loss,
            "toks": toks,
            "nonfinite": jnp.logical_not(jnp.isfinite(loss)).astype(jnp.int32),
        }
        if moe_stats:
            metrics.update(stats)
        if log_grad_norm:
            metrics["grad_norm"] = global_norm(grads)
        new_state = {"params": new_params, "opt_state": opt_state, "step": state["step"] + 1}
        return new_state, metrics

    if mesh is None:
        return jax.jit(train_step, donate_argnums=donate_argnums(0)), None

    assert params_like is not None, "params_like required to derive shardings"
    probe_state = jax.eval_shape(lambda p: init_train_state(p, optimizer), params_like)
    shardings = state_sharding(probe_state, mesh, zero_level)
    # every array of a batch is [B, L] (a diffusion batch has five): one prefix
    batch_shardings = NamedSharding(mesh, batch_pspec(mesh))
    metric_sharding = NamedSharding(mesh, jax.sharding.PartitionSpec())
    step_fn = jax.jit(
        train_step,
        donate_argnums=donate_argnums(0),
        in_shardings=(shardings, batch_shardings),
        out_shardings=(shardings, None),
    )
    return step_fn, shardings


def make_eval_step(loss_fn: Callable, mesh: Optional[Mesh] = None,
                   state_shardings: Optional[Any] = None) -> Callable:
    """Jitted ``(params, batch) -> (loss, token_count)`` (token-weighted val
    loss — deliberate divergence from the reference's mean-of-batch-means,
    SURVEY.md §7.3)."""

    def eval_step(params, batch):
        loss, toks = loss_fn(params, batch)
        return loss, toks

    if mesh is None:
        return jax.jit(eval_step)
    batch_shardings = NamedSharding(mesh, batch_pspec(mesh))
    in_shardings = (
        state_shardings["params"] if state_shardings is not None else None,
        batch_shardings,
    )
    return jax.jit(eval_step, in_shardings=in_shardings)
