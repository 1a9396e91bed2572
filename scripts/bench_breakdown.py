"""Component-level time breakdown for a bench scale (default 100m).

Answers VERDICT r2's "where does the other 70% go": measures, at the same
shape bench.py uses, the cost of
  - loss forward only,
  - forward+backward (value_and_grad),
  - the full optimizer step,
  - the attention stack alone (L x flash fwd; one-layer fwd+bwd),
  - the CE head alone (fused and unfused),
so fwd / bwd / optimizer / attention / CE shares can be read directly.

Each section times ``steps`` iterations in ONE ``lax.scan`` dispatch, so
the numbers are pure chip compute — compare against bench.py rows taken
with ``BENCH_MEGASTEP`` set (the default per-step bench rows additionally
pay one host dispatch per step). ``BREAKDOWN_CHAIN=dispatch`` restores
per-call chaining. Prints JSON lines; run on the TPU:

    python scripts/bench_breakdown.py [--scale 100m] [--steps 10]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from functools import partial

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from bench import SCALES, flops_per_token, mfu_or_unknown


def chain_time(fn, state, steps, donate=False):
    """fn: state -> state (jitted). Times ``steps`` iterations in ONE
    dispatch (lax.scan), so the host's per-dispatch cost is paid once
    instead of per iteration and does not blur the component shares.

    ``donate`` must be True ONLY when ``state`` is a fresh tree owned by
    this section (the full-step sections: params + Adam moments would
    otherwise be held twice and OOM at scales the bench megastep fits)
    and False for sections whose input (module-level params/q0/h0) is
    reused by later sections — donating those would delete their buffers.
    BREAKDOWN_CHAIN=dispatch restores the old per-call chaining."""
    if os.environ.get("BREAKDOWN_CHAIN") == "dispatch":
        out = fn(state)
        jax.device_get(jax.tree_util.tree_leaves(out)[0].ravel()[:1])
        t0 = time.perf_counter()
        cur = out
        for _ in range(steps):
            cur = fn(cur)
        jax.device_get(jax.tree_util.tree_leaves(cur)[0].ravel()[:1])
        return (time.perf_counter() - t0) / steps

    @partial(jax.jit, donate_argnums=(0,) if donate else ())
    def scanned(s):
        return jax.lax.scan(lambda c, _: (fn(c), None), s, None,
                            length=steps)[0]

    out = scanned(state)  # compile + warm
    jax.device_get(jax.tree_util.tree_leaves(out)[0].ravel()[:1])
    t0 = time.perf_counter()
    cur = scanned(out)
    jax.device_get(jax.tree_util.tree_leaves(cur)[0].ravel()[:1])
    return (time.perf_counter() - t0) / steps


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", default="100m", choices=list(SCALES))
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--vocab", type=int, default=32768)
    a = ap.parse_args()

    from mlx_cuda_distributed_pretraining_tpu.config import TrainingConfig
    from mlx_cuda_distributed_pretraining_tpu.models import llama
    from mlx_cuda_distributed_pretraining_tpu.ops.flash_attention import flash_attention
    from mlx_cuda_distributed_pretraining_tpu.ops.fused_ce import fused_cross_entropy
    from mlx_cuda_distributed_pretraining_tpu.optim import build_optimizer
    from mlx_cuda_distributed_pretraining_tpu.train.train_step import (
        init_train_state,
        make_train_step,
    )

    sc = SCALES[a.scale]
    B, S, remat = sc["batch"], sc["seq"], sc["remat"]
    args = llama.LlamaArgs(vocab_size=a.vocab, max_position_embeddings=S,
                           attention_type="flash", **sc["shape"])
    params = llama.init_params(jax.random.PRNGKey(0), args)
    n_params = llama.num_params(params)
    L, H, Dh = args.num_layers, args.num_heads, args.head_dim
    D = args.hidden_size

    rng = np.random.default_rng(0)
    x = rng.integers(1, a.vocab - 4, size=(B, S + 1)).astype(np.int32)
    batch = {"inputs": jnp.asarray(x[:, :-1]), "targets": jnp.asarray(x[:, 1:]),
             "mask": jnp.ones((B, S), jnp.float32)}

    results = {}

    def report(name, sec):
        results[name] = sec * 1e3
        print(json.dumps({"component": name, "ms": round(sec * 1e3, 2)}), flush=True)

    # full optimizer step (fused CE). The jitted step donates its state
    # buffers, so every timed section gets a FRESH params/state tree —
    # reusing a donated tree raises 'Array has been deleted' on device.
    opt = build_optimizer(TrainingConfig(
        hyperparameters={"learning_rate": 1e-3}, scheduler={"type": "cosine"},
        optimization={"optimizer": "adamw"}), 1000)

    def fresh_params():
        return llama.init_params(jax.random.PRNGKey(0), args)

    def loss_fused(p, b):
        return llama.loss_fn(p, b, args, compute_dtype=jnp.bfloat16,
                             remat=remat, ce_chunk=2048)

    def loss_unfused(p, b):
        return llama.loss_fn(p, b, args, compute_dtype=jnp.bfloat16,
                             remat=remat, ce_chunk=0)

    step, _ = make_train_step(loss_fused, opt)
    report("full_step_fused_ce",
           chain_time(lambda s: step(s, batch)[0],
                      init_train_state(fresh_params(), opt), a.steps,
                      donate=True))

    step_u, _ = make_train_step(loss_unfused, opt)
    report("full_step_unfused_ce",
           chain_time(lambda s: step_u(s, batch)[0],
                      init_train_state(fresh_params(), opt), a.steps,
                      donate=True))

    # non-donating sections below reuse the module-level params (never
    # donated: both full-step sections built their own trees)

    # forward-only loss (chained by feeding loss into a dummy param perturbation)
    @jax.jit
    def fwd_only(p):
        loss, _ = loss_fused(p, batch)
        return jax.tree_util.tree_map(lambda a: a + 0 * loss.astype(a.dtype), p)

    report("forward_loss", chain_time(fwd_only, params, a.steps))

    # forward+backward (no optimizer)
    @jax.jit
    def fwd_bwd(p):
        g = jax.grad(lambda q: loss_fused(q, batch)[0])(p)
        return jax.tree_util.tree_map(lambda a, b: a + 0 * b.astype(a.dtype), p, g)

    report("forward_backward", chain_time(fwd_bwd, params, a.steps))

    # attention stack alone: L flash calls fwd / fwd+bwd
    q0 = jnp.asarray(rng.normal(size=(B, S, H, Dh)).astype(np.float32), jnp.bfloat16)

    @jax.jit
    def attn_stack(q):
        for _ in range(L):
            q = flash_attention(q, q, q)
        return q

    report("attention_stack_fwd", chain_time(attn_stack, q0, a.steps))

    @jax.jit
    def attn_stack_bwd(q):
        # one layer under grad (key: attention_one_layer_fwd_bwd — multiply
        # by L for the stack share)
        g = jax.grad(lambda z: flash_attention(z, z, z).astype(jnp.float32).sum())(q)
        return q + 0 * g

    report("attention_one_layer_fwd_bwd", chain_time(attn_stack_bwd, q0, a.steps))

    # CE head alone
    h0 = jnp.asarray(rng.normal(size=(B, S, D)).astype(np.float32), jnp.bfloat16)
    w = params["tok_embeddings"]["weight"].astype(jnp.bfloat16)

    @jax.jit
    def ce_fused(h):
        nll = fused_cross_entropy(h, w, batch["targets"], batch["mask"], chunk=2048)
        return h + 0 * nll.astype(h.dtype)

    report("ce_head_fused_fwd", chain_time(ce_fused, h0, a.steps))

    @jax.jit
    def ce_fused_bwd(h):
        g = jax.grad(lambda z: fused_cross_entropy(
            z, w, batch["targets"], batch["mask"], chunk=2048))(h)
        return h + 0 * g

    report("ce_head_fused_fwd_bwd", chain_time(ce_fused_bwd, h0, a.steps))

    ft = flops_per_token(n_params, L, S, H * Dh)
    step_s = results["full_step_fused_ce"] / 1e3
    tok_s = B * S / step_s
    print(json.dumps({
        "scale": a.scale, "batch": B, "seq": S, "vocab": a.vocab,
        "params_m": round(n_params / 1e6, 1),
        "tok_s": round(tok_s, 0),
        "mfu": mfu_or_unknown(ft, tok_s),
        "breakdown_ms": {k: round(v, 2) for k, v in results.items()},
    }), flush=True)


if __name__ == "__main__":
    main()
