#!/usr/bin/env python3
"""Time the Kimi Delta Attention core alone on the chip (``ops/kda.py``): the
forward kernel, and forward (with the states saved) plus backward, at each
``--chunks`` size, against the roof ``benchmark/flops/kda_chunk.py`` counts.

    python scripts/bench_kda.py [--shape 2x8192x32x128] [--chunks 64,128] [--heads 1,2,4] [--reps 5]

Prints ms a call (a jitted loop of ``--inner`` calls, the median of ``--reps``
runs that end in ``block_until_ready``, over ``--inner``) and the share of the
roof; ``--check`` also compares the kernel's value and gradients at a short
length against the XLA form on the chip. Step 0 of PR 51 (PERF.md section 6).
"""

from __future__ import annotations

import argparse
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from benchmark import peaks
from benchmark.flops import kda_chunk
from mlx_cuda_distributed_pretraining_tpu.ops import kda as kda_ops


def operands(B, S, H, d, dtype, seed=0, scale=0.3):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    n = lambda k, s: jax.random.normal(k, s, jnp.float32)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    q = (unit(n(ks[0], (B, S, H, d))) * d ** -0.5).astype(dtype)
    k = unit(n(ks[1], (B, S, H, d))).astype(dtype)
    v = n(ks[2], (B, S, H, d)).astype(dtype)
    g = -jax.nn.softplus(n(ks[3], (B, S, H, d))) * scale
    beta = jax.nn.sigmoid(n(ks[4], (B, S, H)))
    w = n(ks[5], (B, S, H, d)).astype(dtype)
    return q, k, v, g, beta, w


def timed(fn, args, reps, inner):
    """Every trip's ``v`` takes a (zero) term of the trip before, so the call is not invariant in
    the loop: XLA hoisted an invariant call out and PR 51's first step 0 read a quarter of the time."""
    def loop(q, k, v, *rest):
        def body(_, acc):
            out = fn(q, k, v + (acc * 0.0).astype(v.dtype), *rest)
            return acc + sum(jnp.mean(o.astype(jnp.float32)) for o in jax.tree_util.tree_leaves(out))
        return jax.lax.fori_loop(0, inner, body, jnp.zeros((), jnp.float32))
    run = jax.jit(loop)
    jax.block_until_ready(run(*args))
    out = []
    for _ in range(reps):
        t = time.perf_counter()
        jax.block_until_ready(run(*args))
        out.append(time.perf_counter() - t)
    return 1e3 * statistics.median(out) / inner


def main():
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--shape", default="2x8192x32x128")
    p.add_argument("--chunks", default="64,128")
    p.add_argument("--heads", default=str(kda_ops.HEADS_PER_STEP),
                   help="heads a grid step (ops/kda.py::HEADS_PER_STEP), comma-separated")
    p.add_argument("--reps", type=int, default=5)
    p.add_argument("--inner", type=int, default=4)
    p.add_argument("--check", action="store_true")
    a = p.parse_args()
    B, S, H, d = (int(x) for x in a.shape.split("x"))
    dev = jax.devices()[0]
    pk = peaks.peak(dev.device_kind)
    print(f"device: {dev.platform} {dev.device_kind}; call {B} x {S} x {H} x {d} bfloat16", flush=True)
    q, k, v, g, beta, w = operands(B, S, H, d, jnp.bfloat16)
    roof_f = 1e3 * kda_chunk.roof_seconds(kda_chunk.fwd_flops(B, S, H, d), kda_chunk.fwd_bytes(B, S, H, d), pk)
    roof_b = 1e3 * kda_chunk.roof_seconds(kda_chunk.bwd_flops(B, S, H, d), kda_chunk.bwd_bytes(B, S, H, d), pk)
    print(f"roof: forward {roof_f:.3f} ms, backward {roof_b:.3f} ms (HBM binds both)", flush=True)
    for c, heads in ((int(c), int(h)) for c in a.chunks.split(",") for h in a.heads.split(",")):
        fwd = lambda q, k, v, g, b, c=c, heads=heads: kda_ops._kda(q, k, v, g, b, "kernel", c, heads)
        grad = jax.grad(lambda q, k, v, g, b, w, c=c: jnp.sum(fwd(q, k, v, g, b).astype(jnp.float32) * w),
                        argnums=(0, 1, 2, 3, 4))
        t_f = timed(fwd, (q, k, v, g, beta), a.reps, a.inner)
        t_fb = timed(grad, (q, k, v, g, beta, w), a.reps, a.inner)
        print(f"chunk {c}, {heads} head(s) a grid step: forward {t_f:.2f} ms ({100 * roof_f / t_f:.1f}% of "
              f"its roof); forward with states + backward {t_fb:.2f} ms (backward about {t_fb - t_f:.2f} ms, "
              f"{100 * roof_b / max(t_fb - t_f, 1e-9):.1f}% of its roof)", flush=True)
    if a.check:
        qs, ks_, vs, gs, bs, ws = operands(1, 512, 2, d, jnp.float32, seed=1)
        rel = lambda x, y: float(jnp.linalg.norm(x - y) / (jnp.linalg.norm(y) + 1e-30))
        for c in (int(x) for x in a.chunks.split(",")):
            core = lambda be: (lambda *o: kda_ops._kda(*o, be, c, kda_ops.HEADS_PER_STEP))
            f = lambda be: (lambda *o: jnp.sum(core(be)(*o) * ws))
            val = rel(core("kernel")(qs, ks_, vs, gs, bs), core("xla")(qs, ks_, vs, gs, bs))
            gk = jax.grad(f("kernel"), argnums=(0, 1, 2, 3, 4))(qs, ks_, vs, gs, bs)
            gx = jax.grad(f("xla"), argnums=(0, 1, 2, 3, 4))(qs, ks_, vs, gs, bs)
            print(f"check chunk {c} (float32, 1 x 512 x 2 x {d}): value {val:.2e}; gradients "
                  + " ".join(f"{rel(x, y):.2e}" for x, y in zip(gk, gx)), flush=True)


if __name__ == "__main__":
    main()
