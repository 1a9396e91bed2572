#!/usr/bin/env python3
"""Time ``ops/selective_scan.py``'s two kernels alone on the chip, and check
them against the XLA form at a smaller size.

    python scripts/bench_scan.py [--seq 16384] [--inner 5120] [--state 16]

Prints ms a call of the forward and of forward + backward (each the median of
``--reps`` calls that end in ``block_until_ready``), and the largest gap of
the value and the six gradients against the XLA form at ``--check-seq``.
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

from mlx_cuda_distributed_pretraining_tpu.ops import selective_scan as ss


def operands(key, Bt, S, Di, N):
    ks = jax.random.split(key, 7)
    return (jax.random.normal(ks[0], (Bt, S, Di)),
            jax.nn.softplus(jax.random.normal(ks[1], (Bt, S, Di)) - 4.0),
            -jnp.broadcast_to(jnp.arange(1, N + 1, dtype=jnp.float32), (Di, N)),
            jax.random.normal(ks[3], (Bt, S, N)), jax.random.normal(ks[4], (Bt, S, N)),
            jnp.ones((Di,))), jax.random.normal(ks[6], (Bt, S, Di))


def timed(fn, args, reps):
    jax.block_until_ready(fn(*args))
    out = []
    for _ in range(reps):
        t = time.perf_counter()
        jax.block_until_ready(fn(*args))
        out.append(1e3 * (time.perf_counter() - t))
    return statistics.median(out)


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--seq", type=int, default=16384)
    p.add_argument("--inner", type=int, default=5120)
    p.add_argument("--state", type=int, default=16)
    p.add_argument("--check-seq", type=int, default=1024)
    p.add_argument("--reps", type=int, default=5)
    p.add_argument("--chunks", default="128", help="comma-separated kernel chunks to time")
    a = p.parse_args()
    print("device:", jax.devices()[0].device_kind, flush=True)
    args, w = operands(jax.random.PRNGKey(0), 1, a.check_seq, a.inner, a.state)
    loss = lambda backend: jax.jit(jax.value_and_grad(
        lambda *ops: jnp.sum(ss.selective_scan(*ops, backend=backend) * w), argnums=range(6)))
    (v0, g0), (v1, g1) = loss("xla")(*args), loss("kernel")(*args)
    gap = lambda x, y: float(jnp.max(jnp.abs(x - y)) / jnp.maximum(jnp.max(jnp.abs(y)), 1e-30))
    print(f"check at S={a.check_seq}: value {gap(v1, v0):.3g}, gradients "
          + " ".join(f"{gap(x, y):.3g}" for x, y in zip(g1, g0)), flush=True)
    args, w = operands(jax.random.PRNGKey(1), 1, a.seq, a.inner, a.state)
    for chunk in (int(c) for c in a.chunks.split(",")):
        fwd = jax.jit(lambda *ops: ss.selective_scan(*ops, backend="kernel", chunk=chunk))
        both = jax.jit(jax.grad(lambda *ops: jnp.sum(
            ss.selective_scan(*ops, backend="kernel", chunk=chunk) * w), argnums=range(6)))
        f, fb = timed(fwd, args, a.reps), timed(both, args, a.reps)
        updates = a.seq * a.inner * a.state
        print(f"chunk {chunk}: S={a.seq} Di={a.inner} N={a.state}: forward {f:.2f} ms "
              f"({updates / f / 1e6:.1f} G state updates/s), forward+backward {fb:.2f} ms", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
