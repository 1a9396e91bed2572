#!/usr/bin/env python3
"""Time the fused cross-entropy's differentiated walk alone on the chip, with
the Pallas kernel between its matmuls and with XLA's chain there.

    python scripts/bench_fused_ce.py [--shapes 16384x2560x200064,16384x4096x32768]

For each ``rows x width x vocabulary`` (defaults: the head of
``phi4-mini-flash-l6.train-seq16k`` and of ``mistral-7b-v0_3-l4.train-1chip``)
prints ms a call of ``ops/fused_ce.py::_grad_walk`` with ``_softmax_grad_kernel``
and with ``_softmax_grad_xla`` (each the median of ``--reps`` calls that end in
``block_until_ready``), ms a call of either function alone on one chunk's
logits (a jitted loop of 10 calls less one of 2, over 8: a single call is
mostly the host's round trip; alone, XLA's chain also takes the row maximum
that the walk's logits matmul computes in its epilogue), and the largest gap
between the two walks' loss, ``dX`` and ``dW``. It calls the two chunk
functions directly: the package has no switch.

On a v5e (my chip run, PR 45; ms, kernel | XLA's chain; chunks of 2,048 rows):

    rows x width x vocabulary    block   walk              one chunk alone   through the kernel
    16,384 x 2,560 x 200,064      16     322.43 | 342.57   3.569 | 5.757     689 GB/s
    16,384 x 4,096 x 32,768      128      82.20 |  85.98   0.619 | 0.977     651 GB/s
    16,384 x 3,584 x 16,384      256      36.35 |  36.58   0.337 | 0.511     597 GB/s

The kernel is bound by its copies: a body that only rounds the logits to
bfloat16 takes the same 3.59 ms a chunk at 200,064 (685 GB/s), as do blocks
of 32 rows, 16 lane registers a trip, and the exponential taken again in
place of the kept one. XLA's own passes over the same arrays read 695-756
GB/s in the step (PERF.md section 6, PR 45).
"""

from __future__ import annotations

import argparse
import functools
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from mlx_cuda_distributed_pretraining_tpu.ops import fused_ce

FORMS = (("kernel", fused_ce._softmax_grad_kernel), ("xla", fused_ce._softmax_grad_xla))


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
    p.add_argument("--shapes", default="16384x2560x200064,16384x4096x32768",
                   help="comma-separated rows x width x vocabulary")
    p.add_argument("--chunk", type=int, default=2048)
    p.add_argument("--dtype", default="bfloat16")
    p.add_argument("--reps", type=int, default=5)
    a = p.parse_args()
    dtype = jnp.dtype(a.dtype)
    print("device:", jax.devices()[0].device_kind, flush=True)
    for shape in a.shapes.split(","):
        N, D, V = (int(x) for x in shape.split("x"))
        ks = jax.random.split(jax.random.PRNGKey(0), 4)
        hidden = jax.random.normal(ks[0], (1, N, D), dtype)
        w_vd = (0.02 * jax.random.normal(ks[1], (V, D))).astype(dtype)
        targets = jax.random.randint(ks[2], (1, N), 0, V)
        mask = (jax.random.uniform(ks[3], (1, N)) > 0.1).astype(jnp.float32)
        rows = fused_ce._chunk_rows(N, a.chunk)
        print(f"{N} x {D} x {V}, chunks of {rows}: the kernel's block is "
              f"{fused_ce.softmax_grad_rows(rows, V, dtype)} rows", flush=True)
        logits = fused_ce._chunk_logits(hidden[0, :rows], w_vd, None, None)
        walks, ms = {}, {}
        for name, fn in FORMS:
            walk = jax.jit(lambda h, w, t, m, fn=fn: fused_ce._grad_walk(
                h, w, t, m, None, None, a.chunk, 0.0, fn))

            def alone(n, x, t, m, fn=fn):
                def again(i, carry):  # other targets a call, so no call is hoisted
                    loss, d = fn(x, (t + i) % V, m, None, 0.0, dtype)
                    return carry[0] + loss, d
                return jax.lax.fori_loop(0, n, again, (jnp.zeros(()), jnp.zeros((rows, V), dtype)))

            chunk_args = (logits, targets[0, :rows], mask[0, :rows])
            few, many = (timed(jax.jit(functools.partial(alone, n)), chunk_args, a.reps)
                         for n in (2, 10))
            ms[name] = (timed(walk, (hidden, w_vd, targets, mask), a.reps), (many - few) / 8)
            loss, (dx, dw, _) = walk(hidden, w_vd, targets, mask)
            # the gaps are taken on the host: two walks' dW do not fit the chip together
            walks[name] = jax.device_get((loss, dx, jnp.max(jnp.abs(dw), axis=1)))
            del loss, dx, dw
        gap = lambda x, y: float(abs(x - y).max() / max(abs(y).max(), 1e-30))  # noqa: E731
        print(f"  walk: kernel {ms['kernel'][0]:.2f} ms, xla {ms['xla'][0]:.2f} ms; "
              f"one chunk alone: kernel {ms['kernel'][1]:.3f} ms, xla {ms['xla'][1]:.3f} ms "
              f"({rows * V * (4 + dtype.itemsize) / ms['kernel'][1] / 1e6:.0f} GB/s through "
              f"the kernel)", flush=True)
        print("  gaps kernel against xla: loss {:.3g}, dX {:.3g}, dW row maxima {:.3g}".format(
            *(gap(x, y) for x, y in zip(walks["kernel"], walks["xla"]))), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
