#!/usr/bin/env python3
"""Time a KDA mixer's kernels alone on the chip: the delta rule's core
(``ops/kda.py``), the forward kernel and forward (with the states saved) plus
backward at each ``--chunks`` size, against the roof
``benchmark/flops/kda_chunk.py`` counts; or, with ``--prologue``, the q, k, v
prologue (``ops/short_conv.py``), its two kernels at each ``--blocks`` shape
and its XLA form, against the bytes a call has to move, after one line with the
seconds the host takes to trace and lower each kernel body once (what every run
of the cell pays before its first step, whatever the compile cache holds).

    python scripts/bench_kda.py [--shape 2x8192x32x128] [--chunks 64,128] [--heads 1,2,4] [--reps 5] [--check]
    python scripts/bench_kda.py --prologue [--blocks 512x1024,256x2048] [--tiles 32] [--check]

Prints ms a call (a jitted loop of ``--inner`` calls, the median of ``--reps``
runs that end in ``block_until_ready``, over ``--inner``) and the share of the
roof (``short_conv``: GB/s of the required bytes). ``--check`` also holds the
core's kernels, at each chunk, to the benchmark's float32 recurrence on the
chip at float32 and at bfloat16 operands (value and five gradients, printed
after the chunk's times), and the prologue's to their XLA form. Readings at the
cell's call, forward | backward ms: 18.7 | 25.1 with sixteen partner passes a
chunk (PR 51 to 54), 17.8 | 22.9 with the pairs as seven levels of matmuls (PR
55: 20.5 | 25.0 at chunk 64; 17.9 | 23.2 at 2 heads, 17.7 | 22.8 at 8), against a
float32 solve that alone is 9.6 | 7.8 of them (PERF.md section 6: step 0 of PR 51
and of PR 55, the block shapes of PR 52, the bodies' host cost of PR 53).
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
from mlx_cuda_distributed_pretraining_tpu.ops import short_conv as conv_ops


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


def _mean(o):
    return jnp.mean(o.astype(jnp.float32))


def timed(fn, args, reps, inner, chain=2, taste=_mean):
    """Every trip's ``v`` (operand ``chain``) takes a (zero) term of the trip before, so the call is
    not invariant in the loop: XLA hoisted an invariant call out and PR 51's first step 0 read a
    quarter of the time. ``taste``: what of each result the next trip waits for."""
    def loop(*ops):
        def body(_, acc):
            fed = ops[chain] + (acc * 0.0).astype(ops[chain].dtype)
            out = fn(*ops[:chain], fed, *ops[chain + 1:])
            return acc + sum(taste(o) for o in jax.tree_util.tree_leaves(out))
        return jax.lax.fori_loop(0, inner, body, jnp.zeros((), jnp.float32))
    run = jax.jit(loop)
    jax.block_until_ready(run(*args))
    out = []
    for _ in range(reps):
        t = time.perf_counter()
        jax.block_until_ready(run(*args))
        out.append(time.perf_counter() - t)
    return 1e3 * statistics.median(out) / inner


def _with_grads(f, g):
    """``f(*ops)`` and its cotangents under ``g``, one tuple: a forward and a backward call."""
    return lambda *ops: (lambda y, vjp: (y,) + vjp(g))(*jax.vjp(f, *ops))


def lowering_seconds(x, w, H, d):
    """Seconds to trace and lower (``jit(...).lower()``, nothing compiled) the prologue's call
    once from cold caches, forward alone and differentiated (the backward body and the trace of
    the forward), with the norm (q and k: one body, the scale an operand) and without (v)."""
    out = []
    for label, heads in (("norm", H), ("no norm", None)):
        call = lambda x, w: conv_ops.short_conv(x, w, heads=heads, scale=d ** -0.5 if heads else 1.0, backend="kernel")
        grad = jax.grad(lambda x, w: call(x, w).astype(jnp.float32).sum(), argnums=(0, 1))
        for fn in (call, grad):
            jax.clear_caches()
            t0 = time.perf_counter()
            jax.jit(fn).lower(x, w)
            out.append(time.perf_counter() - t0)
        print(f"host, trace and lower once, {label}: forward {out[-2]:.3f} s, differentiated {out[-1]:.3f} s", flush=True)
    print(f"host, trace and lower once, all four bodies: {sum(out):.3f} s", flush=True)


def bench_short_conv(a, B, S, H, d):
    """The prologue at the cell's call, ``[B, S, H d]`` bfloat16 and four taps: the kernels are
    chained through the taps (16 K numbers) and tasted by one element, so a trip is the call
    and nothing the size of its operand; XLA's form would be cut down to that element, so its
    trips take a mean, which fuses into its last pass."""
    D, dt = H * d, jnp.bfloat16
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(ks[0], (B, S, D), jnp.float32).astype(dt)
    w = (jax.random.normal(ks[1], (D, 4), jnp.float32) * 0.5).astype(dt)
    g = jax.random.normal(ks[2], (B, S, D), jnp.float32).astype(dt)
    fwd_bytes, bwd_bytes = 2 * x.size * 2, 3 * x.size * 2
    print(f"required bytes: forward {fwd_bytes / 1e6:.0f} MB (a read, y written), backward "
          f"{bwd_bytes / 1e6:.0f} MB (a and dy read, da written); HBM {peaks.peak(jax.devices()[0].device_kind)['hbm_bytes_per_s'] / 1e9:.0f} GB/s", flush=True)
    lowering_seconds(x, w, H, d)
    one = lambda o: o.ravel()[0].astype(jnp.float32)

    def report(label, heads, call, taste):
        fwd = lambda w, x: call(x, w, heads)
        both = lambda w, x, g: _with_grads(lambda x, w: call(x, w, heads), g)(x, w)
        t_f = timed(fwd, (w, x), a.reps, a.inner, chain=0, taste=taste)
        t_fb = timed(both, (w, x, g), a.reps, a.inner, chain=0, taste=taste)
        print(f"{label}, {'norm over ' + str(heads) + ' heads' if heads else 'no norm'}: forward {t_f:.3f} ms "
              f"({fwd_bytes / t_f / 1e6:.0f} GB/s); forward + backward {t_fb:.3f} ms (backward about "
              f"{t_fb - t_f:.3f} ms, {bwd_bytes / max(t_fb - t_f, 1e-9) / 1e6:.0f} GB/s)", flush=True)

    for heads in (H, None):
        report("xla", heads, lambda x, w, heads: conv_ops._short_conv(
            x, w, None, heads, 1.0, dt, "xla", 0, 0), _mean)
    for tile in (int(t) for t in a.tiles.split(",")):
        conv_ops._TILE_ROWS = tile     # the walk inside a block: the module's constant, steered here alone
        for rows, lanes in (tuple(int(v) for v in b.split("x")) for b in a.blocks.split(",")):
            for heads in (H, None):
                report(f"kernel rows {rows} lanes {lanes} tile {tile}", heads,
                       lambda x, w, heads: conv_ops._short_conv(x, w, None, heads, 1.0, dt, "kernel", rows, lanes), one)
    if a.check:
        rel = lambda p, q: float(jnp.linalg.norm(p.astype(jnp.float32) - q.astype(jnp.float32))
                                 / (jnp.linalg.norm(q.astype(jnp.float32)) + 1e-30))
        xs, gs = x[:1, :2048], g[:1, :2048]
        bias = jax.random.normal(ks[2], (D,), jnp.float32) * 0.1
        for adt, heads, b in ((jnp.bfloat16, H, None), (jnp.float32, H, None), (jnp.float32, None, bias)):
            xa, ga = xs.astype(adt), gs.astype(adt)
            form = lambda be: jax.jit(_with_grads(lambda x, w, b: conv_ops.short_conv(
                x, w, bias=b, heads=heads, scale=d ** -0.5 if heads else 1.0, out_dtype=adt, backend=be), ga))
            got, want = form("kernel")(xa, w.astype(jnp.float32), b), form("xla")(xa, w.astype(jnp.float32), b)
            print(f"check {jnp.dtype(adt).name}, {'heads ' + str(heads) if heads else 'no norm, bias'} (1 x 2048 x {D}): "
                  "value, da, dw" + (", dbias " if b is not None else " ")
                  + " ".join(f"{rel(p, q):.2e}" for p, q in zip(jax.tree_util.tree_leaves(got),
                                                              jax.tree_util.tree_leaves(want))), flush=True)


def main():
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--op", choices=("kda", "short_conv"), default="kda")
    p.add_argument("--prologue", dest="op", action="store_const", const="short_conv",
                   help="the q, k, v prologue's kernel pair (the same as --op short_conv)")
    p.add_argument("--blocks", default=f"{conv_ops._BLOCK_ROWS}x{conv_ops._BLOCK_LANES}",
                   help="short_conv: rows x lanes of a block, comma-separated")
    p.add_argument("--tiles", default=str(conv_ops._TILE_ROWS), help="short_conv: rows a trip inside a block")
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
    if a.op == "short_conv":
        return bench_short_conv(a, B, S, H, d)
    q, k, v, g, beta, w = operands(B, S, H, d, jnp.bfloat16)
    roof_f = 1e3 * kda_chunk.roof_seconds(kda_chunk.fwd_flops(B, S, H, d), kda_chunk.fwd_bytes(B, S, H, d), pk)
    roof_b = 1e3 * kda_chunk.roof_seconds(kda_chunk.bwd_flops(B, S, H, d), kda_chunk.bwd_bytes(B, S, H, d), pk)
    print(f"roof: forward {roof_f:.3f} ms, backward {roof_b:.3f} ms (HBM binds both)", flush=True)
    for c in (int(c) for c in a.chunks.split(",")):
        for heads in (int(h) for h in a.heads.split(",")):
            fwd = lambda q, k, v, g, b, c=c, heads=heads: kda_ops._kda(q, k, v, g, b, "kernel", c, heads)
            grad = jax.grad(lambda q, k, v, g, b, w, fwd=fwd: jnp.sum(fwd(q, k, v, g, b).astype(jnp.float32) * w),
                            argnums=(0, 1, 2, 3, 4))
            t_f = timed(fwd, (q, k, v, g, beta), a.reps, a.inner)
            t_fb = timed(grad, (q, k, v, g, beta, w), a.reps, a.inner)
            print(f"chunk {c}, {heads} head(s) a grid step: forward {t_f:.2f} ms ({100 * roof_f / t_f:.1f}% of "
                  f"its roof); forward with states + backward {t_fb:.2f} ms (backward about {t_fb - t_f:.2f} ms, "
                  f"{100 * roof_b / max(t_fb - t_f, 1e-9):.1f}% of its roof)", flush=True)
        if a.check:
            check(c, d)


def check(c, d, shape=(1, 512, 2)):
    """The kernels' value and five gradients at chunk ``c`` against the benchmark's float32 recurrence
    (a step at a time, ``benchmark/reference/kimi_linear.py``) on the same inputs, at float32 and at
    bfloat16 operands, at a gentle and at a steep decay: this device's own arithmetic, which the CPU
    tests (interpret mode) and the cell's ``correct`` (PERF.md section 7 (f)) do not read."""
    from benchmark.reference.kimi_linear import delta_rule

    f32 = lambda x: x.astype(jnp.float32)
    rel = lambda x, y: float(jnp.linalg.norm(f32(x) - y) / (jnp.linalg.norm(y) + 1e-30))
    both = lambda core: jax.jit(lambda ops, w: _with_grads(lambda *o: f32(core(*o)), w)(*ops))
    kernel = both(lambda *o: kda_ops._kda(*o, "kernel", c, kda_ops.HEADS_PER_STEP))
    recurrence = both(delta_rule)
    for dtype, decay in ((t, s) for t in (jnp.float32, jnp.bfloat16) for s in (0.3, 4.0)):
        q, k, v, g, b, w = operands(*shape, d, dtype, seed=1, scale=decay)
        got, want = kernel((q, k, v, g, b), f32(w)), recurrence((f32(q), f32(k), f32(v), g, b), f32(w))
        print(f"check chunk {c}, {jnp.dtype(dtype).name} operands, decay {decay} a step ({' x '.join(map(str, shape))} x {d}) "
              f"against the float32 recurrence: value {rel(got[0], want[0]):.2e}; gradients q k v g beta "
              + " ".join(f"{rel(x, y):.2e}" for x, y in zip(got[1:], want[1:])), flush=True)


if __name__ == "__main__":
    main()
