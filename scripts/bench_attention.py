"""Attention kernel microbenchmark on the real TPU chip.

Four modes:

- default: the Pallas flash kernel (fwd+bwd) against XLA's fused attention
  (reference_attention: einsum + softmax, fully materialized scores) across
  sequence lengths. The VERDICT r1 done-bar: flash >= XLA at seq
  2048/4096/8192 and seq 16k running without OOM.
- ``--sweep``: (block_q, block_kv) over three shapes, through
  ``flash_attention`` (fwd+bwd, transposes included).
- ``--forward-only``: the raw ``flash_fwd`` kernel alone on [B, H, S, D],
  one row per (path, block_q x block_kv) of ``--paths`` x ``--blocks`` at
  ``--batch`` x ``--seq``, with ``share_of_peak`` by the benchmark's count
  (``benchmark/flops/flash_attention.py``: 2 B Hq S^2 D a causal call, twice
  that under ``--mask-type full``) over ``benchmark/peaks.py``. This is the
  row a trace's ``flash_fwd`` self time compares with: the cell
  ``mistral-7b-v0_3-l4.train-1chip`` is
  ``--heads 32 --kv-heads 8 --head-dim 128 --batch 4 --seq 4096``.
  ``--mask-type sliding_window --window W`` and ``--mask-type block_diffusion
  --block-length B'`` (``--seq`` is then the call's ``2 L`` rows, both copies)
  count the mask's own pairs (``flops/flash_window.py``: the band;
  ``flops/flash_blockdiff.py``: ``L^2 + L B'`` a head): the cell
  ``sdar-30b-a3b-ep8.train-bd8k`` is ``--heads 32 --kv-heads 4 --head-dim 128
  --batch 1 --seq 16384 --mask-type block_diffusion --block-length 4``.
- ``--backward-only``: the same rows for the raw ``flash_bwd_dq`` and
  ``flash_bwd_dkv`` (``--kernels``), on the residuals of one forward call
  (3 and 4 half squares a causal call by the benchmark's count): what a
  trace's ``flash_bwd_dq`` / ``flash_bwd_dkv`` self times compare with.
  Given with ``--forward-only``, one process prints all three kernels' rows.

Methodology: each measurement jits an on-device ``lax.fori_loop`` that
chains N attention calls (output feeds the next query, so nothing is
DCE'd), syncs via a 1-element ``device_get``, and reports
(T(n_hi) - T(n_lo)) / (n_hi - n_lo) to cancel the fixed per-call overhead.

Usage (on TPU):  python scripts/bench_attention.py [--sweep | --forward-only | --backward-only]
Writes results to stdout as JSON lines.
"""

import argparse
import json
import os
import sys
import time
from functools import partial

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp


def timed_loop(step, q, k, v, *rest, n_lo=5, n_hi=25):
    """step: (q, k, v, *rest) -> array shaped like q. Returns seconds per
    call. Every array the step reads is an argument of the jitted loop: one
    it closed over would be compiled in as a constant."""

    @partial(jax.jit, static_argnums=(0,))
    def loop(iters, q, k, v, *rest):
        return jax.lax.fori_loop(0, iters, lambda i, qq: step(qq, k, v, *rest), q)

    def run(iters):
        out = loop(iters, q, k, v, *rest)
        jax.device_get(out[(0,) * (out.ndim - 1) + (slice(0, 1),)])

    run(n_lo)  # compile both shapes
    run(n_hi)
    t0 = time.perf_counter()
    run(n_lo)
    t_lo = time.perf_counter() - t0
    t0 = time.perf_counter()
    run(n_hi)
    t_hi = time.perf_counter() - t0
    return max((t_hi - t_lo) / (n_hi - n_lo), 1e-9)


def attn_flops(B, H, Sq, Skv, D, causal=True):
    # QK^T + PV, 2 matmuls of 2*S*S*D MACs each; causal halves the work.
    f = 4.0 * B * H * Sq * Skv * D
    return f / 2 if causal else f


def mask_call(a):
    """``(keywords of a raw kernel call, name -> operations of one call)`` under
    ``--mask-type``: the mask's program and plan, and the benchmark's count of
    the pairs it admits at ``[B, Hq, S, D]``."""
    from benchmark.flops import flash_attention, flash_blockdiff, flash_window
    from mlx_cuda_distributed_pretraining_tpu.ops import masks as M

    shape = (a.batch, a.heads, a.seq, a.head_dim)
    kw = dict(mask_type=a.mask_type, canonical_mask=a.mask_type != "full",
              scale=a.head_dim ** -0.5)
    if a.mask_type == "block_diffusion":
        kw.update(mask_fn=M.block_diffusion(a.seq // 2, a.block_length),
                  window=a.block_length, prefix_len=a.seq // 2)
        count = {n: f(*shape, a.block_length) for n, f in flash_blockdiff.BY_KERNEL.items()}
    elif a.mask_type == "sliding_window":
        kw.update(mask_fn=M.sliding_window(a.window), window=a.window)
        count = {n: f(*shape, a.window) for n, f in flash_window.BY_KERNEL.items()}
    else:
        # the benchmark's count is the causal call's; a full mask runs twice that
        causal = a.mask_type == "causal"
        kw.update(mask_fn=M.causal() if causal else None)
        count = {n: f(*shape) * (1 if causal else 2)
                 for n, f in flash_attention.BY_KERNEL.items()}
    return kw, count


def raw_kernel_rows(a, dtype):
    """Rows of the raw kernels, forward or backward; ``path`` "auto" leaves
    the choice to ``flash_plan`` and any other value forces it."""
    from benchmark import peaks
    from mlx_cuda_distributed_pretraining_tpu.ops import flash_attention as fa

    B, S, Hq, Hkv, D = a.batch, a.seq, a.heads, a.kv_heads, a.head_dim
    peak = peaks.peak(jax.devices()[0].device_kind)["bf16_flops"]
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (B, Hq, S, D), dtype)
    k = jax.random.normal(ks[1], (B, Hkv, S, D), dtype)
    v = jax.random.normal(ks[2], (B, Hkv, S, D), dtype)
    mask_kw, count = mask_call(a)
    rest, kernels = (), {}
    if a.forward_only:
        kernels["flash_fwd"] = lambda qq, kk, vv, *_, **kw: fa.flash_fwd(qq, kk, vv, **kw)[0]
    if a.backward_only:
        # the residuals a backward call gets: o and lse of the forward, a
        # cotangent, and delta = rowsum(dO * O)
        g = jax.random.normal(ks[3], (B, Hq, S, D), dtype)
        o, lse = jax.jit(lambda q, k, v: fa.flash_fwd(q, k, v, **mask_kw))(q, k, v)
        delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)[:, :, None, :]
        rest = (g, lse, delta)
        # dK per query head has q's shape where Sq == Skv: chained like dQ
        backward = {"dq": fa.flash_bwd_dq,
                    "dkv": lambda *ops, **kw: fa.flash_bwd_dkv(*ops, **kw)[0]}
        kernels.update({f"flash_bwd_{n}": backward[n] for n in a.kernels.split(",")})
    for name, fn in kernels.items():
        fl = count[name]
        for path in a.paths.split(","):
            for blocks in a.blocks.split(","):
                # "auto": the path's own default blocks
                bq, bkv = (None, None) if blocks == "auto" else (int(x) for x in blocks.split("x"))
                kw = dict(mask_kw, block_q=bq, block_kv=bkv)
                if path != "auto":
                    kw["_path"] = path
                row = {"name": name, "mask_type": a.mask_type, "path": path,
                       "block_q": bq, "block_kv": bkv, "B": B, "Hq": Hq, "Hkv": Hkv,
                       "S": S, "D": D}
                if a.mask_type == "sliding_window":
                    row["window"] = a.window
                if a.mask_type == "block_diffusion":
                    row["block_length"] = a.block_length
                try:
                    t = timed_loop(lambda *ops: fn(*ops, **kw), q, k, v, *rest, n_hi=45)
                    row.update(ms=round(t * 1e3, 3), share_of_peak=round(fl / t / peak, 4))
                except Exception as e:  # noqa: BLE001 - a block the compiler refuses is a row too
                    row["error"] = f"{type(e).__name__}: {str(e)[:300]}"
                print(json.dumps(row), flush=True)


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--sweep", action="store_true", help="sweep block sizes")
    parser.add_argument("--forward-only", action="store_true",
                        help="raw flash_fwd rows at --batch x --seq")
    parser.add_argument("--backward-only", action="store_true",
                        help="raw flash_bwd_dq / flash_bwd_dkv rows at --batch x --seq")
    parser.add_argument("--kernels", default="dq,dkv",
                        help="comma list of dq|dkv for --backward-only")
    parser.add_argument("--dtype", default="bfloat16")
    parser.add_argument("--head-dim", type=int, default=64)
    parser.add_argument("--heads", type=int, default=16)
    parser.add_argument("--kv-heads", type=int, default=None,
                        help="KV heads (default: --heads)")
    parser.add_argument("--batch", type=int, default=4)
    parser.add_argument("--seq", type=int, default=4096)
    parser.add_argument("--mask-type", default="causal",
                        choices=("causal", "full", "sliding_window", "block_diffusion"))
    parser.add_argument("--window", type=int, default=2048,
                        help="the band of --mask-type sliding_window")
    parser.add_argument("--block-length", type=int, default=4,
                        help="B' of --mask-type block_diffusion, whose --seq is both copies' rows")
    parser.add_argument("--blocks", default="auto",
                        help="comma list of auto | block_q x block_kv for --forward-only "
                             "and --backward-only")
    parser.add_argument("--paths", default="auto",
                        help="comma list of auto|resident|streamed, likewise")
    a = parser.parse_args()
    a.kv_heads = a.kv_heads or a.heads

    from mlx_cuda_distributed_pretraining_tpu.ops import masks as M
    from mlx_cuda_distributed_pretraining_tpu.ops.attention import reference_attention
    from mlx_cuda_distributed_pretraining_tpu.ops.flash_attention import flash_attention

    dtype = jnp.dtype(a.dtype)
    H, Hkv, D = a.heads, a.kv_heads, a.head_dim
    dev = jax.devices()[0]
    print(json.dumps({"device": str(dev), "device_kind": dev.device_kind,
                      "dtype": str(dtype), "H": H, "Hkv": Hkv, "D": D}))

    if a.forward_only or a.backward_only:
        raw_kernel_rows(a, dtype)
        return

    def make_inputs(B, S, key=0):
        ks = jax.random.split(jax.random.PRNGKey(key), 3)
        return tuple(jax.random.normal(k, (B, S, h, D), dtype)
                     for k, h in zip(ks, (H, Hkv, Hkv)))

    def run_case(name, fn, q, k, v):
        B, S = q.shape[0], q.shape[1]

        def fwd_step(qq, kk, vv):
            return fn(qq, kk, vv)

        def bwd_step(qq, kk, vv):
            # grad wrt q has q's shape: chain it as the next query
            return jax.grad(lambda x: jnp.sum(fn(x, kk, vv).astype(jnp.float32)))(qq)

        t_f = timed_loop(fwd_step, q, k, v)
        t_b = timed_loop(bwd_step, q, k, v)
        fl = attn_flops(B, H, S, S, D)
        return {
            "name": name, "B": B, "S": S,
            "fwd_ms": round(t_f * 1e3, 3), "bwd_ms": round(t_b * 1e3, 3),
            "fwd_tflops": round(fl / t_f / 1e12, 2),
            # bwd step includes the fwd recompute + dQ/dK/dV (~3.5x fwd FLOPs)
            "bwd_tflops": round(3.5 * fl / t_b / 1e12, 2),
        }

    if a.sweep:
        for B, S in [(16, 2048), (8, 4096), (4, 8192)]:
            q, k, v = make_inputs(B, S)
            for bq in (128, 256, 512, 1024):
                for bkv in (256, 512, 1024, 2048, 4096):
                    if bkv > S or bq > S:
                        continue
                    r = run_case(
                        f"flash_bq{bq}_bkv{bkv}",
                        lambda q, k, v, bq=bq, bkv=bkv: flash_attention(
                            q, k, v, block_q=bq, block_kv=bkv),
                        q, k, v)
                    print(json.dumps(r), flush=True)
        return

    # tokens-per-batch held ~constant so memory stays bounded
    cases = [(32, 1024), (16, 2048), (8, 4096), (4, 8192), (2, 16384), (1, 32768)]
    for B, S in cases:
        q, k, v = make_inputs(B, S)
        r = run_case("flash", flash_attention, q, k, v)
        print(json.dumps(r), flush=True)
        if S <= 4096:  # XLA full-score attention OOMs/fails to compile beyond
            try:
                r = run_case("xla_fused", lambda q, k, v: reference_attention(
                    q, k, v, mask_mod=M.causal()), q, k, v)
                print(json.dumps(r), flush=True)
            except Exception as e:  # noqa: BLE001
                print(json.dumps({"name": "xla_fused", "B": B, "S": S,
                                  "error": str(e)[:160]}), flush=True)


if __name__ == "__main__":
    main()
