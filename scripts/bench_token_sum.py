#!/usr/bin/env python3
"""Time the expert layer's token-side sum alone on the chip: XLA's form through
``[T, K, D]``, the kernel that copies a tile's ranges (``ops/token_sum.py``), and the
same kernel fed one copy a held row.

    python scripts/bench_token_sum.py [--cases 16384x8x2048x67584x16of128:5000+17000+61000,...]

A case is ``tokens x top-k x width x buffer rows x held "of" routed experts`` and the
held selections a call sums (of ``tokens x top-k``). Defaults: a routed layer of
``trinity-mini-ep8.train-seq16k`` and ``sdar-30b-a3b-ep8.train-bd8k`` at the lightest,
a usual and the heaviest load, and a chunk of ``xing4_0-29b-a4b-ep8.train-1chip``.
For each it draws a router's choice with that many held selections, makes the
layer's own ``dispatch_plan`` and prints ms a call (a jitted loop of 10 calls less one
of 2, over 8, each the median of ``--reps`` runs that end in ``block_until_ready``: a
single call is mostly the host's round trip) of

- ``xla``: ``models/moe.py::_sum_held(_take_rows(...))``, a row for every selection;
- ``ranges``: ``token_sum`` at each ``--tiles`` size, with what it copies (pieces of 16
  rows a call, and their bytes);
- ``rows``: the same kernel with a piece for every held selection (the 16 rows its row
  lies in: Mosaic takes no slice of a tiled buffer in HBM narrower than a register, so
  one copy a row moves the row's whole tile), in tiles of 128 tokens, its list of held
  selections made by a sort ahead of the call;

each with a float32 scale (the combine: three products a chunk against bfloat16 rows)
and with the 0/1 scale of the dispatch's backward (one), the largest gap of either
kernel against ``xla`` over the largest value and the share of elements that differ at
all (the float32 sum runs by expert where XLA's runs by ``k``, so a bfloat16 result can
round the other way), and how much of ``ranges`` is the XLA operations ahead of the
kernel that find a tile's ranges (about: ``pos`` alone is timed, summed to a column:
in a train step they fuse into a few hundredths of a millisecond). The package holds
the ``ranges`` form alone; ``rows`` lives here, for this comparison. Last, the gate
weights' gradient of a combine, ``[T, K]`` dots of a selection's row with its token's
cotangent: XLA's form (the dots taken on the buffer's side, then a gather of scalars)
against the kernel ``token_dot`` over the same tiles.

On a v5e (my chip run, PR 49; ms a call, bfloat16 rows):

    shape, held selections            xla     ranges 128   ranges 256   rows 128   dgate_w: xla | token_dot 128
    16,384 x 8 of 2,048, 5,045        6.36    0.92 (0.69)  0.94 (0.65)  1.55        2.26 | 0.32
    16,384 x 8 of 2,048, 17,035       6.52    1.08 (0.78)  1.14 (0.76)  3.86        2.25 | 0.43
    16,384 x 8 of 2,048, 61,362       6.16    1.60 (1.13)  2.07 (1.20) 12.36        2.25 | 0.77
    2,048 x 4 of 3,584, 1,047         0.85    0.108 (0.064) 0.093 (0.062) 0.36      0.151 | 0.059
    2,048 x 4 of 3,584, 2,061         0.84    0.095 (0.078) 0.141 (0.064) 0.67      0.162 | 0.069

(in brackets the 0/1 scale). The kernels' results differ from ``xla``'s on 0.0001-0.0035%
of the elements with the float32 scale (by a rounding of the bfloat16 cast: gap
0.0013-0.0022 of the largest value) and nowhere with the 0/1 scale; ``token_dot`` by
2e-7. ``ranges`` is faster than ``rows`` at every load and both shapes, and tiles of 128
tokens are as fast as 256 or faster but for the 0/1 scale at light loads: the package
takes 128.
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
import numpy as np

from mlx_cuda_distributed_pretraining_tpu.models import moe
from mlx_cuda_distributed_pretraining_tpu.ops import token_sum as ts

DEFAULT_CASES = ("16384x8x2048x67584x16of128:5000+17000+61000,"
                 "2048x4x3584x5120x8of64:1024+2048")
ROW_TILE = 128   # tokens a tile of the one-copy-a-row form


def timed(fn, args, reps):
    jax.block_until_ready(fn(*args))
    out = []
    for _ in range(reps):
        t = time.perf_counter()
        jax.block_until_ready(fn(*args))
        out.append(1e3 * (time.perf_counter() - t))
    return statistics.median(out)


def ms_a_call(form, buf, plan, scale, reps):
    """``form(buf, plan, scale)`` in a jitted loop, so that no call is the host's."""
    def loop(n, buf, plan, scale):
        def again(i, acc):   # another scale a call, so no call is hoisted
            return acc + form(buf, plan, scale * (1 + i).astype(scale.dtype)).astype(jnp.float32)
        out = jax.eval_shape(form, buf, plan, scale)
        return jax.lax.fori_loop(0, n, again, jnp.zeros(out.shape, jnp.float32))
    few, many = (timed(jax.jit(functools.partial(loop, n)), (buf, plan, scale), reps) for n in (2, 10))
    return (many - few) / 8


def router_choice(rng, T, K, held, routed, load):
    """``gate_idx [T, K]``: distinct experts a token, about ``load`` of them held."""
    n_held = np.minimum(rng.binomial(K, load / (T * K), size=T), min(K, held))
    idx = np.empty((T, K), np.int32)
    for t in range(T):
        idx[t, :n_held[t]] = rng.permutation(held)[:n_held[t]]
        idx[t, n_held[t]:] = held + rng.permutation(routed - held)[:K - n_held[t]]
        rng.shuffle(idx[t])
    return idx


def xla_form(buf, plan, scale):
    return moe._sum_held(moe._take_rows(buf, plan.sel_row), jnp.where(plan.sel_held, scale, 0), buf.dtype)


def ranges_form(bt, exact, buf, plan, scale):
    return ts.token_sum(buf, plan.sel_row, plan.sel_held, scale, plan.group_sizes, bt, exact_scale=exact)


def xla_dot(buf, plan, dout):
    """``dgate_w`` in XLA's form: the dots a buffer row at a time, then ``[T, K]`` of them."""
    dout_row = moe._take_rows(dout, plan.row_tok).astype(jnp.float32)
    dw_row = jnp.sum(buf.astype(jnp.float32) * dout_row, axis=-1)
    return jnp.where(plan.sel_held, moe._take_rows(dw_row, plan.sel_row), 0)


def kernel_dot(bt, buf, plan, dout):
    return ts.token_dot(buf, plan.sel_row, plan.sel_held, dout, plan.group_sizes, bt)


def _row_pieces(sel_row, sel_held, group_sizes, bt, cap):
    """``ts._tile_pieces`` for one copy a held row: a tile's held selections in
    order, each staged as the piece its row lies in."""
    del group_sizes
    T, K = sel_row.shape
    held = sel_held.reshape(T // bt, bt * K)
    rank = jnp.cumsum(held, axis=1, dtype=jnp.int32) - held
    pos = jnp.where(sel_held, rank.reshape(T, K) * ts._PIECE + sel_row % ts._PIECE, -1).T
    order = jnp.argsort(~held, axis=1, stable=True)
    src = jnp.take_along_axis((sel_row // ts._PIECE).reshape(held.shape), order, axis=1)[:, :cap]
    return pos, src, jnp.sum(held, axis=1, dtype=jnp.int32)


def rows_form(exact, buf, plan, scale):
    ranges = ts._tile_pieces, ts._tile_pieces_cap
    ts._tile_pieces, ts._tile_pieces_cap = _row_pieces, lambda bt, K, E: bt * K
    try:
        return ranges_form(ROW_TILE, exact, buf, plan, scale)
    finally:
        ts._tile_pieces, ts._tile_pieces_cap = ranges


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--cases", default=DEFAULT_CASES)
    p.add_argument("--tiles", default="128,256", help="tokens a tile of the ranges form")
    p.add_argument("--dtype", default="bfloat16")
    p.add_argument("--reps", type=int, default=5)
    p.add_argument("--skip-rows", action="store_true", help="leave the one-copy-a-row form out")
    a = p.parse_args()
    dtype = jnp.dtype(a.dtype)
    print("device:", jax.devices()[0].device_kind, flush=True)
    rng = np.random.default_rng(0)
    for case in a.cases.split(","):
        shape, _, loads = case.partition(":")
        T, K, D, R, experts = shape.split("x")
        T, K, D, R = int(T), int(K), int(D), int(R)
        held, routed = (int(x) for x in experts.split("of"))
        buf = jax.random.normal(jax.random.PRNGKey(1), (R, D), jnp.float32).astype(dtype)
        for load in (int(x) for x in loads.split("+")):
            gate_idx = jnp.asarray(router_choice(rng, T, K, held, routed, load))
            plan = jax.jit(functools.partial(moe.dispatch_plan, num_experts=held, block_t=128, rows=R))(gate_idx)
            if int(jnp.sum(plan.group_sizes)) > R:
                print(f"{case}: a load of {load} does not fit {R} rows; skipped", flush=True)
                continue
            gate_w = jax.random.uniform(jax.random.PRNGKey(2), (T, K), jnp.float32, 0.05, 1.0)
            print(f"{T} x {K} selections of {D} from {R} rows, {held} of {routed} experts held, "
                  f"{int(plan.sel_held.sum())} selections held:", flush=True)
            for name, scale, exact in (("float32 scale", gate_w, False), ("0/1 scale", jnp.ones_like(gate_w), True)):
                want = jax.jit(xla_form)(buf, plan, scale).astype(jnp.float32)
                line = [f"xla {ms_a_call(xla_form, buf, plan, scale, a.reps):.3f} ms"]
                forms = [(f"ranges bt={bt}", functools.partial(ranges_form, int(bt), exact), int(bt))
                         for bt in a.tiles.split(",") if T % int(bt) == 0]
                if not a.skip_rows:
                    forms.append((f"rows bt={ROW_TILE}", functools.partial(rows_form, exact), 0))
                for label, form, bt in forms:
                    try:
                        got = jax.jit(form)(buf, plan, scale).astype(jnp.float32)
                        gap = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
                        differ = float(jnp.mean(got != want))
                        copied = ""
                        if bt:
                            where = functools.partial(ts._tile_pieces, bt=bt, cap=ts._tile_pieces_cap(bt, K, held))
                            pieces = int(jnp.sum(where(plan.sel_row, plan.sel_held, plan.group_sizes)[2]))
                            ahead = ms_a_call(lambda buf, plan, scale: jnp.sum(where(
                                plan.sel_row, plan.sel_held & (scale > 0), plan.group_sizes)[0], axis=0
                                )[:, None].astype(buf.dtype) * buf[:1], buf, plan, scale, a.reps)
                            copied = (f", {pieces} pieces = {pieces * ts._PIECE * D * dtype.itemsize / 1e6:.0f} MB, "
                                      f"{ahead:.3f} ms of it ahead of the kernel")
                        line.append(f"{label} {ms_a_call(form, buf, plan, scale, a.reps):.3f} ms "
                                    f"(gap {gap:.2g} on {100 * differ:.4f}% of the elements{copied})")
                    except Exception as e:  # noqa: BLE001 - a form the compiler refuses is a finding
                        line.append(f"{label} refused: {type(e).__name__}: {str(e)[:300]}")
                print(f"  {name}: " + "; ".join(line), flush=True)
            dout = jax.random.normal(jax.random.PRNGKey(3), (T, D), jnp.float32).astype(dtype)
            want = jax.jit(xla_dot)(buf, plan, dout)
            line = [f"xla, on the buffer's side {ms_a_call(xla_dot, buf, plan, dout, a.reps):.3f} ms"]
            for bt in (int(bt) for bt in a.tiles.split(",") if T % int(bt) == 0):
                form = functools.partial(kernel_dot, bt)
                gap = float(jnp.max(jnp.abs(jax.jit(form)(buf, plan, dout) - want)) / jnp.max(jnp.abs(want)))
                line.append(f"token_dot bt={bt} {ms_a_call(form, buf, plan, dout, a.reps):.3f} ms (gap {gap:.2g})")
            print("  dgate_w, a selection's row against its token's cotangent: " + "; ".join(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
