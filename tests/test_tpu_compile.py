"""The chip's compiler, without the chip.

libtpu compiles for a TPU that is described and not attached
(``jax.experimental.topologies``), so the kernels of the main path are
compiled here at their real widths for one v5e device: what the chip's
compiler refuses (a slice off the tiling, too much VMEM, a program over
16 GB) fails in tier-1 instead of on a chip call. Nothing executes, so
these say nothing about results or times — ``chip_smoke.py`` does.

``_interpret()`` in ops/ asks ``jax.default_backend()``, which is the CPU
here; the tests steer it (monkeypatch), the program has no option for it.
"""

import collections
import dataclasses
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs under /tmp

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from mlx_cuda_distributed_pretraining_tpu.models import llama
from mlx_cuda_distributed_pretraining_tpu.ops import flash_attention as fa
from mlx_cuda_distributed_pretraining_tpu.ops import fused_ce
from mlx_cuda_distributed_pretraining_tpu.ops.attention import core_counts
from mlx_cuda_distributed_pretraining_tpu.ops import grouped_matmul as gm
from mlx_cuda_distributed_pretraining_tpu.ops import token_sum as ts

HBM_BYTES = 16 * 10**9  # one v5e chip (Google Cloud TPU v5e documentation)


@pytest.fixture(scope="module")
def v5e_devices():
    """The four described devices of a v5e:2x2 host; skip where libtpu
    cannot describe them."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe = no compiler here
        pytest.skip(f"cannot describe a v5e:2x2 topology: {type(e).__name__}: {e}")
    # A compile for a described device is written to the persistent cache
    # but cannot be read back without a chip: keep the cache off here.
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield list(topo.devices)
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.fixture
def v5e(v5e_devices):
    """One described v5e device."""
    return SingleDeviceSharding(v5e_devices[0])


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Take the Mosaic branch of the kernels, as on a TPU backend."""
    monkeypatch.setattr(fa, "_interpret", lambda: False)
    monkeypatch.setattr(gm, "_interpret", lambda: False)
    monkeypatch.setattr(fused_ce, "_interpret", lambda: False)
    monkeypatch.setattr(ts, "_interpret", lambda: False)
    # Trace fresh: a core cached by an interpret-mode test would be reused.
    fa._cached_core.cache_clear()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


FLASH_CASES = {
    # the 1B recipe: 16 heads of 128, context 2048
    "causal_16x128": dict(Hq=16, Hkv=16, D=128, mask_type="causal", window_size=512),
    # grouped queries at the narrower head the ladder's 100M-650M configs use
    "gqa_12over4x64": dict(Hq=12, Hkv=4, D=64, mask_type="causal", window_size=512),
    "sliding_window_512": dict(Hq=16, Hkv=16, D=128,
                               mask_type="sliding_window", window_size=512),
}


def _flash_args(case, dev):
    B, S = 2, 2048
    q = _sds((B, S, case["Hq"], case["D"]), jnp.bfloat16, dev)
    kv = _sds((B, S, case["Hkv"], case["D"]), jnp.bfloat16, dev)
    return q, kv, kv


@pytest.mark.parametrize("name", sorted(FLASH_CASES))
def test_flash_forward_compiles_for_v5e(name, v5e, compiled_kernels):
    case = FLASH_CASES[name]

    def fwd(q, k, v):
        return fa.flash_attention(q, k, v, mask_type=case["mask_type"],
                                  window_size=case["window_size"])

    hlo = jax.jit(fwd).lower(*_flash_args(case, v5e)).compile().as_text()
    assert hlo.count("tpu_custom_call") >= 1


@pytest.mark.parametrize("name", sorted(FLASH_CASES))
def test_flash_backward_compiles_for_v5e(name, v5e, compiled_kernels):
    case = FLASH_CASES[name]

    def loss(q, k, v):
        o = fa.flash_attention(q, k, v, mask_type=case["mask_type"],
                               window_size=case["window_size"])
        return o.astype(jnp.float32).sum()

    hlo = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        *_flash_args(case, v5e)).compile().as_text()
    # forward (for the residuals), dQ and dK/dV
    assert hlo.count("tpu_custom_call") >= 3


# mistral-7b-v0_3-l4.train-1chip, the benchmark's cell: 4 x 4,096, GQA 32/8 of 128
CELL = dict(B=4, S=4096, Hq=32, Hkv=8, D=128)


def _largest_resident_seq(D=128, dtype=jnp.bfloat16):
    return max(s for s in (2 ** n for n in range(10, 18))
               if fa.flash_plan(s, s, D, dtype).path == "resident")


def _path_case(name, v5e):
    """``(q, kv, want)`` of a size that decides the plan: the benchmark cell's
    shape, the longest sequence the resident budget admits, and twice that."""
    edge = _largest_resident_seq()
    B, S = {"cell": (CELL["B"], CELL["S"]), "largest_resident": (1, edge),
            "first_streamed": (1, 2 * edge)}[name]
    q = _sds((B, S, CELL["Hq"], CELL["D"]), jnp.bfloat16, v5e)
    kv = _sds((B, S, CELL["Hkv"], CELL["D"]), jnp.bfloat16, v5e)
    return q, kv, "streamed" if name == "first_streamed" else "resident"


@pytest.mark.parametrize("name", ["cell", "largest_resident", "first_streamed"])
def test_flash_forward_paths_compile_for_v5e(name, v5e, compiled_kernels):
    """The forward ``flash_plan`` picks, at the sizes that decide it: the
    benchmark cell's shape (K/V resident in VMEM), the longest sequence the
    resident budget admits (a budget Mosaic's scoped VMEM limit refuses
    fails here), and twice that, the first one streamed through the grid."""
    q, kv, want = _path_case(name, v5e)
    assert fa.flash_plan(q.shape[1], q.shape[1], CELL["D"], jnp.bfloat16).path == want
    before = fa.plan_counts()
    hlo = jax.jit(fa.flash_attention).lower(q, kv, kv).compile().as_text()
    assert hlo.count("tpu_custom_call") >= 1
    assert fa.plan_counts()[want] == before[want] + 1


@pytest.mark.parametrize("name", ["cell", "largest_resident", "first_streamed"])
def test_flash_backward_paths_compile_for_v5e(name, v5e, compiled_kernels):
    """The same three sizes through the backward: dQ with K/V resident and
    dK/dV with Q/dO resident compile at the cell's shape and at the longest
    length their budget admits (four float32 chunk arrays and, in dK/dV, the
    lse and delta rows beside the held operands: what Mosaic's scoped VMEM
    limit refuses fails here), the streamed pair past it, and the tally says
    which ran."""
    q, kv, want = _path_case(name, v5e)
    S = q.shape[1]
    for kernel in ("flash_bwd_dq", "flash_bwd_dkv"):
        assert fa.flash_plan(S, S, CELL["D"], jnp.bfloat16, kernel=kernel).path == want

    def loss(q, k, v):
        return fa.flash_attention(q, k, v).astype(jnp.float32).sum()

    before = fa.plan_counts()
    hlo = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, kv, kv).compile().as_text()
    assert hlo.count("tpu_custom_call") >= 3
    after = fa.plan_counts()
    other = "resident" if want == "streamed" else "streamed"
    for kernel in ("bwd_dq", "bwd_dkv"):
        assert after[f"{kernel}_{want}"] == before[f"{kernel}_{want}"] + 1
        assert after[f"{kernel}_{other}"] == before[f"{kernel}_{other}"]


@pytest.mark.parametrize("shape", ["recipe_1b", "cell"])
def test_flash_under_fsdp_mesh_compiles_for_v5e(shape, v5e_devices, compiled_kernels):
    """GSPMD cannot partition a Mosaic kernel, so under a mesh the call has
    to go through a shard_map (ops/flash_attention.py _mesh_partition); left
    to the partitioner this lowering raises NotImplementedError, and with it
    every sharded training config that uses flash attention. Both shapes run
    the resident forward: each chip holds the K/V of its own rows."""
    from mlx_cuda_distributed_pretraining_tpu.parallel.context import use_mesh

    mesh = Mesh(np.array(v5e_devices), ("fsdp",))
    rows = NamedSharding(mesh, P("fsdp"))
    case = FLASH_CASES["causal_16x128"] if shape == "recipe_1b" else CELL
    S = case.get("S", 2048)
    qkv = [_sds((8, S, h, case["D"]), jnp.bfloat16, rows)
           for h in (case["Hq"], case["Hkv"], case["Hkv"])]

    def loss(q, k, v):
        return fa.flash_attention(q, k, v).astype(jnp.float32).sum()

    before = fa.plan_counts()
    with use_mesh(mesh):
        hlo = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(*qkv).compile().as_text()
    assert hlo.count("tpu_custom_call") >= 3
    # each chip attends over its own two rows: nothing to exchange
    assert "all-gather" not in hlo and "all-reduce" not in hlo
    after = fa.plan_counts()
    assert after["resident"] > before["resident"] and after["streamed"] == before["streamed"]
    for kernel in ("bwd_dq", "bwd_dkv"):
        assert after[f"{kernel}_resident"] == before[f"{kernel}_resident"] + 1
        assert after[f"{kernel}_streamed"] == before[f"{kernel}_streamed"]


# OLMoE's expert shapes (ROADMAP R1): 64 experts of width 1024 on h2048,
# one 8192-token dispatch.
GMM = dict(T=8192, K=2048, N=1024, E=64)


def _gmm_args(dev):
    return (_sds((GMM["T"], GMM["K"]), jnp.bfloat16, dev),
            _sds((GMM["E"], GMM["K"], GMM["N"]), jnp.bfloat16, dev),
            _sds((GMM["E"],), jnp.int32, dev))


def test_gmm_compiles_for_v5e(v5e, compiled_kernels):
    def fwd(x, w, sizes):
        return gm.gmm(x, w, sizes, backend="pallas")

    hlo = jax.jit(fwd).lower(*_gmm_args(v5e)).compile().as_text()
    assert hlo.count("tpu_custom_call") >= 1


def test_tgmm_compiles_for_v5e(v5e, compiled_kernels):
    def loss(x, w, sizes):
        return gm.gmm(x, w, sizes, backend="pallas").astype(jnp.float32).sum()

    hlo = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        *_gmm_args(v5e)).compile().as_text()
    # dX (a gmm against transposed weights) and dW (tgmm); the forward's
    # output feeds neither, so the compiler drops it
    assert hlo.count("tpu_custom_call") >= 2


# The benchmark's two expert-parallel cells, one chunk's dispatch: rows of the
# buffer (the whole dropless one, and the small one a chunk takes while its rows
# fit: ``moe.chunk_buffer_rows``), held experts, and an expert's [K, N] (PERF.md
# section 4). Each loop cuts its chunks by what its own buffer has rows for
# (``moe.held_chunks``), so the small loop takes half the trips and its buffer
# over a chunk twice as long has the rows the whole loop's has.
GMM_CELLS = {"xing4_0-29b-a4b-ep8": (5120, 8, 3584, 1024),
             "trinity-mini-ep8": (67584, 16, 2048, 1024),
             "xing4_0-29b-a4b-ep8-small": (5120, 8, 3584, 1024),
             "trinity-mini-ep8-small": (67584, 16, 2048, 1024)}


def test_the_cells_buffer_rows_are_the_programs():
    """``GMM_CELLS``' rows are what ``models/moe.py`` gives a chunk of each cell in
    each of its two loops (tokens a step, top-k, held of routed experts, the
    model's ``chunk_rows``: PERF.md section 4): 8,192 and 131,072 selections a
    chunk of the small loop, 4,096 and 65,536 a chunk of the whole one."""
    from mlx_cuda_distributed_pretraining_tpu.models import moe

    for cell, tokens, top_k, held, routed, chunk_rows, selections in (
            ("xing4_0-29b-a4b-ep8", 8192, 4, 8, 64, moe.HELD_CHUNK_ROWS, (8192, 4096)),
            ("trinity-mini-ep8", 16384, 8, 16, 128, 65536, (131072, 65536))):
        trips = moe.held_chunks(tokens, top_k, held, routed, chunk_rows)
        assert tuple(tokens * top_k // n for n in trips) == selections
        small, whole = (moe.chunk_buffer_rows(s, held, routed, gm.pick_block_t(s, held))[size]
                        for size, s in enumerate(selections))
        assert (small, whole) == (GMM_CELLS[cell + "-small"][0], GMM_CELLS[cell][0])


@pytest.mark.parametrize("orientation", ["up", "down"])
@pytest.mark.parametrize("cell", list(GMM_CELLS))
def test_gmm_resident_plan_compiles_for_v5e_at_the_cells_shapes(cell, orientation, v5e,
                                                                compiled_kernels):
    """``gmm`` and its gradient (dX, ``tgmm``) at the cells' own shapes: the
    plan's column blocks (the weights' whole width in ``gmm``; half of it
    or more, with float32 sums, in ``tgmm``), and Mosaic accepts the VMEM
    they ask for."""
    T, E, K, N = GMM_CELLS[cell]
    if orientation == "down":
        K, N = N, K
    args = (_sds((T, K), jnp.bfloat16, v5e), _sds((E, K, N), jnp.bfloat16, v5e),
            _sds((E,), jnp.int32, v5e))

    def fwd(x, w, sizes):
        return gm.gmm(x, w, sizes, backend="pallas")

    def loss(x, w, sizes):
        return fwd(x, w, sizes).astype(jnp.float32).sum()

    before = gm.plan_counts()
    hlo = jax.jit(fwd).lower(*args).compile().as_text()
    assert hlo.count("tpu_custom_call") == 1
    hlo = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(*args).compile().as_text()
    assert hlo.count("tpu_custom_call") == 2
    traced = {k: n - before.get(k, 0) for k, n in gm.plan_counts().items() if n - before.get(k, 0)}
    dw_bn = gm.gmm_plan(K, N, gm.DEFAULT_BLOCK_T, jnp.bfloat16, "tgmm")
    want = collections.Counter({"gmm_resident": 3, "tgmm_resident": 1, f"tgmm_bn{dw_bn}": 1})
    want.update({f"gmm_bn{N}": 2, f"gmm_bn{K}": 1})    # the forward twice, dX over the transposed weights
    assert traced == dict(want) and dw_bn >= 512


# A routed layer's token-side sum in the two expert-parallel shapes (tokens of a
# chunk of the small loop, top-k, width, rows and groups of its buffer), and a
# decode step's four tokens through every expert of a small model.
TOKEN_SUM_CELLS = {"trinity-mini-ep8": (16384, 8, 2048, 67584, 16, {"bfloat16": 128, "float32": 128}),
                   "xing4_0-29b-a4b-ep8": (2048, 4, 3584, 5120, 8, {"bfloat16": 128, "float32": 0}),
                   # rows 4,096 wide (PR 56): a round of 16 pieces, so that a tile of 128 tokens fits; the
                   # plan's tile of 64 tokens before it was no whole lane register, which Mosaic refuses
                   "solar-open2-250b-ep40": (16384, 8, 4096, 14208, 8, {"bfloat16": 128, "float32": 0})}


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("cell", list(TOKEN_SUM_CELLS))
def test_token_sum_compiles_for_v5e_at_the_cells_shapes(cell, dtype, v5e, compiled_kernels):
    """The kernels at the cells' own shapes (``token_sum`` with the combine's
    float32 scale and with the 0/1 scale of the dispatch's backward, and
    ``token_dot``): Mosaic takes the copies of 16 rows
    out of the buffer as it lies in HBM and the VMEM the tile asks for under the
    default scoped limit (XLA compiles a fusion into the call under that one);
    and what the plan gives each shape: tiles of 128 tokens, and XLA's form for
    float32 rows of 3,584 (two staging slots of them leave no room for a tile);
    a decode step's few tokens as one tile; XLA's form off the
    backend, for a buffer tiled by 8 and for rows too wide for any tile."""
    T, K, D, rows, E, tiles = TOKEN_SUM_CELLS[cell]
    tile, dtype = tiles[dtype], jnp.dtype(dtype)
    assert ts.token_sum_plan(T, K, D, rows, dtype, "pallas") == tile
    assert ts.token_sum_plan(4, 2, 512, 64, jnp.float32, "pallas") == 16     # a decode step: one tile
    assert ts.token_sum_plan(4, 2, 512, 72, jnp.float32, "pallas") == 0      # its buffer tiled by 8
    assert ts.token_sum_plan(T, K, D, rows, dtype, "blocked") == 0
    assert ts.token_sum_plan(T, K, 64 * D, rows, dtype, "pallas") == 0
    args = (_sds((rows, D), dtype, v5e), _sds((T, K), jnp.int32, v5e), _sds((T, K), jnp.bool_, v5e),
            _sds((T, K), jnp.float32, v5e), _sds((E,), jnp.int32, v5e))
    fns = [lambda buf, sel_row, sel_held, scale, sizes, exact=exact: ts.token_sum(
        buf, sel_row, sel_held, scale, sizes, tile, exact_scale=exact) for exact in (False, True)]
    # and ``dgate_w``'s kernel, a selection's row against its token's cotangent
    fns.append(lambda buf, sel_row, sel_held, scale, sizes: ts.token_dot(
        buf, sel_row, sel_held, jnp.zeros((T, D), buf.dtype) + scale[:, :1].astype(buf.dtype),
        sizes, tile))
    for fn in fns if tile else ():
        hlo = jax.jit(fn).lower(*args).compile().as_text()
        assert hlo.count("tpu_custom_call") == 1 and f"[{T},{K},{D}]" not in hlo


def test_paged_decode_step_fits_one_v5e(v5e, monkeypatch):
    """The batch engine's decode step at the 1B widths, 8 rows over a full
    2048-token attend bucket, fp32 weights and KV as the server holds them.
    No Pallas kernel is in it today (it attends through reference_attention,
    ROADMAP S2), so this only asserts that the chip's compiler takes the
    program and that it fits the chip's memory."""
    from mlx_cuda_distributed_pretraining_tpu.serve import batch_step

    # Declare the donation the step has on an accelerator (ops/donation.py):
    # without it the KV arena would be counted twice.
    monkeypatch.setenv("GRAFTAUDIT_FORCE_DONATE", "1")
    args = llama.LlamaArgs(
        vocab_size=259, hidden_size=2048, intermediate_size=5632, num_layers=16,
        num_heads=16, num_kv_heads=16, head_dim=128, max_position_embeddings=2048)
    rows, block, max_len = 8, 32, 2048
    width = max_len // block
    on_dev = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda x: _sds(x.shape, x.dtype, v5e), t)
    params = on_dev(jax.eval_shape(
        lambda: llama.init_params(jax.random.PRNGKey(0), args)))
    cache = on_dev(jax.eval_shape(
        lambda: llama.init_paged_cache(args, rows * width + 1, block,
                                       dtype=jnp.float32)))
    step = batch_step.paged_decode_step(args, 0, max_len, width, block)
    compiled = step.lower(
        params, cache,
        _sds((rows, 1), jnp.int32, v5e), _sds((rows,), jnp.int32, v5e),
        _sds((rows, width), jnp.int32, v5e), _sds((rows,), jnp.float32, v5e),
        _sds((rows, 2), jnp.uint32, v5e)).compile()
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert ma.alias_size_in_bytes > 0, "the KV arena is not donated"
    assert total < HBM_BYTES, f"paged decode step needs {total / 1e9:.2f} GB"


def test_scoped_train_step_compiles_for_v5e(v5e, compiled_kernels, monkeypatch):
    """The train step of the benchmark's cell (Mistral-7B widths, four scanned
    layers, full remat, flash, fused CE, Adafactor with clipping, 4 x 4,096),
    as the chip's compiler leaves it: every Mosaic call is one of the three
    named flash kernels or the head's ``ce_softmax_grad`` and every matmul
    sits under a scope of the vocabulary, so a trace of the chip can be
    reduced by scope (README "Reading a profile"); the head is three matmuls
    and that one kernel a chunk walk, none of them recomputed
    (ops/fused_ce.py); and the step fits the chip as the benchmark's
    ``step_hbm_gib`` counts it."""
    import re
    from functools import partial

    from mlx_cuda_distributed_pretraining_tpu.optim.adafactor import adafactor
    from mlx_cuda_distributed_pretraining_tpu.train.train_step import (
        init_train_state, make_train_step)

    vocabulary = {
        "embed", "layer", "norm", "attn_qkv", "attn_core", "attn_out", "ffn",
        "final_norm", "lm_head_ce", "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv",
        "grad_accum", "grad_clip", "optimizer"}
    # Declare the donation the step has on an accelerator (ops/donation.py):
    # without it the state would be counted twice.
    monkeypatch.setenv("GRAFTAUDIT_FORCE_DONATE", "1")
    args = llama.LlamaArgs(
        vocab_size=32768, hidden_size=4096, intermediate_size=14336, num_layers=4,
        num_heads=32, num_kv_heads=8, head_dim=128, max_position_embeddings=32768,
        rope_theta=1e6, tie_word_embeddings=False, attention_type="flash")
    loss = partial(llama.loss_fn, args=args, compute_dtype=jnp.bfloat16,
                   remat="full", scan_layers=True)
    opt = adafactor(lambda count: 1e-3, grad_clip=1.0)
    step, _ = make_train_step(lambda p, b: loss(p, b), opt)
    on_dev = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda x: _sds(x.shape, x.dtype, v5e), t)
    state = on_dev(jax.eval_shape(
        lambda: init_train_state(llama.init_params(jax.random.PRNGKey(0), args), opt)))
    batch = {k: _sds((4, 4096), jnp.int32, v5e) for k in ("inputs", "targets", "mask")}
    ce_before = fused_ce.plan_counts()
    compiled = step.lower(state, batch).compile()
    ce_after = fused_ce.plan_counts()
    hlo = compiled.as_text()

    def innermost(op_name):
        return next((t for t in reversed(re.split(r"[/()]", op_name)) if t in vocabulary),
                    None)

    kernels, matmuls = [], []
    for line in hlo.split("\n"):
        m = re.search(r'op_name="([^"]+)"', line)
        if m and "tpu_custom_call" in line:
            kernels.append(m.group(1))
        elif m and " convolution(" in line:
            matmuls.append(m.group(1))
    # forward, its recomputation, dQ, dK/dV: four calls, three names; and the
    # kernel between the head's matmuls, in the walk's loop
    assert sorted(innermost(k) for k in kernels) == [
        "flash_bwd_dkv", "flash_bwd_dq", "flash_fwd", "flash_fwd", "lm_head_ce"], kernels
    assert sum("rematted_computation" in k for k in kernels) == 1
    assert all(re.search(r"%(flash_(fwd|bwd_dq|bwd_dkv)|ce_softmax_grad)[.\d]* = ", line)
               for line in hlo.split("\n") if "tpu_custom_call" in line and " = " in line
               and "custom-call(" in line), "XLA names the instruction after the kernel"
    assert len(matmuls) >= 20
    assert {innermost(m) for m in matmuls} == {"attn_qkv", "attn_out", "ffn", "lm_head_ce"}, \
        sorted({m for m in matmuls if innermost(m) is None})
    # logits, dX and dW of a chunk, all in the forward walk's loop
    head = [m for m in matmuls if innermost(m) == "lm_head_ce"]
    assert len(head) == 3 and not any("rematted_computation" in m for m in head), head
    assert {k: ce_after[k] - ce_before[k] for k in ce_after} == {
        "grad_in_forward": 1, "forward_only": 0, "softmax_grad_kernel": 1, "softmax_grad_xla": 0}
    ma = compiled.memory_analysis()
    assert ma.alias_size_in_bytes > 0, "the state is not donated"
    step_hbm_gib = (ma.argument_size_in_bytes + ma.temp_size_in_bytes) / 2**30
    assert step_hbm_gib < 13.5, f"the cell's step needs {step_hbm_gib:.3f} GiB"


CE_CELLS = {  # a chunk's rows, the vocabulary, logit_scale, z_weight
    "phi4-mini-flash-l6": (2048, 200064, None, 0.0),
    "mistral-7b-v0_3-l4": (2048, 32768, None, 0.0),
    "xing4_0-29b-a4b-ep8": (2048, 16384, None, 0.0),
    "scaled_with_z_loss": (2048, 32768, 0.5, 1e-4),
}


@pytest.mark.parametrize("cell", list(CE_CELLS))
def test_ce_softmax_grad_compiles_for_v5e_at_the_cells_vocabularies(cell, v5e, compiled_kernels):
    """The kernel between the head's matmuls (ops/fused_ce.py) at a chunk of
    each cell that takes it: whole rows of the vocabulary in VMEM under the
    limit the call asks for (16 rows of 200,064 are 12.8 MB of logits)."""
    rows, vocab, logit_scale, z_weight = CE_CELLS[cell]
    block = fused_ce.softmax_grad_rows(rows, vocab, jnp.bfloat16)
    assert block and fused_ce._block_vmem_bytes(block, vocab, 2) <= fused_ce._VMEM_BUDGET
    compiled = jax.jit(lambda x, t, m: fused_ce._softmax_grad_kernel(
        x, t, m, logit_scale, z_weight, jnp.bfloat16)).lower(
        _sds((rows, vocab), jnp.float32, v5e), _sds((rows,), jnp.int32, v5e),
        _sds((rows,), jnp.float32, v5e)).compile()
    assert "ce_softmax_grad" in compiled.as_text()


def test_head_under_fsdp_mesh_compiles_for_v5e_with_xlas_chain(v5e_devices, compiled_kernels):
    """GSPMD cannot partition a Mosaic kernel: a head whose rows a mesh shards
    (fsdp = 4, 8 x 2,048 rows at Mistral's vocabulary) has to compile for the
    chip, which it does because the walk takes XLA's chain there
    (ops/fused_ce.py ``_left_to_gspmd``)."""
    from mlx_cuda_distributed_pretraining_tpu.parallel.context import use_mesh

    mesh = Mesh(np.array(v5e_devices), ("fsdp",))
    rows, whole = NamedSharding(mesh, P("fsdp")), NamedSharding(mesh, P())
    operands = (_sds((8, 2048, 1024), jnp.bfloat16, rows), _sds((32768, 1024), jnp.bfloat16, whole),
                _sds((8, 2048), jnp.int32, rows), _sds((8, 2048), jnp.float32, rows))
    before = fused_ce.plan_counts()
    with use_mesh(mesh):
        hlo = jax.jit(jax.grad(fused_ce.fused_cross_entropy, argnums=(0, 1))).lower(
            *operands).compile().as_text()
    after = fused_ce.plan_counts()
    assert "tpu_custom_call" not in hlo
    assert {k: after[k] - before[k] for k in after} == {
        "grad_in_forward": 1, "forward_only": 0, "softmax_grad_kernel": 0, "softmax_grad_xla": 1}


def test_two_kinds_of_attention_core_compile_for_v5e_at_the_cells_length(v5e, compiled_kernels):
    """``trinity-mini-ep8.train-seq16k``'s attention (models/afmoe.py): one
    row of 16,384, GQA 32/4 of 128, a traced flag choosing by ``lax.cond``
    between the window-2,048 core (with RoPE) and the causal one, forward and
    backward: both branches' three kernels are in the program the chip's
    compiler leaves, each under its kind's scope, all six on the resident
    path (16,384 is the last length K and V stay in VMEM)."""
    import re

    from mlx_cuda_distributed_pretraining_tpu.models import afmoe

    args = afmoe.AfmoeArgs(hidden_size=2048, num_heads=32, num_kv_heads=4, head_dim=128,
                           sliding_window=2048, attention_type="flash")
    S = 16384
    p = jax.eval_shape(lambda: afmoe.init_params(jax.random.PRNGKey(0), dataclasses.replace(
        args, num_layers=2, layer_types=(afmoe.SLIDING, afmoe.FULL)), jnp.bfloat16))
    att = jax.tree_util.tree_map(lambda a: _sds(a.shape, a.dtype, v5e), p["layers"][0]["attention"])

    def loss(att, x, flag):
        out = afmoe.gated_attention(att, x, args, jnp.arange(S, dtype=jnp.int32), flag)
        return out.astype(jnp.float32).sum()

    before = core_counts()
    hlo = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        att, _sds((1, S, 2048), jnp.bfloat16, v5e), _sds((), jnp.bool_, v5e)).compile().as_text()
    traced = {k: n - before.get(k, 0) for k, n in core_counts().items() if n - before.get(k, 0)}
    assert traced == {f"{kind}_{what}": 1 for kind in ("window", "global")
                      for what in ("layers", "fwd_resident", "bwd_dq_resident", "bwd_dkv_resident")}
    calls = [m.group(1) for line in hlo.split("\n") if "tpu_custom_call" in line
             for m in [re.search(r'op_name="([^"]+)"', line)] if m]
    for kind in ("attn_window", "attn_global"):
        mine = [c for c in calls if kind in re.split(r"[/()]", c)]
        assert sorted(next(t for t in reversed(re.split(r"[/()]", c)) if t.startswith("flash_"))
                      for c in mine) == ["flash_bwd_dkv", "flash_bwd_dq", "flash_fwd"], (kind, calls)
    assert len(calls) == 6 and " conditional(" in hlo


@pytest.mark.parametrize("rows,path", [(16384, "resident"), (32768, "streamed")])
def test_block_diffusion_cores_compile_for_v5e(v5e, compiled_kernels, rows, path):
    """``sdar-30b-a3b-ep8.train-bd8k``'s attention (models/sdar.py): two copies of
    a row of 8,192 side by side, GQA 32/4 of 128, the block-diffusion mask in
    blocks of 4, forward and backward: Mosaic takes the walk over a plan of
    segments, a cut segment's closed form on index vectors and the noised
    diagonal in squares of 128, all three kernels on the resident path (16,384
    rows are the last length K and V stay in VMEM); and, a length on, the
    in-tile mask program (and/or of comparisons and a shift: it has no select
    between booleans) in the streamed grid gated and clamped by the same segments."""
    import re

    from mlx_cuda_distributed_pretraining_tpu.ops.attention import attention_core

    def loss(q, k, v):
        out = attention_core(q, k, v, "flash", kind="blockdiff", mask_type="block_diffusion",
                             window_size=4)
        return out.astype(jnp.float32).sum()

    before, counted = core_counts(), fa.plan_counts()
    hlo = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        _sds((1, rows, 32, 128), jnp.bfloat16, v5e), _sds((1, rows, 4, 128), jnp.bfloat16, v5e),
        _sds((1, rows, 4, 128), jnp.bfloat16, v5e)).compile().as_text()
    traced = {k: n - before.get(k, 0) for k, n in core_counts().items() if n - before.get(k, 0)}
    assert traced == {f"blockdiff_{what}": 1 for what in
                      ("layers", f"fwd_{path}", f"bwd_dq_{path}", f"bwd_dkv_{path}")}
    plans = {k: n - counted[k] for k, n in fa.plan_counts().items() if n - counted[k]}
    assert plans == {path: 1, f"bwd_dq_{path}": 1, f"bwd_dkv_{path}": 1}
    blocks = fa.flash_plan(rows, rows, 128, jnp.bfloat16)[1:]
    tiles = fa.block_diffusion_tiles(rows // 2, 4, *blocks)
    assert fa.bd_tiles_traced() == fa.block_diffusion_walk(rows // 2, 4, *blocks,
                                                           resident=path == "resident")
    assert fa.bd_tiles_traced()["live"] == int((tiles > 0).sum())
    if path == "resident":
        # the noised diagonal's 16 tiles in four squares of 128 (Mosaic takes the
        # slices of the scratches and the masks on index vectors), 32 cut tiles whole
        assert fa.bd_tiles_traced() == {"live": 288, "grid": 1024, "masked": 32, "narrow": 16}
    else:
        assert fa.bd_tiles_traced()["narrow"] == 0
    calls = [m.group(1) for line in hlo.split("\n") if "tpu_custom_call" in line
             for m in [re.search(r'op_name="([^"]+)"', line)] if m]
    assert sorted(next(t for t in reversed(re.split(r"[/()]", c)) if t.startswith("flash_"))
                  for c in calls) == ["flash_bwd_dkv", "flash_bwd_dq", "flash_fwd"], calls
    assert all("attn_blockdiff" in re.split(r"[/()]", c) for c in calls)


def test_selective_scan_kernels_compile_for_v5e_at_the_cells_width(v5e, monkeypatch):
    """``phi4-mini-flash-l6.train-seq16k``'s scan (ops/selective_scan.py): one row
    of 16,384 steps, 5,120 channels of 16 states, forward and backward: Mosaic
    takes both kernels (SMEM blocks of a chunk's ``B_t``, ``C_t``, the backward's
    ``T + 1`` rebuilt states in VMEM under the raised limit), and nothing as large
    as the ``[S, d_inner, N]`` states (5.4 GB) is left in the program."""
    from mlx_cuda_distributed_pretraining_tpu.ops import selective_scan as ss

    monkeypatch.setattr(ss, "_interpret", lambda: False)
    S, Di, N = 16384, 5120, 16
    ops = (_sds((1, S, Di), jnp.float32, v5e), _sds((1, S, Di), jnp.float32, v5e),
           _sds((Di, N), jnp.float32, v5e), _sds((1, S, N), jnp.float32, v5e),
           _sds((1, S, N), jnp.float32, v5e), _sds((Di,), jnp.float32, v5e))
    before = ss.plan_counts()
    grad = jax.grad(lambda *a: ss.selective_scan(*a, backend="kernel").sum(), argnums=tuple(range(6)))
    compiled = jax.jit(grad).lower(*ops).compile()
    traced = {k: n - before.get(k, 0) for k, n in ss.plan_counts().items() if n - before.get(k, 0)}
    assert traced == {"fwd_kernel": 1, "bwd_kernel": 1, "fwd_kernel_chunk128": 1, "bwd_kernel_chunk128": 1}
    hlo = compiled.as_text()
    calls = [line for line in hlo.split("\n") if "tpu_custom_call" in line]
    assert sum("ssm_scan_fwd" in c for c in calls) == 1 and sum("ssm_scan_bwd" in c for c in calls) == 1
    # operands, results, saved chunk states and layout copies: a few arrays of [S, Di], not N of them
    assert compiled.memory_analysis().temp_size_in_bytes < 6 * S * Di * 4


@pytest.mark.parametrize("H", [32, 64])
def test_kda_kernels_compile_for_v5e_at_the_cells_call(v5e, monkeypatch, H):
    """``kimi-linear-48b-a3b-ep16.train-seq8k``'s delta-rule core (ops/kda.py) and
    ``solar-open2-250b-ep40.train-seq8k``'s: 2 rows
    of 8,192 steps, 32 | 64 heads of 128, bfloat16 operands beside a float32 decay,
    forward and backward: Mosaic takes both kernels (one head's lanes of a chunk
    as a block of ``[B, S, H d]``, ``beta`` as a column and as a row, the float32
    solve, the pairs' seven levels of masked matmuls), the differentiated call holds one
    forward with its saved states and one backward, and nothing larger than the
    states at chunk starts (0.5 | 1 GiB) is left in the program."""
    from mlx_cuda_distributed_pretraining_tpu.ops import kda

    monkeypatch.setattr(kda, "_interpret", lambda: False)
    Bt, S, d = 2, 8192, 128
    ops = tuple(_sds((Bt, S, H, d), t, v5e) for t in (jnp.bfloat16,) * 3 + (jnp.float32,)) \
        + (_sds((Bt, S, H), jnp.float32, v5e),)
    before = kda.plan_counts()
    core = lambda *a: kda.kda(*a, backend="kernel")
    forward = jax.jit(core).lower(*ops).compile()
    grad = jax.jit(jax.grad(lambda *a: core(*a).astype(jnp.float32).sum(), argnums=tuple(range(5))))
    compiled = grad.lower(*ops).compile()
    traced = {k: n - before.get(k, 0) for k, n in kda.plan_counts().items() if n - before.get(k, 0)}
    assert traced == {"kernel": 2, f"kernel_chunk{kda.KERNEL_CHUNK}": 2, "levels7": 2, "pair_passes0": 2}
    calls = lambda c: [line for line in c.as_text().split("\n") if "tpu_custom_call" in line]
    assert sum("kda_fwd" in c for c in calls(forward)) == 1 and len(calls(forward)) == 1
    assert sum("kda_fwd" in c for c in calls(compiled)) == 1 and sum("kda_bwd" in c for c in calls(compiled)) == 1
    states = f"f32[{Bt},{H},{S // kda.KERNEL_CHUNK},{d},{d}]"
    # the primal alone saves no state; differentiated: the states, five cotangents and, for
    # operands handed over as [B, S, H, d], their copies into [B, S, H d] (a model's projections
    # come in that layout)
    assert states not in forward.as_text() and states in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 16 * Bt * S * H * d * 2   # a dozen operands' worth


@pytest.mark.parametrize("heads", [32, None])
def test_short_conv_kernels_compile_for_v5e_at_the_cells_call(v5e, monkeypatch, no_mesh_left_behind, heads):
    """The same cell's q, k, v prologue (ops/short_conv.py): a bfloat16 projection of
    2 x 8,192 rows of 4,096 channels and four taps, with the norm over 32 heads (q, k)
    and without (v), forward and backward: Mosaic takes both kernels at the module's
    block (a 16-row view of a packed operand before and after a block, the rows
    shifted through sublane rotations, the taps' sums resident over the row axis),
    the differentiated call is one backward kernel (the forward is not run again for
    residuals: they are the operands), and no float32 ``[B, S, D]`` array is left in
    the program: the largest temporary is the bfloat16 cotangent."""
    from mlx_cuda_distributed_pretraining_tpu.ops import short_conv as sc

    monkeypatch.setattr(sc, "_interpret", lambda: False)
    Bt, S, D = 2, 8192, 4096
    ops = (_sds((Bt, S, D), jnp.bfloat16, v5e), _sds((D, 4), jnp.bfloat16, v5e))
    before = sc.plan_counts()
    call = lambda a, w: sc.short_conv(a, w, heads=heads, scale=128 ** -0.5 if heads else 1.0, backend="kernel")
    forward = jax.jit(call).lower(*ops).compile()
    compiled = jax.jit(jax.grad(lambda a, w: call(a, w).astype(jnp.float32).sum(), argnums=(0, 1))).lower(*ops).compile()
    assert {k: n - before[k] for k, n in sc.plan_counts().items()} == {"conv_kernel": 2, "conv_xla": 0}
    calls = lambda c: [line for line in c.as_text().split("\n") if "tpu_custom_call" in line]
    assert len(calls(forward)) == 1 and "short_conv_fwd" in calls(forward)[0]
    assert len(calls(compiled)) == 1 and "short_conv_bwd" in calls(compiled)[0]
    assert f"f32[{Bt},{S},{D}]" not in forward.as_text() + compiled.as_text()
    assert forward.memory_analysis().temp_size_in_bytes < 2**20
    assert compiled.memory_analysis().temp_size_in_bytes < 1.1 * Bt * S * D * 2


def test_a_stack_of_kda_layers_lowers_each_prologue_body_once(v5e, monkeypatch, no_mesh_left_behind):
    """What a run of the cell pays on the host before its first step (PERF.md section 6, PR 53):
    the differentiated, checkpointed stack of three KDA layers at tiny widths (heads of 128)
    calls the prologue's kernels from 27 sites (q, k, v; forward, recomputed, backward), and
    each distinct body goes through Mosaic's lowering ONCE: two forward bodies (with the norm:
    q and k, a scalar operand apart; without: v) and two backward. The module's text may name
    a forward body's jitted call twice (``jax.checkpoint`` re-makes a jitted call's jaxpr for
    its recomputation, as it does ``kda_fwd``'s; the copy's kernel is served by JAX's
    per-equation lowering cache), never once a site."""
    import json
    import re

    from benchmark import run as harness
    from benchmark.reference import kimi_linear as ref
    from benchmark.traffic_kinds import train_job_kda as kind
    from jax._src.pallas import pallas_call as pallas_call_lib
    from mlx_cuda_distributed_pretraining_tpu.config import Config
    from mlx_cuda_distributed_pretraining_tpu.models import kimi_linear as kl
    from mlx_cuda_distributed_pretraining_tpu.ops import kda
    from mlx_cuda_distributed_pretraining_tpu.ops import short_conv as sc

    monkeypatch.setattr(sc, "_interpret", lambda: False)
    monkeypatch.setattr(kda, "_interpret", lambda: False)
    monkeypatch.setenv("KDA_BACKEND", "kernel")
    lowered = collections.Counter()
    mosaic = pallas_call_lib.mosaic_tpu_backend
    rule = mosaic.pallas_call_tpu_lowering_rule
    monkeypatch.setattr(mosaic, "pallas_call_tpu_lowering_rule",
                        lambda ctx, *a, **kw: (lowered.update([kw["name"]]), rule(ctx, *a, **kw))[1])

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    load = lambda path: json.load(open(os.path.join(root, path)))
    cfg = harness.merge_into(load("benchmark/configs/kimi-linear-48b-a3b-ep16.json"), load("benchmark/rehearse_kda.json")["config"])
    cfg = dict(cfg, num_hidden_layers=4, hidden_size=128, linear_attn_config=dict(
        cfg["linear_attn_config"], head_dim=128, num_heads=2, kda_layers=[1, 2, 3], full_attn_layers=[4]))
    model = kind.arch.MODEL_SECTIONS["kimi_linear"](cfg, {"attention_type": "simple"})
    args = kl.KimiLinearArgs.from_config(Config.from_dict({"name": "t", "model": model}).model, cfg["vocab_size"])
    Bt, S = 2, 256
    params = jax.tree_util.tree_map(lambda x: _sds(x.shape, x.dtype, v5e), jax.eval_shape(lambda: ref.init_params(7, cfg)))
    batch = {"inputs": _sds((Bt, S), jnp.int32, v5e), "targets": _sds((Bt, S), jnp.int32, v5e),
             "mask": _sds((Bt, S), jnp.float32, v5e)}
    before = kl.kda_plan_counts()
    text = jax.jit(jax.value_and_grad(lambda p, b: kl.loss_fn(p, b, args, remat="full"), has_aux=True)).lower(
        params, batch).as_text()
    traced = {k: n - before.get(k, 0) for k, n in kl.kda_plan_counts().items()}
    assert traced["kernel"] == 3 and traced["conv_kernel"] == 9 and traced["conv_xla"] == traced["xla"] == 0
    assert {k: lowered[k] for k in ("short_conv_fwd", "short_conv_bwd")} == {"short_conv_fwd": 2, "short_conv_bwd": 2}
    assert lowered["kda_fwd"] == lowered["kda_bwd"] == 1          # the yardstick: the cores' pair
    functions = re.split(r"\n  func\.func ", text)
    holding = lambda name: [f.split("(", 1)[0].split()[-1].lstrip("@") for f in functions if f'kernel_name = "{name}"' in f]
    sites = lambda name: sum(len(re.findall(r"call @%s\(" % re.escape(f), text)) for f in holding(name))
    assert sites("short_conv_fwd") == 18 and sites("short_conv_bwd") == 9
    assert len(holding("short_conv_fwd")) <= 4 and len(holding("short_conv_bwd")) == 2


def test_differential_attention_cores_compile_for_v5e_at_the_cells_length(v5e, compiled_kernels):
    """The same cell's attention (models/sambay.py): 40 + 40 stacked query heads of
    64 over 20 stacked key heads and values of 128, one row of 16,384, under the
    window of 512 and causally: heads of 64 have never been the flash kernels'
    at this length, and all six calls take the resident path."""
    from mlx_cuda_distributed_pretraining_tpu.models import sambay

    args = sambay.SambaYArgs(hidden_size=2560, num_heads=40, num_kv_heads=20, head_dim=64,
                             sliding_window=512, attention_type="flash")
    S = 16384
    q = _sds((1, S, 40, 64), jnp.bfloat16, v5e)
    k_st, vbar = _sds((1, S, 20, 64), jnp.bfloat16, v5e), _sds((1, S, 10, 128), jnp.bfloat16, v5e)

    def loss(q, k_st, vbar, kind):
        a1, a2 = sambay.diff_attention_core(q, k_st, vbar, args, kind)
        return (a1.astype(jnp.float32) - 0.5 * a2.astype(jnp.float32)).sum()

    before = core_counts()
    for kind in ("S", "F"):
        hlo = jax.jit(jax.grad(lambda q, k, v, kind=kind: loss(q, k, v, kind), argnums=(0, 1, 2))).lower(
            q, k_st, vbar).compile().as_text()
        assert sum("tpu_custom_call" in line for line in hlo.split("\n")) == 3
    traced = {k: n - before.get(k, 0) for k, n in core_counts().items() if n - before.get(k, 0)}
    assert traced == {f"{kind}_{what}": 1 for kind in ("window", "global")
                      for what in ("layers", "fwd_resident", "bwd_dq_resident", "bwd_dkv_resident")}
