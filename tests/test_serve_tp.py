"""Tensor-parallel serving (GSPMD batch engine) + reshard-on-load.

Everything runs on the conftest-forced 8-device virtual CPU platform:
tp=2 meshes take a 2-device prefix. The bar throughout is token-for-token
greedy identity with the unsharded (mesh=None) engine — sharding is a
layout annotation, never a numerics change.
"""

import threading
import warnings
from types import SimpleNamespace

import jax
import numpy as np
import pytest
from jax.sharding import NamedSharding

from mlx_cuda_distributed_pretraining_tpu.checkpoint.manager import (
    CheckpointManager,
)
from mlx_cuda_distributed_pretraining_tpu.checkpoint.safetensors_io import (
    save_safetensors,
)
from mlx_cuda_distributed_pretraining_tpu.config import DataConfig
from mlx_cuda_distributed_pretraining_tpu.models import llama
from mlx_cuda_distributed_pretraining_tpu.models.llama import LlamaArgs
from mlx_cuda_distributed_pretraining_tpu.parallel import (
    build_mesh,
    build_serve_mesh,
    mesh_axis_sizes,
    parse_mesh_spec,
)
from mlx_cuda_distributed_pretraining_tpu.parallel.sharding_rules import (
    param_pspec,
    tree_pspecs,
)
from mlx_cuda_distributed_pretraining_tpu.serve import BatchEngine, EngineConfig
from mlx_cuda_distributed_pretraining_tpu.tokenizer import TokenizerManager
from mlx_cuda_distributed_pretraining_tpu.utils.tree import flatten_dict

TOK = TokenizerManager(DataConfig())
# num_heads=4 and num_kv_heads=2 both divide tp=2: attention shards clean.
ARGS = LlamaArgs(
    vocab_size=TOK.vocab_size, hidden_size=32, intermediate_size=64,
    num_layers=2, num_heads=4, num_kv_heads=2, head_dim=8,
    max_position_embeddings=128,
)
PARAMS = llama.init_params(jax.random.PRNGKey(0), ARGS)
MAX_LEN = 128

PROMPTS = ["the quick brown fox", "a b c a b c a", "hello world hello world"]


def _tp2():
    # Exact 2-device prefix: no stranded devices, no warning.
    return build_serve_mesh({"tp": 2}, devices=jax.devices()[:2])


def _engine(mesh=None, **kw):
    cfg = EngineConfig(**{"num_slots": 2, "max_len": MAX_LEN,
                          "prefill_chunk": 16, **kw})
    return BatchEngine(PARAMS, ARGS, TOK, cfg, mesh=mesh)


def _collect(eng, prompts, max_tokens=24, **gen_kw):
    eng.start()
    outs = [None] * len(prompts)
    try:
        def run(i):
            outs[i] = eng.generate(prompts[i], max_tokens=max_tokens,
                                   timeout=300.0, **gen_kw)

        threads = [threading.Thread(target=run, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        metrics = eng.metrics()
    finally:
        eng.stop()
    return outs, metrics


# -- mesh construction --------------------------------------------------------

def test_parse_mesh_spec():
    assert parse_mesh_spec("tp=2") == {"tp": 2}
    assert parse_mesh_spec("tp=2, dp=4") == {"tp": 2, "dp": 4}
    assert parse_mesh_spec("") == {}
    with pytest.raises(ValueError, match="axis=N"):
        parse_mesh_spec("tp")
    with pytest.raises(ValueError, match="axis size"):
        parse_mesh_spec("tp=two")


def test_build_serve_mesh_none_on_trivial_specs():
    # None means "run the pre-mesh single-device path": the engine's jit
    # cache keys stay byte-identical to a build without the mesh feature.
    assert build_serve_mesh(None) is None
    assert build_serve_mesh({}) is None
    assert build_serve_mesh({"tp": 1, "dp": 1}) is None
    assert build_serve_mesh("tp=1") is None


def test_build_serve_mesh_rejects_trainer_axes():
    with pytest.raises(ValueError, match="trainer-only"):
        build_serve_mesh({"fsdp": 2})


def test_build_serve_mesh_shapes():
    mesh = _tp2()
    assert dict(mesh.shape) == {"tp": 2} and mesh.size == 2
    both = build_serve_mesh("dp=2,tp=2", devices=jax.devices()[:4])
    # AXIS_ORDER puts dp before tp — same order the trainer mesh uses.
    assert tuple(both.axis_names) == ("dp", "tp")


def test_stranded_devices_warn_loudly():
    with pytest.warns(RuntimeWarning, match="STRANDED"):
        sizes = mesh_axis_sizes(SimpleNamespace(mesh={"tp": 2}), 8)
    assert sizes == {"tp": 2}
    # Exact cover: silent.
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert mesh_axis_sizes(SimpleNamespace(mesh={"tp": 2}), 2) == {"tp": 2}
        assert mesh_axis_sizes(SimpleNamespace(mesh={"dp": -1}), 8) == {"dp": 8}


# -- tp engine parity ---------------------------------------------------------

@pytest.mark.parametrize("arm", [
    {},                                          # base paged
    {"kv_quant": True},                          # int8 KV quartet sharded
    {"spec_draft_len": 4, "spec_max_ngram": 3},  # spec-decode on top of tp
], ids=["base", "int8", "spec"])
def test_tp2_greedy_matches_unsharded(arm):
    ref, _ = _collect(_engine(**arm), PROMPTS, temperature=0.0)
    tp, m = _collect(_engine(mesh=_tp2(), **arm), PROMPTS, temperature=0.0)
    assert m["mesh"] == "tp=2"
    for r, t in zip(ref, tp):
        assert t["text"] == r["text"]
        assert t["tokens"] == r["tokens"]
        assert t["finish_reason"] == r["finish_reason"]
    if arm.get("spec_draft_len"):
        assert m["spec_proposed"] >= m["spec_accepted"] >= 0


def test_tp2_adds_no_host_readbacks(monkeypatch):
    # GSPMD keeps logits and sampling on the device: every device->host
    # read of the serve loop goes through np.asarray(jax.Array) or
    # jax.device_get, and tp must add none. The flood is queued before the
    # engine starts, so both arms admit and step on the same schedule.
    reads = [0]
    asarray, device_get = np.asarray, jax.device_get

    def counting_asarray(a, *args, **kw):
        if isinstance(a, jax.Array):
            reads[0] += 1
        return asarray(a, *args, **kw)

    def counting_device_get(x):
        reads[0] += 1
        return device_get(x)

    monkeypatch.setattr(np, "asarray", counting_asarray)
    monkeypatch.setattr(jax, "device_get", counting_device_get)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(2, ARGS.vocab_size, size=40).tolist()
               for _ in range(4)]

    def flood(mesh):
        eng = _engine(mesh=mesh, num_slots=4)
        reqs = [eng._submit_ids(ids, 24, 0.0, 0) for ids in prompts]
        before = reads[0]
        eng.start()
        try:
            assert all(r.wait(300.0) for r in reqs)
        finally:
            eng.stop()
        return reads[0] - before, [list(r.tokens) for r in reqs]

    one_reads, one_tokens = flood(None)
    two_reads, two_tokens = flood(_tp2())
    assert two_tokens == one_tokens
    assert two_reads == one_reads > 0, (one_reads, two_reads)


def test_tp2_prefix_cache_adoption_parity():
    # Sequential requests sharing a long prefix: the second adopts the
    # first one's cached KV blocks, which under tp=2 live sharded over
    # the head axis.
    shared = "the quick brown fox jumps over the lazy dog and then"
    prompts = [shared + " stops", shared + " keeps going"]

    def run(eng):
        eng.start()
        try:
            outs = [eng.generate(p, max_tokens=24, temperature=0.0,
                                 timeout=300.0) for p in prompts]
            return outs, eng.metrics()["prefix_cache_hits"]
        finally:
            eng.stop()

    ref, ref_hits = run(_engine(block_size=16, prefix_min_hit_blocks=1))
    tp, tp_hits = run(_engine(mesh=_tp2(), block_size=16,
                              prefix_min_hit_blocks=1))
    assert tp_hits == ref_hits and tp_hits >= 1
    for r, t in zip(ref, tp):
        assert t["text"] == r["text"]


def test_mesh_metrics_surface():
    eng = _engine(mesh=_tp2())
    m = eng.metrics()
    assert m["mesh"] == "tp=2"
    assert _engine().metrics()["mesh"] == "1dev"


# -- reshard-on-load ----------------------------------------------------------

def test_reshard_on_load_fsdp2_checkpoint_into_tp2(tmp_path):
    # A checkpoint written under a TRAINING mesh (fsdp=2) loads directly
    # into the SERVING sharding (tp=2): no host gather, and no device ever
    # holds a full replica of a sharded matrix.
    devs = jax.devices()
    fsdp_mesh = build_mesh(SimpleNamespace(mesh={"fsdp": 2}), devs[:2])
    placed = jax.tree_util.tree_map(
        lambda leaf, spec: jax.device_put(leaf, NamedSharding(fsdp_mesh, spec)),
        PARAMS, tree_pspecs(PARAMS, fsdp_mesh))
    flat_host = {k: np.asarray(v) for k, v in flatten_dict(placed).items()}
    path = str(tmp_path / "model.safetensors")
    save_safetensors(path, flat_host)

    tp_mesh = _tp2()
    loaded = CheckpointManager.load_params(path, like=PARAMS, mesh=tp_mesh)
    flat = flatten_dict(loaded)

    # Column-parallel attention weight: one half per device, exactly.
    wq = flat["layers.0.attention.wq.weight"]
    assert wq.sharding.mesh == tp_mesh
    assert wq.sharding.spec == param_pspec(
        "layers.0.attention.wq.weight", wq.shape, tp_mesh)
    shard_bytes = [s.data.nbytes for s in wq.addressable_shards]
    assert len(shard_bytes) == 2
    assert all(b == wq.nbytes // 2 for b in shard_bytes)

    # Per-device buffer accounting across the WHOLE tree: a leaf sharded
    # over tp contributes exactly its host bytes (half per device), a
    # replicated leaf contributes 2x. Full-replica materialization of the
    # sharded leaves would blow this exact budget.
    expected = actual = 0
    for k, v in flat.items():
        sharded = any(ax is not None
                      for ax in param_pspec(k, v.shape, tp_mesh))
        expected += v.nbytes * (1 if sharded else 2)
        actual += sum(s.data.nbytes for s in v.addressable_shards)
    host_total = sum(v.nbytes for v in flat_host.values())
    assert actual == expected
    assert actual < 2 * host_total  # proves something actually sharded

    # And the resharded params serve token-identically.
    ref, _ = _collect(_engine(), PROMPTS[:2], temperature=0.0)
    cfg = EngineConfig(num_slots=2, max_len=MAX_LEN, prefill_chunk=16)
    tp, _ = _collect(BatchEngine(loaded, ARGS, TOK, cfg, mesh=tp_mesh),
                     PROMPTS[:2], temperature=0.0)
    for r, t in zip(ref, tp):
        assert t["text"] == r["text"]
        assert t["tokens"] == r["tokens"]


def test_load_params_mesh_rejects_dtype_mismatch(tmp_path):
    # With a mesh, a dtype cast would re-materialize the full array on the
    # host — load_params must refuse instead of silently gathering.
    from mlx_cuda_distributed_pretraining_tpu.checkpoint.manager import (
        CheckpointIntegrityError,
    )

    flat = {k: np.asarray(v) for k, v in flatten_dict(PARAMS).items()}
    key = "layers.0.attention.wq.weight"
    flat[key] = flat[key].astype(np.float16)
    path = str(tmp_path / "model.safetensors")
    save_safetensors(path, flat)
    with pytest.raises(CheckpointIntegrityError, match="re-materialize"):
        CheckpointManager.load_params(path, like=PARAMS, mesh=_tp2())


# -- subprocess device forcing (shared conftest helper) -----------------------

@pytest.mark.slow
def test_spawn_with_devices_forces_child_device_count():
    import sys

    from conftest import spawn_with_devices

    src = (
        "import jax\n"
        "from mlx_cuda_distributed_pretraining_tpu.parallel import build_serve_mesh\n"
        "assert jax.device_count() == 2, jax.device_count()\n"
        "mesh = build_serve_mesh('tp=2')\n"
        "print('CHILD_OK', dict(mesh.shape))\n"
    )
    proc = spawn_with_devices([sys.executable, "-c", src], n=2)
    out, _ = proc.communicate(timeout=300)
    assert proc.returncode == 0, out[-2000:]
    assert "CHILD_OK {'tp': 2}" in out
