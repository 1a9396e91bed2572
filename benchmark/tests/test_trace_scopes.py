"""``trace_scopes`` on traces recorded on the chip (one TPU v5e, the
``mistral-7b-v0_3-l4.train-1chip`` job): PR 23's, whose program opened no
scope, and one of this PR's scoped program (my chip run, PR 24, from a
``git archive`` of the final tree: the mix's ``trace_steps`` overridden to 2,
which leaves three whole steps on the ``Steps`` line; ``--keep-trace``). Plus the wire
decoder on a hand-made message, the name-stack tokeniser and the FLOPs."""

import gzip
import os
import shutil

import pytest

from benchmark import trace_reduce, trace_scopes
from benchmark.flops import flash_attention, llama_dense

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
UNSCOPED_TRACE = "train_1chip_v5e.xplane.pb.gz"
SCOPED_TRACE = "train_1chip_v5e_scoped.xplane.pb.gz"


def _unpack(name, tmp_path):
    out = tmp_path / "plugins" / "profile" / "recorded"
    out.mkdir(parents=True)
    with gzip.open(os.path.join(DATA, name)) as src, open(out / "chip.xplane.pb", "wb") as dst:
        shutil.copyfileobj(src, dst)
    return str(tmp_path)


def _steps_busy(trace_dir):
    """``trace_reduce``'s busy seconds inside the whole steps of the trace."""
    ev = trace_reduce.read_events(trace_reduce.find_xplane(trace_dir))
    (steps,) = ev["steps"].values()
    window = (min(s for _, s, _ in steps), max(e for _, _, e in steps))
    return trace_reduce.reduce_events(ev, window)["busy_s"], len(steps)


def _read(name, sources):
    import importlib.util

    path = os.path.join(os.path.dirname(DATA), os.pardir, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("reader_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(sources)


STEP_ROWS = ("attn_core", "attn_proj", "ffn", "lm_head_ce", "optimizer", "unscoped")


def test_name_stack_tokeniser():
    stack = ("jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/"
             "rematted_computation/layer/attn_core/flash_fwd/pallas_call:")
    assert trace_scopes.scope_of(stack) == "flash_fwd"
    assert trace_scopes.is_recompute(stack)
    # the transform wraps the outermost scope
    assert trace_scopes.scope_of("jit(train_step)/jvp(lm_head_ce)/while/body/dot_general:") \
        == "lm_head_ce"
    assert trace_scopes.scope_of("jit(train_step)/transpose(jvp(layer))/attn_qkv/mul:") \
        == "attn_qkv"
    assert trace_scopes.scope_of("jit(train_step)/jvp()/while/body/closed_call/dot_general:") \
        == trace_scopes.UNSCOPED
    assert trace_scopes.scope_of("") == trace_scopes.UNSCOPED
    # a scope is a whole token: `layers` is not `layer`
    assert trace_scopes.scope_of("jit(f)/layers/normalize/add:") == trace_scopes.UNSCOPED
    assert not trace_scopes.is_recompute("jit(f)/checkpoint/layer/ffn/dot_general:")


def test_wire_decoder_on_a_hand_made_message():
    # field 1 varint 300, field 2 bytes "ab", field 3 fixed64 7, field 4 fixed32 9
    msg = bytes([0x08, 0xAC, 0x02, 0x12, 0x02, 0x61, 0x62,
                 0x19, 7, 0, 0, 0, 0, 0, 0, 0, 0x25, 9, 0, 0, 0])
    got = [(f, w, bytes(v) if w == 2 else v) for f, w, v in trace_scopes._fields(memoryview(msg))]
    assert got == [(1, 0, 300), (2, 2, b"ab"), (3, 1, 7), (4, 5, 9)]
    with pytest.raises(ValueError, match="not an xplane file"):
        list(trace_scopes._fields(memoryview(bytes([0x0B]))))  # wire type 3


def test_flash_flops_against_the_required_count():
    B, Hq, S, D, L = 4, 32, 4096, 128, 4
    assert flash_attention.fwd(B, Hq, S, D) == 2.0 * B * Hq * S * S * D == 549_755_813_888.0
    executed = sum(f(B, Hq, S, D) for f in flash_attention.BY_KERNEL.values())
    assert executed == 9.0 * B * Hq * S * S * D
    # the step *requires* 6 L S d_attn a token for attention: two thirds of
    # what the three kernels execute (each backward kernel recomputes Q K^T)
    required = 6.0 * L * S * (Hq * D) * (B * S)
    assert required == pytest.approx(L * executed * 6.0 / 9.0)
    cfg = {"hidden_size": 4096, "intermediate_size": 14336, "num_hidden_layers": L,
           "vocab_size": 32768, "num_attention_heads": Hq, "num_key_value_heads": 8,
           "head_dim": D}
    attention = llama_dense.train_flops_per_token(cfg, S) - 6.0 * llama_dense.matmul_params(cfg)
    assert attention * B * S == pytest.approx(required)


def test_unscoped_program_reads_everything_but_recompute_as_unscoped(tmp_path):
    trace_dir = _unpack(UNSCOPED_TRACE, tmp_path)
    red = trace_scopes.reduce(trace_reduce.find_xplane(trace_dir))
    busy, steps = _steps_busy(trace_dir)
    assert red["steps"] == steps == 5
    assert red["busy_s"] == pytest.approx(busy, rel=1e-3)
    assert set(red["scope_s"]) == {trace_scopes.UNSCOPED}
    assert red["kernels"] == {}
    # 1.03 s of the five steps under rematted_computation (ISSUE 24)
    assert red["recompute_s"] == pytest.approx(1.0314, rel=1e-3)
    assert red["host_s"]["train"] == pytest.approx(5.2377, rel=1e-3)

    sources = {"trace_dir": trace_dir, "peaks": {"bf16_flops": 197e12}}
    assert _read("step_device_ms.unscoped", sources) == pytest.approx(1e3 * busy / 5, rel=1e-3)
    assert _read("step_device_ms.recompute", sources) == pytest.approx(206.3, rel=1e-3)
    for row in STEP_ROWS[:-1]:
        assert _read("step_device_ms." + row, sources) is None
    assert _read("kernel_peak_pct.flash_fwd", sources) is None
    assert _read("kernel_peak_pct.flash_bwd", sources) is None
    assert _read("step_host_ms", sources) is None  # no train.* phase but the step itself


def test_scoped_program_adds_up_to_the_busy_time(tmp_path):
    trace_dir = _unpack(SCOPED_TRACE, tmp_path)
    red = trace_scopes.reduce(trace_reduce.find_xplane(trace_dir))
    busy, steps = _steps_busy(trace_dir)
    assert red["steps"] == steps == 3
    assert sum(red["scope_s"].values()) == pytest.approx(busy, rel=1e-3)
    assert set(red["scope_s"]) <= set(trace_scopes.VOCABULARY) | {trace_scopes.UNSCOPED}
    assert red["scope_s"][trace_scopes.UNSCOPED] < 0.02 * busy

    sources = {"trace_dir": trace_dir, "peaks": {"bf16_flops": 197e12}}
    rows = {r: _read("step_device_ms." + r, sources) for r in STEP_ROWS}
    assert all(v is not None and v > 0 for v in rows.values()), rows
    # the six rows are not a partition: `layer` (the scan's stacking and
    # slicing of the weights), `norm`, `embed` and `final_norm` feed none
    others = sum(red["scope_s"].get(k, 0.0) for k in ("layer", "norm", "embed", "final_norm"))
    assert sum(rows.values()) + 1e3 * others / steps == pytest.approx(1e3 * busy / steps, rel=1e-3)
    assert 0.94 < sum(rows.values()) / (1e3 * busy / steps) < 0.97
    assert rows["ffn"] > rows["attn_proj"] > rows["optimizer"]
    recompute = _read("step_device_ms.recompute", sources)
    assert 0.15 < recompute / (1e3 * busy / steps) < 0.25

    # four layers: the forward kernel runs in the forward pass and again in
    # the recomputation, each backward kernel once
    calls = {k: v["calls"] / steps for k, v in red["kernels"].items()}
    assert calls == {"flash_fwd": 8.0, "flash_bwd_dq": 4.0, "flash_bwd_dkv": 4.0}
    assert all(v["dims"] == [4, 32, 4096, 128] for v in red["kernels"].values())
    fwd = _read("kernel_peak_pct.flash_fwd", sources)
    bwd = _read("kernel_peak_pct.flash_bwd", sources)
    assert 10 < fwd < 105 and 10 < bwd < 105
    per_call_ms = 1e3 * red["kernels"]["flash_fwd"]["seconds"] / red["kernels"]["flash_fwd"]["calls"]
    assert fwd == pytest.approx(100 * flash_attention.fwd(4, 32, 4096, 128)
                                / (per_call_ms * 1e-3) / 197e12, rel=1e-6)
    host = _read("step_host_ms", sources)
    assert host is not None and 0 < host < 50
    assert {"train.data_get", "train.log_window", "train.dispatch"} <= set(red["host_s"])


def test_compiles_in_window_reads_the_programs_counter():
    assert _read("compiles_in_window", {"step_window_events": [
        {"step": 7, "xla_compiles": 0}, {"step": 8, "xla_compiles": 2}]}) == 2.0
    # the parent's events carry no counter: nothing to read, no error
    assert _read("compiles_in_window", {"step_window_events": [{"step": 7}]}) is None
    assert _read("compiles_in_window", {}) is None


def test_readers_return_nothing_without_a_trace(tmp_path):
    for name in ["step_device_ms." + r for r in STEP_ROWS + ("recompute",)] + [
            "kernel_peak_pct.flash_fwd", "kernel_peak_pct.flash_bwd", "step_host_ms"]:
        assert _read(name, {"trace_dir": None}) is None
        assert _read(name, {"trace_dir": str(tmp_path)}) is None  # no .xplane.pb there


def test_vocabulary_is_the_programs():
    """Every scope the program opens is one the reader knows (and the other
    way round): the program's side is pinned by tests/test_scopes.py."""
    import glob
    import re

    root = os.path.join(os.path.dirname(DATA), os.pardir, os.pardir,
                        "mlx_cuda_distributed_pretraining_tpu")
    found = set()
    for sub in ("models", "ops", "optim", "train", "serve"):
        for path in glob.glob(os.path.join(root, sub, "*.py")):
            with open(path) as f:
                text = f.read()
            found |= set(re.findall(r'named_scope\("([^"]+)"\)', text))
            found |= set(re.findall(r'interpret=_interpret\(\),\n\s+name="(\w+)",', text))
    assert found == set(trace_scopes.VOCABULARY)
