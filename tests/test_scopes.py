"""Names on the device work and the host's turns (README "Reading a profile").

Device side: the program opens a closed vocabulary of ``jax.named_scope``s
and names its Pallas calls; at tiny widths on the CPU the compiled train
step (scan + full remat) and the four serving steps must carry them in the
``op_name`` of their instructions, which is where a profiler trace reads
them from. Host side: ``Tracer.phase`` writes the loops' phases as
``jax.profiler.TraceAnnotation``s, so a CPU profiler capture around a few
trainer steps and an engine serving two requests must hold them; the
engine's ``busy_iterations`` and the compile counter are checked beside.
"""

import glob
import json
import os
import re
import time
from functools import partial

import jax
import jax.numpy as jnp
import pytest

from mlx_cuda_distributed_pretraining_tpu.config import Config, DataConfig
from mlx_cuda_distributed_pretraining_tpu.models import llama
from mlx_cuda_distributed_pretraining_tpu.obs import compiles
from mlx_cuda_distributed_pretraining_tpu.obs.trace import Tracer
from mlx_cuda_distributed_pretraining_tpu.ops import fused_ce
from mlx_cuda_distributed_pretraining_tpu.ops import grouped_matmul as gm
from mlx_cuda_distributed_pretraining_tpu.optim.enhanced import adamw
from mlx_cuda_distributed_pretraining_tpu.serve import BatchEngine, EngineConfig, batch_step
from mlx_cuda_distributed_pretraining_tpu.tokenizer import TokenizerManager
from mlx_cuda_distributed_pretraining_tpu.train.train_step import (
    init_train_state,
    make_train_step,
)

PACKAGE = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                       "mlx_cuda_distributed_pretraining_tpu")

# The closed vocabulary; benchmark/trace_scopes.py keeps the reader's copy.
VOCABULARY = {
    "embed", "layer", "norm", "attn_qkv", "attn_core", "attn_out", "ffn",
    "moe_router", "moe_experts", "final_norm", "lm_head_ce",
    "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv", "gmm", "tgmm",
    "grad_accum", "grad_clip", "optimizer", "kv_gather", "sample",
}


def op_names(hlo_text):
    return re.findall(r'op_name="([^"]+)"', hlo_text)


def scopes_of(op_name):
    return [t for t in re.split(r"[/()]", op_name) if t in VOCABULARY]


def jitted_as(names, fn_name):
    """Every name stack that starts at a jit starts at this one (parameters
    and the bodies of reductions carry a bare name)."""
    roots = {n.split("/", 1)[0] for n in names if n.startswith("jit(")}
    return roots == {f"jit({fn_name})"}


def _args(**kw):
    return llama.LlamaArgs(**{
        "vocab_size": 64, "hidden_size": 32, "intermediate_size": 64, "num_layers": 2,
        "num_heads": 4, "num_kv_heads": 2, "head_dim": 8, "max_position_embeddings": 64,
        "tie_word_embeddings": False, "attention_type": "flash", **kw})


def _train_step_hlo(args, accum_steps=1):
    loss = partial(llama.loss_fn, args=args, remat="full", scan_layers=True, ce_chunk=16)
    opt = adamw(lambda count: 1e-3, grad_clip=1.0)
    step, _ = make_train_step(lambda p, b: loss(p, b), opt, accum_steps=accum_steps)
    params = llama.init_params(jax.random.PRNGKey(0), args)
    state = init_train_state(params, opt)
    batch = {k: jnp.ones((4, 32), jnp.int32) for k in ("inputs", "targets", "mask")}
    return step.lower(state, batch).compile().as_text()


def _train_step_op_names(args, accum_steps=1):
    return op_names(_train_step_hlo(args, accum_steps))


@pytest.fixture(scope="module")
def dense_names():
    return _train_step_op_names(_args(), accum_steps=2)


LAYER_SCOPES = ["norm", "attn_qkv", "attn_core", "attn_out", "ffn", "flash_fwd"]


@pytest.mark.parametrize("scope", LAYER_SCOPES)
def test_layer_scope_in_forward_backward_and_recomputation(dense_names, scope):
    mine = [n for n in dense_names if scope in scopes_of(n)]
    assert all("layer" in scopes_of(n) for n in mine), "opened outside `layer`"
    assert any("transpose(" not in n for n in mine), f"{scope}: no forward op"
    assert any("rematted_computation" in n for n in mine), f"{scope}: no recomputed op"
    if scope != "flash_fwd":  # its backward is the two kernels below
        assert any("transpose(" in n and "rematted_computation" not in n for n in mine), \
            f"{scope}: no backward op"


@pytest.mark.parametrize("scope", ["embed", "final_norm", "lm_head_ce", "flash_bwd_dq",
                                   "flash_bwd_dkv", "grad_accum", "grad_clip", "optimizer"])
def test_step_scope_is_in_the_train_step(dense_names, scope):
    assert any(scopes_of(n)[-1:] == [scope] for n in dense_names), \
        f"no instruction's innermost scope is {scope}"


def test_train_step_keeps_its_name_and_the_kernels_theirs(dense_names):
    assert jitted_as(dense_names, "train_step")
    for kernel in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert any(f"attn_core/{kernel}/" in n for n in dense_names), kernel
    # the backward kernels run once; the forward kernel in the forward pass
    # and again in the recomputation: separable rows
    fwd = {("rematted_computation" in n) for n in dense_names if "/flash_fwd/" in n}
    assert fwd == {False, True}


# -- the head: gradients in the fused CE's forward walk (ops/fused_ce.py) ------------
HEAD_VOCAB, HEAD_CHUNK = 80, 16  # a vocabulary no other width of _args() equals


def instructions(hlo_text):
    """(opcode, result dimensions, op_name or None) of each instruction."""
    out = []
    for line in hlo_text.split("\n"):
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (\S+) ([\w\-]+)\(", line)
        if m:
            dims = [tuple(int(n) for n in d.split(",") if n)
                    for d in re.findall(r"\[([\d,]*)\]", m.group(1))]
            name = re.search(r'op_name="([^"]+)"', line)
            out.append((m.group(2), dims, name.group(1) if name else None))
    return out


def _head_work(hlo_text):
    """What only the head computes: everything shaped like a chunk of logits,
    and every matmul over the vocabulary (parameters of fused computations
    carry no name)."""
    return [(op, dims, name) for op, dims, name in instructions(hlo_text)
            if op != "parameter"
            and ((HEAD_CHUNK, HEAD_VOCAB) in dims
                 or (op == "dot" and any(HEAD_VOCAB in d for d in dims)))]


@pytest.fixture(scope="module")
def head_step():
    """The compiled train step at a vocabulary of its own, and what tracing
    it added to ``fused_ce.plan_counts()``."""
    before = fused_ce.plan_counts()
    hlo = _train_step_hlo(_args(vocab_size=HEAD_VOCAB))
    after = fused_ce.plan_counts()
    return hlo, {k: after[k] - before[k] for k in after}


@pytest.fixture(scope="module")
def head_eval():
    """The same loss compiled with nothing differentiating it, as the
    trainer's validation does."""
    args = _args(vocab_size=HEAD_VOCAB)
    params = llama.init_params(jax.random.PRNGKey(0), args)
    batch = {k: jnp.ones((4, 32), jnp.int32) for k in ("inputs", "targets", "mask")}
    before = fused_ce.plan_counts()
    hlo = jax.jit(partial(llama.loss_fn, args=args, scan_layers=True, ce_chunk=HEAD_CHUNK)
                  ).lower(params, batch).compile().as_text()
    after = fused_ce.plan_counts()
    return hlo, {k: after[k] - before[k] for k in after}


def test_head_is_not_recomputed(head_step):
    hlo, _ = head_step
    head = [n for n in op_names(hlo) if "lm_head_ce" in scopes_of(n)]
    assert head and not any("rematted_computation" in n for n in head)
    # the layers' recomputation is the recipe's, and stays
    assert any("rematted_computation" in n for n in op_names(hlo))


def test_head_matmuls_three_in_the_train_step_one_in_an_evaluation(head_step, head_eval):
    def head_dots(hlo):
        return [dims for op, dims, name in instructions(hlo)
                if op == "dot" and name and scopes_of(name)[-1:] == ["lm_head_ce"]]

    V, D, C = HEAD_VOCAB, 32, HEAD_CHUNK
    # one chunk walk: logits, dX of the chunk's rows, dW
    assert sorted(d[0] for d in head_dots(head_step[0])) == sorted([(C, V), (C, D), (V, D)])
    assert [d[0] for d in head_dots(head_eval[0])] == [(C, V)]


def test_no_head_operation_is_unscoped(head_step, head_eval):
    for hlo in (head_step[0], head_eval[0]):
        work = _head_work(hlo)
        assert len(work) >= 5
        stray = [(op, dims, name) for op, dims, name in work
                 if name is None or scopes_of(name)[-1:] != ["lm_head_ce"]]
        assert not stray, stray
    # the backward pass, which only scales the residuals, carries the scope too
    assert any("transpose(jvp(lm_head_ce))" in n for n in op_names(head_step[0]))


def test_fused_ce_plan_counts_tell_a_train_step_from_an_evaluation(head_step, head_eval):
    # a vocabulary of 80 is no whole lane register: XLA's chain between the matmuls
    assert head_step[1] == {"grad_in_forward": 1, "forward_only": 0,
                            "softmax_grad_kernel": 0, "softmax_grad_xla": 1}
    assert head_eval[1] == {"grad_in_forward": 0, "forward_only": 1,
                            "softmax_grad_kernel": 0, "softmax_grad_xla": 0}


def test_moe_step_scopes():
    names = _train_step_op_names(_args(num_local_experts=4, num_experts_per_tok=2,
                                       moe_group_size=16))
    for scope in ("moe_router", "moe_experts"):
        mine = [n for n in names if scope in scopes_of(n)]
        assert mine and all("layer" in scopes_of(n) for n in mine), scope
    assert not any("ffn" in scopes_of(n) for n in names)


@pytest.mark.parametrize("n_out", [128, 256])
def test_grouped_matmul_kernels_are_named(n_out):
    """``gmm`` and ``tgmm``: the names the trace's reader keys on, whatever
    the column block (``plan_counts()`` tells the blocks apart)."""
    x = jnp.ones((32, 16), jnp.float32)
    w = jnp.ones((4, 16, n_out), jnp.float32)
    sizes = jnp.array([8, 8, 8, 8], jnp.int32)

    def loss(x, w):
        return gm.gmm(x, w, sizes, block_t=8, backend="pallas").sum()

    before = gm.plan_counts()
    names = op_names(jax.jit(jax.grad(loss, argnums=(0, 1))).lower(x, w).compile().as_text())
    after = gm.plan_counts()
    assert after["gmm_resident"] == before["gmm_resident"] + 2    # the forward and dX
    assert after["tgmm_resident"] == before["tgmm_resident"] + 1
    assert after[f"tgmm_bn{n_out}"] == before.get(f"tgmm_bn{n_out}", 0) + 1
    assert any("gmm" in scopes_of(n) for n in names)
    assert any("tgmm" in scopes_of(n) for n in names)


SERVE_SCOPES = {"embed", "layer", "norm", "attn_qkv", "kv_gather", "attn_core", "attn_out",
                "ffn", "final_norm", "lm_head_ce"}


def _serving_step(kind):
    """(jitted step, example arguments) of one of the four factories."""
    args = _args(attention_type="simple")
    params = llama.init_params(jax.random.PRNGKey(0), args)
    B, T, C, block = 2, 32, 8, 8
    i32 = lambda *shape: jnp.zeros(shape, jnp.int32)  # noqa: E731
    sampling = (jnp.zeros((B,), jnp.float32), jnp.zeros((B, 2), jnp.uint32))
    if kind == "decode_step":
        cache = llama.init_cache(args, B, T)
        for c in cache:
            c.pop("pos")
        return batch_step.decode_step(args, T), (params, cache, i32(B), i32(B), *sampling)
    if kind == "prefill_step":
        cache = llama.init_cache(args, B, T)
        for c in cache:
            c.pop("pos")
        return (batch_step.prefill_step(args, C, T, True),
                (params, cache, i32(C), jnp.int32(0), jnp.int32(0), jnp.int32(C - 1)))
    cache = llama.init_paged_cache(args, B * T // block + 1, block)
    width = T // block
    if kind == "paged_decode_step":
        return (batch_step.paged_decode_step(args, 0, T, width, block),
                (params, cache, i32(B, 1), i32(B), i32(B, width), *sampling))
    return (batch_step.paged_prefill_step(args, C, T, width, block, True),
            (params, cache, i32(C), i32(width), jnp.int32(0), jnp.int32(C - 1)))


@pytest.mark.parametrize("kind", ["decode_step", "prefill_step", "paged_decode_step",
                                  "paged_prefill_step"])
def test_serving_step_scopes_and_name(kind):
    step, example = _serving_step(kind)
    names = op_names(step.lower(*example).compile().as_text())
    assert jitted_as(names, kind), "the jitted step lost its name"
    seen = {s for n in names for s in scopes_of(n)}
    want = SERVE_SCOPES | ({"sample"} if "decode" in kind else set())
    assert want <= seen, f"missing {sorted(want - seen)}"
    assert seen <= VOCABULARY


def test_named_scopes_in_the_program_are_the_vocabulary():
    found, kernels, pallas_calls = set(), [], 0
    for sub in ("models", "ops", "optim", "train", "serve"):
        for path in glob.glob(os.path.join(PACKAGE, sub, "*.py")):
            with open(path) as f:
                text = f.read()
            found |= set(re.findall(r'named_scope\("([^"]+)"\)', text))
            assert not re.search(r"named_scope\((?!\")", text), f"computed scope name in {path}"
            # a pallas_call's name= is also the innermost scope of its ops
            kernels += re.findall(r'interpret=(?:_interpret\(\)|interpret),\n\s+name="(\w+)",', text)
            pallas_calls += text.count("pl.pallas_call(")
    # each flash kernel twice: its resident and its streamed path share the
    # name the trace's reader keys on (plan_counts() tells them apart)
    assert sorted(kernels) == ["ce_softmax_grad", "flash_bwd_dkv", "flash_bwd_dkv",
                               "flash_bwd_dq", "flash_bwd_dq", "flash_fwd", "flash_fwd",
                               "gmm", "kda_bwd", "kda_fwd", "short_conv_bwd", "short_conv_fwd",
                               "ssm_scan_bwd", "ssm_scan_fwd", "tgmm", "token_dot", "token_sum"]
    assert pallas_calls == len(kernels), "a pallas_call without a name="
    # architecture xing_mla_moe opens two more, afmoe three, sambay five with
    # its two scan kernels, sdar_moe two and kimi_linear four with its two delta-rule
    # kernels (and, under ``kda_proj``, the q, k, v prologue's pair, ops/short_conv.py, which is
    # read as part of ``kda``), which the benchmark reads by their own helpers (layer_metrics/_named_scopes.py,
    # _attn_kinds.py, _ssm_scan.py, _blockdiff.py, _kda.py) until its
    # closed vocabulary takes them in; the head's kernel (ops/fused_ce.py) runs
    # under ``lm_head_ce`` and the expert layer's two token-side kernels
    # (ops/token_sum.py) under ``moe_experts``, and each is read as part of that scope
    assert found | set(kernels) == VOCABULARY | {"hc_mix", "mtp"} | {
        "attn_window", "attn_global", "attn_gate"} | {
        "ssm", "ssm_proj", "ssm_conv", "gmu", "attn_diff", "ssm_scan_fwd", "ssm_scan_bwd"} | {
        "ce_softmax_grad", "token_sum", "token_dot"} | {"attn_blockdiff", "bd_rows"} | {
        "kda", "kda_proj", "kda_core", "kda_out", "kda_fwd", "kda_bwd", "short_conv_fwd", "short_conv_bwd"}


# -- the host's turns ---------------------------------------------------------------
def test_phase_with_the_ring_disabled_records_nothing():
    tr = Tracer("t", enabled=False)
    with tr.phase("train.dispatch", step=1) as ph:
        time.sleep(0.002)
    assert ph.seconds >= 0.002
    assert tr.stats() == {"recorded": 0, "dropped": 0, "buffered": 0}


def test_phase_with_the_ring_enabled_records_the_same_span():
    tr = Tracer("t", enabled=True)
    with tr.phase("engine.decode", rows=3) as ph:
        time.sleep(0.002)
    (ev,) = [e for e in tr.chrome_events() if e.get("ph") == "X"]
    assert ev["name"] == "engine.decode" and ev["args"] == {"rows": 3}
    assert ev["dur"] == pytest.approx(ph.seconds * 1e6, abs=2)
    # a sampled-out trace id keeps the annotation and skips the ring
    tr.sample = 0.0
    with tr.phase("engine.prefill_chunk", trace_id="ab" * 16):
        pass
    assert tr.stats()["recorded"] == 1


def _host_event_names(trace_dir):
    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    names = {}
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    names[e.name] = names.get(e.name, 0) + 1
    return names


def _capture(trace_dir):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)


def _tiny_trainer(tmp_path, iters):
    from mlx_cuda_distributed_pretraining_tpu.train.trainer import Trainer

    train = tmp_path / "train.jsonl"
    with open(train, "w") as f:
        for _ in range(40):
            f.write(json.dumps({"text": "the quick brown fox jumps over the lazy dog " * 4})
                    + "\n")
    cfg = Config.from_dict({
        "name": "scoped", "overwrite": True,
        "data": {"input_file": str(train), "preprocessing": {"max_context_size": 64},
                 "tokenizer": {"normal_vocab_size": 256}},
        "model": {"architecture": "llama",
                  "dimensions": {"hidden_size": 32, "intermediate_size": 64, "num_layers": 2},
                  "attention": {"num_heads": 4, "num_kv_heads": 2, "head_dim": 8}},
        "training": {"hyperparameters": {"batch_size": 4, "learning_rate": 1e-2,
                                         "iters": iters},
                     "scheduler": {"type": "constant"},
                     "optimization": {"optimizer": "adamw"}},
        "logging": {"steps": {"logging_interval": 1, "checkpoint_interval": 0,
                              "validation_interval": 0}},
        "system": {"seed": 0, "device": "cpu"}})
    return Trainer(cfg, runs_root=str(tmp_path / "runs"), quiet=True)


def test_trainer_phases_on_the_profilers_clock_and_compiles_per_window(tmp_path):
    tr = _tiny_trainer(tmp_path, iters=5)
    _capture(tmp_path / "trace")
    try:
        tr.train()
    finally:
        jax.profiler.stop_trace()
    names = _host_event_names(str(tmp_path / "trace"))
    for phase in ("train.data_get", "train.dispatch", "train.loss_sync", "train.log_window"):
        assert names.get(phase, 0) >= 5, f"{phase}: {names.get(phase)} events in 5 steps"
    assert names.get("train", 0) >= 5  # the StepTraceAnnotation stays
    assert names.get("checkpoint_save", 0) >= 1  # the final save
    assert tr.tracer.stats()["recorded"] == 0  # the ring was off throughout

    with open(os.path.join(tr.run_dir, "events.jsonl")) as f:
        windows = [e for e in map(json.loads, f) if e.get("type") == "step_window"]
    assert len(windows) == 5
    assert windows[0]["xla_compiles"] >= 1 and windows[0]["xla_compile_s"] > 0
    assert [w["xla_compiles"] for w in windows[2:]] == [0, 0, 0]


def test_compile_counter_counts_a_new_shape_and_nothing_when_steady():
    f = jax.jit(lambda x: x * 2 + 1)
    f(jnp.ones(3)).block_until_ready()
    n0, s0 = compiles.totals()
    f(jnp.ones(3)).block_until_ready()
    assert compiles.totals() == (n0, s0)
    f(jnp.ones(5)).block_until_ready()
    n1, s1 = compiles.totals()
    assert n1 >= n0 + 1 and s1 > s0


TOK = TokenizerManager(DataConfig())


@pytest.fixture
def engine():
    args = _args(vocab_size=TOK.vocab_size, attention_type="simple",
                 max_position_embeddings=128, tie_word_embeddings=True)
    params = llama.init_params(jax.random.PRNGKey(0), args)
    eng = BatchEngine(params, args, TOK,
                      EngineConfig(num_slots=2, max_len=128, prefill_chunk=16))
    yield eng.start()
    eng.stop()


def test_engine_phases_and_iteration_steps_on_the_profilers_clock(engine, tmp_path):
    _capture(tmp_path / "trace")
    try:
        for prompt in ("the quick brown fox", "jumps over the lazy dog"):
            engine.generate(prompt, max_tokens=4, temperature=0.0, timeout=300.0)
        time.sleep(0.1)  # a few idle turns inside the capture
    finally:
        jax.profiler.stop_trace()
    names = _host_event_names(str(tmp_path / "trace"))
    for phase in ("engine.admit", "engine.prefill_chunk", "engine.decode",
                  "engine.build_tables", "engine.dispatch", "engine.sample_fetch",
                  "engine.idle_wait"):
        assert names.get(phase, 0) >= 1, f"no {phase} in the capture"
    m = engine.metrics()
    assert names.get("engine_iter", 0) == m["busy_iterations"] >= 2
    assert names["engine.dispatch"] >= m["busy_iterations"]
    assert engine.tracer.stats()["recorded"] == 0  # ring off: annotations only


def test_busy_iterations_stay_put_over_idle_turns(engine):
    time.sleep(0.15)
    idle = engine.metrics()
    assert idle["iterations"] >= 3 and idle["busy_iterations"] == 0
    engine.generate("the quick brown fox", max_tokens=4, temperature=0.0, timeout=300.0)
    served = engine.metrics()
    assert 2 <= served["busy_iterations"] <= served["iterations"]
    assert served["xla_compiles"] >= 1 and served["xla_compile_s"] > 0
    time.sleep(0.15)
    later = engine.metrics()
    assert later["busy_iterations"] == served["busy_iterations"]
    assert later["iterations"] > served["iterations"]
    engine._last_publish = 0.0  # let the next idle turn mirror into the registry
    time.sleep(0.1)
    flat = engine.metrics_registry.flat()
    assert flat["serve_busy_iterations_total"] == later["busy_iterations"]
    assert flat["serve_xla_compiles_total"] >= 1
