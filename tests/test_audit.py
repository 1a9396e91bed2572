"""graftaudit rule-behavior + plumbing tests.

Each audit rule is pinned against tiny jitted programs with a known
ground truth — a step that forgets to donate its state, a bf16 program
with an fp32 matmul, a captured megabyte constant, a replicated param
the sharding rules expect sharded. Lowering happens on the 8-device
virtual CPU platform the conftest forces; nothing executes.

The full-config gate (audit the sample config end to end, zero new
findings, committed budget matches a fresh census) runs in a subprocess
and is marked slow — scripts/lint.sh runs it too.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from mlx_cuda_distributed_pretraining_tpu.analysis import audit, audit_rules
from mlx_cuda_distributed_pretraining_tpu.analysis.audit_rules import (
    AuditProgram,
    CollectiveCensus,
    DonationGap,
    DtypeUpcast,
    LargeConstantCapture,
    ReplicatedParam,
    parse_hlo_census,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

f32 = jnp.float32
SDS = jax.ShapeDtypeStruct


def _prog(fn, args, donate=(), name="prog", **kw):
    jitted = jax.jit(fn, donate_argnums=donate)
    kw.setdefault("arg_names", tuple(f"arg{i}" for i in range(len(args))))
    return audit._trace_program(name, "testcfg", jitted, args, **kw)


def _by_rule(prog, rule):
    return [f for f in rule.check(prog)]


# -- donation-gap ------------------------------------------------------------

# (256, 256) f32 = 256 KiB — comfortably above the 64 KiB group floor.
BIG = SDS((256, 256), f32)


def _state_step(state, batch):
    return state + batch.sum(), batch.mean()


def test_donation_gap_fires_on_undonated_state():
    prog = _prog(_state_step, (BIG, SDS((32, 32), f32)),
                 arg_names=("state", "batch"))
    findings = _by_rule(prog, DonationGap())
    assert len(findings) == 1
    f = findings[0]
    assert f.rule == "donation-gap"
    assert "`state`" in f.message and "256.0 KiB" in f.message
    assert f.path == "<testcfg:prog>"


def test_donation_gap_silent_when_donated():
    prog = _prog(_state_step, (BIG, SDS((32, 32), f32)), donate=(0,),
                 arg_names=("state", "batch"))
    assert _by_rule(prog, DonationGap()) == []
    assert prog.donation_summary() == {
        "donated_bytes": 256 * 256 * 4, "gap_bytes": 0}


def test_donation_gap_ignores_read_only_args():
    # params shape (256, 256) but the output is (32,): no in/out pair,
    # nothing to alias, no finding — read-only args never flag.
    prog = _prog(lambda w, x: (x @ w).sum(axis=1), (BIG, SDS((32, 256), f32)))
    assert _by_rule(prog, DonationGap()) == []


def test_donation_gap_floor_suppresses_small_buffers():
    # (16, 16) f32 = 1 KiB round-trips un-donated, but chasing it is
    # noise: below max(64 KiB, 5% of inputs) the rule stays quiet.
    prog = _prog(_state_step, (SDS((16, 16), f32), SDS((8, 8), f32)))
    assert _by_rule(prog, DonationGap()) == []


def test_donation_gap_donated_inputs_consume_matches_first():
    # Two same-shaped buffers, one output of that shape: the donated one
    # claims the output slot, the undonated one has nothing left to pair
    # with (returning it unchanged would be read-only anyway).
    def step(a, b):
        return a + b
    prog = _prog(step, (BIG, BIG), donate=(0,))
    assert _by_rule(prog, DonationGap()) == []


# -- dtype-upcast ------------------------------------------------------------


def _bf16_body_with_fp32_dot(x, w):
    h = x @ w                                    # bf16 — fine
    return (h.astype(f32) @ w.astype(f32)).sum()  # fp32 — the finding


def test_dtype_upcast_fires_in_bf16_program():
    args = (SDS((64, 64), jnp.bfloat16), SDS((64, 64), jnp.bfloat16))
    prog = _prog(_bf16_body_with_fp32_dot, args, compute_dtype="bfloat16")
    findings = _by_rule(prog, DtypeUpcast())
    assert len(findings) == 1
    f = findings[0]
    assert "fp32 dot_general" in f.message and "(64, 64)" in f.message
    assert f.line > 0  # attributed to real source, not the synthetic path
    assert "test_audit" in f.path


def test_dtype_upcast_inactive_in_fp32_program():
    args = (SDS((64, 64), f32), SDS((64, 64), f32))
    prog = _prog(lambda x, w: (x @ w).sum(), args, compute_dtype="float32")
    assert _by_rule(prog, DtypeUpcast()) == []


def test_dtype_upcast_silent_on_bf16_matmul():
    args = (SDS((64, 64), jnp.bfloat16), SDS((64, 64), jnp.bfloat16))
    prog = _prog(lambda x, w: (x @ w).sum(), args, compute_dtype="bfloat16")
    assert _by_rule(prog, DtypeUpcast()) == []


# -- large-constant-capture --------------------------------------------------


def test_large_constant_capture_fires():
    baked = jnp.asarray(np.ones((256, 256), np.float32))  # 256 KiB
    prog = _prog(lambda x: (x * baked).sum(), (BIG,))
    findings = _by_rule(prog, LargeConstantCapture())
    assert len(findings) == 1
    assert "(256, 256)" in findings[0].message
    assert "256.0 KiB" in findings[0].message


def test_small_constant_capture_silent():
    baked = jnp.asarray(np.ones((16, 16), np.float32))  # 1 KiB
    prog = _prog(lambda x: (x * baked).sum(), (SDS((16, 16), f32),))
    assert _by_rule(prog, LargeConstantCapture()) == []


# -- collective-census -------------------------------------------------------

_HLO = """\
  %ar = f32[128,256]{1,0} all-reduce(f32[128,256]{1,0} %p0), replica_groups={}
  %ag = f32[1024]{0} all-gather-start(f32[128]{0} %p1), dimensions={0}
  %agd = f32[1024]{0} all-gather-done(f32[1024]{0} %ag)
  %tup = (f32[64,64]{1,0}, f32[64,64]{1,0}) all-to-all(f32[64,64] %a, f32[64,64] %b)
  %fus = f32[128,256]{1,0} fusion(f32[128,256]{1,0} %ar), kind=kLoop
"""


def test_parse_hlo_census_counts_and_bytes():
    census = parse_hlo_census(_HLO)
    # -start counted once, -done skipped, operand references (the fusion
    # consuming %ar) never match.
    assert census["all-reduce"] == {"count": 1, "bytes": 128 * 256 * 4}
    assert census["all-gather"] == {"count": 1, "bytes": 1024 * 4}
    assert census["all-to-all"] == {"count": 1, "bytes": 2 * 64 * 64 * 4}


def _census_prog(census, budget):
    prog = AuditProgram(
        name="p", config_name="testcfg", lowered=None, closed_jaxpr=None,
        arg_leaves=[], out_avals=[], budget=budget)
    prog._census = census
    return prog


def test_census_regression_over_budget():
    prog = _census_prog({"all-reduce": {"count": 3, "bytes": 4096}},
                        {"all-reduce": {"count": 2, "bytes": 4096}})
    findings = _by_rule(prog, CollectiveCensus())
    assert len(findings) == 1
    assert "regressed" in findings[0].message


def test_census_within_budget_is_silent():
    prog = _census_prog({"all-reduce": {"count": 2, "bytes": 4096}},
                        {"all-reduce": {"count": 2, "bytes": 4096}})
    assert _by_rule(prog, CollectiveCensus()) == []


def test_census_without_budget_demands_one():
    prog = _census_prog({"all-reduce": {"count": 2, "bytes": 4096}}, None)
    findings = _by_rule(prog, CollectiveCensus())
    assert len(findings) == 1
    assert "no committed budget" in findings[0].message


def test_census_real_lowering_sees_gspmd_collectives():
    # GSPMD inserts the all-reduce during compilation — it exists in no
    # jaxpr, which is exactly why the census parses compiled HLO.
    mesh = Mesh(np.array(jax.devices()[:8]), ("dp",))
    fn = jax.jit(lambda x: x.sum(),
                 in_shardings=NamedSharding(mesh, P("dp")),
                 out_shardings=NamedSharding(mesh, P()))
    prog = audit._trace_program("sum", "testcfg", fn, (SDS((64, 8), f32),),
                                arg_names=("x",))
    assert sum(v["count"] for v in prog.census().values()) >= 1


# -- replicated-param --------------------------------------------------------


def _sharded_param_prog(param_spec):
    mesh = Mesh(np.array(jax.devices()[:8]), ("dp",))
    fn = jax.jit(
        lambda p, x: x @ p["w"],
        in_shardings=({"w": NamedSharding(mesh, param_spec)},
                      NamedSharding(mesh, P())),
    )
    return audit._trace_program(
        "mm", "testcfg", fn,
        ({"w": SDS((64, 64), f32)}, SDS((8, 64), f32)),
        arg_names=("params", "x"), param_arg_index=0,
        expected_param_specs={"w": str(P("dp", None))})


def test_replicated_param_fires_when_spec_dropped():
    findings = _by_rule(_sharded_param_prog(P()), ReplicatedParam())
    assert len(findings) == 1
    assert "`w` lowered fully replicated" in findings[0].message


def test_replicated_param_silent_when_sharded():
    assert _by_rule(_sharded_param_prog(P("dp", None)),
                    ReplicatedParam()) == []


# -- plumbing: suppression, budgets, baseline hygiene ------------------------


def test_synthetic_findings_skip_inline_suppression(tmp_path):
    from mlx_cuda_distributed_pretraining_tpu.analysis.core import Finding

    src = tmp_path / "mod.py"
    src.write_text("x = 1  # graftlint: disable=dtype-upcast\ny = 2\n")
    findings = [
        Finding("dtype-upcast", str(src), 1, 0, "suppressed one"),
        Finding("dtype-upcast", str(src), 2, 0, "active one"),
        Finding("donation-gap", "<testcfg:prog>", 0, 0, "synthetic"),
    ]
    active, suppressed = audit._apply_suppressions(findings)
    assert [f.message for f in suppressed] == ["suppressed one"]
    assert {f.message for f in active} == {"active one", "synthetic"}


def test_budget_doc_roundtrip_and_shrink_gate(tmp_path):
    prog = _census_prog({"all-reduce": {"count": 2, "bytes": 4096}}, None)
    prog.arg_leaves = []
    doc = audit.build_budget_doc("testcfg", 8, [prog])
    path = str(tmp_path / "budgets" / "testcfg.json")
    audit.write_budget(path, doc)
    assert audit.load_budget(path) == doc
    assert audit.budget_shrinks([prog], doc) == []

    # Committed numbers above the observed census: the budget overstates
    # the comm cost and must be refreshed, not silently coasted on.
    fat = json.loads(json.dumps(doc))
    fat["programs"]["p"]["collectives"]["all-reduce"]["count"] = 5
    shrinks = audit.budget_shrinks([prog], fat)
    assert len(shrinks) == 1 and "shrank" in shrinks[0]


def test_committed_budgets_are_well_formed():
    bdir = os.path.join(REPO, "mlx_cuda_distributed_pretraining_tpu",
                        "analysis", "budgets")
    docs = [f for f in os.listdir(bdir) if f.endswith(".json")]
    assert "model-config-sample.json" in docs
    assert "model-config-moe-8x40m.json" in docs
    for name in docs:
        with open(os.path.join(bdir, name)) as f:
            doc = json.load(f)
        assert doc["tool"] == "graftaudit"
        assert doc["config"] == name[:-len(".json")]
        assert doc["programs"], name
        for prog, entry in doc["programs"].items():
            assert set(entry) == {"collectives", "donation"}, (name, prog)
            # The whole donation sweep: every audited program aliases its
            # updated state and leaves NO provable gap.
            assert entry["donation"]["gap_bytes"] == 0, (name, prog)
            for op, v in entry["collectives"].items():
                assert v["count"] > 0 and v["bytes"] >= 0, (name, prog, op)


def test_audit_baseline_entries_carry_reasons():
    path = audit.default_audit_baseline_path()
    if not os.path.isfile(path):
        pytest.skip("no audit baseline committed (tree is clean)")
    with open(path) as f:
        doc = json.load(f)
    for e in doc.get("findings", []):
        reason = (e.get("reason") or "").strip()
        assert reason and "REPLACE" not in reason, (
            f"baseline entry for [{e.get('rule')}] {e.get('path')} has no "
            f"real reason")


def test_cli_rejects_unknown_program_and_missing_config():
    assert audit.main(["--config", "configs/no-such.yaml"]) == 2
    assert audit.main(["--config",
                       os.path.join(REPO, "configs/model-config-sample.yaml"),
                       "--programs", "bogus"]) == 2


def test_cli_list_rules(capsys):
    assert audit.main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rid in ("donation-gap", "collective-census", "dtype-upcast",
                "large-constant-capture", "replicated-param"):
        assert rid in out


# -- the gate (subprocess, slow) ---------------------------------------------


@pytest.mark.slow
def test_sample_config_audits_clean():
    """The merged tree must audit green: zero new findings and a committed
    budget that matches a fresh lowering, exactly what scripts/lint.sh
    enforces."""
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    proc = subprocess.run(
        [sys.executable, "-m",
         "mlx_cuda_distributed_pretraining_tpu.analysis.audit",
         "--config", "configs/model-config-sample.yaml", "--format", "json"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    doc = json.loads(proc.stdout)
    assert doc["tool"] == "graftaudit"
    assert doc["new"] == [] and doc["stale_budget"] == []
    assert len(doc["suppressed"]) >= 3  # the muon Newton-Schulz fp32 dots
