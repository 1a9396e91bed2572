"""The output check has to be able to fail.

Two kinds of test, each at the tiny widths of ``rehearse.json`` but under the
cells' own limits. The control: the reference in the next lower precision, put
in the program's place, comes out NOT correct. The broken run: everything a
run does after the look for a chip, with the timed path broken underneath (a
train step that never moves the weights; an engine that alters tokens where it
emits them), reports ``correct: false``; the same run unbroken reports true.
The serving kinds are driven directly, because their cells are not declared
yet: the last test shows the fault of the program that keeps them out.
"""

import json
import os

import jax
import jax.numpy as jnp
import pytest

from benchmark import control, run as harness

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(BENCH, "..", "BENCHMARK.json")) as _f:
    CELLS = [w["name"] for w in json.load(_f)["workloads"]]
TRAIN_CELLS = [c for c in CELLS if harness._load(os.path.join(
    BENCH, "traffic", harness._load(os.path.join(BENCH, "workloads", c + ".json"))["traffic"]
    + ".json"))["kind"] == "train_job"]
SERVE_MIXES = ["chat-open-0.8knee", "batch-closed-64"]


def _cell(name):
    return harness.load_cell(name, rehearse=True)[1:]


@pytest.mark.parametrize("name", TRAIN_CELLS)
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_training_control_comes_out_not_correct(name, seed):
    cell, config, mix = _cell(name)
    verdict = control.train_control(cell, config, mix, seed, rehearse=True, say=lambda m: None)
    assert not verdict["ok"], verdict["numbers"]
    # the lower precision has to fail one of the cell's numbers, not each:
    # the loss at seeded weights hardly moves
    assert max(verdict["numbers"]["first_grad_norm_gap"],
               verdict["numbers"]["param_change_gap"]) > cell["limits"]["first_grad_norm_gap"]


def _frozen_weights(rec):
    """A step that returns its weights unchanged."""
    inner = rec.inner

    def frozen(state, batch):
        new, metrics = inner(jax.tree_util.tree_map(jnp.copy, state), batch)
        return dict(new, params=state["params"]), metrics

    rec.inner = frozen
    return rec


def test_four_chip_job_rehearses_on_forced_host_devices(tmp_path):
    """``pack4k-b8-fsdp4`` (fsdp=4, not a declared cell yet, PERF.md section 7)
    through the training kind on four host devices: sharded weights from the
    seed, the sharded reference, the same readings."""
    from benchmark.traffic_kinds import train_job

    cell, config, _ = _cell(TRAIN_CELLS[0])
    tiny = harness._load(os.path.join(BENCH, "rehearse.json"))
    mix = harness.merge_into(harness._load(os.path.join(BENCH, "traffic", "pack4k-b8-fsdp4.json")),
                             tiny["traffic_kinds"]["train_job"])
    ctx = harness.Context(dict(cell, name="rehearsal.train-fsdp4", chips=4), config, mix, 5, 1.5,
                          False, True, str(tmp_path), quiet=True)
    res = train_job.run(ctx)
    assert res["correct"], res["check_numbers"]
    assert res["sources"]["chips"] == 4


@pytest.mark.parametrize("name", TRAIN_CELLS)
def test_training_run_sound_is_correct_and_frozen_is_not(name):
    sound = harness.run_cell(name, 5, 1.5, False, rehearse=True, quiet=True)
    assert sound["correct"], sound["check_numbers"]
    broken = harness.run_cell(name, 5, 1.5, False, rehearse=True, quiet=True,
                              wrap_step=_frozen_weights)
    assert not broken["correct"]
    assert broken["check_numbers"]["param_change_gap"] > 0.9


def _serve_run(mix_name, tmp_path, seed=5, **mix_over):
    """A serving mix at tiny widths through its traffic kind. The serving
    cells are not in BENCHMARK.json yet (PERF.md section 7), so the test
    builds the context itself; the limit is the one a float32 program has to
    meet at these widths, where CPU arithmetic is exact: no gap at all."""
    import importlib

    config = harness.merge_into(
        harness._load(os.path.join(BENCH, "configs", "internlm2-1_8b.json")),
        harness._load(os.path.join(BENCH, "rehearse.json"))["config"])
    mix = harness._load(os.path.join(BENCH, "traffic", mix_name + ".json"))
    mix = harness.merge_into(mix, harness._load(
        os.path.join(BENCH, "rehearse.json"))["traffic_kinds"][mix["kind"]])
    mix = harness.merge_into(mix, mix_over)
    cell = {"name": "internlm2-1_8b." + mix_name, "chips": 1,
            "limits": {"served_token_gap": 0.0}}
    ctx = harness.Context(cell, config, mix, seed, 2.0, False, True, str(tmp_path), quiet=True)
    return importlib.import_module("benchmark.traffic_kinds." + mix["kind"]).run(ctx)


@pytest.mark.parametrize("mix_name", SERVE_MIXES)
def test_serving_run_sound_is_correct_and_altered_tokens_are_not(mix_name, tmp_path, monkeypatch):
    from mlx_cuda_distributed_pretraining_tpu.serve.engine import BatchEngine

    # one request at a time: see test_concurrent_prefill_changes_served_tokens
    alone = {"clients": 1, "rate_per_s": 1.0}
    sound = _serve_run(mix_name, tmp_path / "sound", **alone)
    assert sound["correct"] and sound["failed"] == 0, sound["check_numbers"]
    emit = BatchEngine._emit

    def altered(self, req, tok, lp):  # every third token replaced where it is produced
        if len(req.tokens) % 3 == 2:
            tok = (tok + 257) % self.args.vocab_size
        return emit(self, req, tok, lp)

    monkeypatch.setattr(BatchEngine, "_emit", altered)
    broken = _serve_run(mix_name, tmp_path / "broken", **alone)
    assert not broken["correct"], broken["check_numbers"]
    assert broken["check_numbers"]["served_token_gap"] > 0.01


def test_concurrent_prefill_changes_served_tokens(tmp_path):
    """Why the serving cells wait (PERF.md, Findings of PR 23): with several
    requests in the engine, a prompt longer than one prefill chunk is served
    differently from the same prompt alone, because the decode step of the
    other rows writes a masked row's token 0 into position 0 of the blocks of
    a row that is still prefilling (``serve/engine.py::_decode_paged`` hands
    the step every row's block table). The log-probability the program reports
    for its own tokens then leaves the reference's; alone it does not. When the
    program is repaired this test fails, and the serving cells can be added."""
    sizes = {"check_requests": 8, "num_requests": 64,
             "prompt_tokens": {"median": 120, "sigma": 0.3, "min": 70, "max": 160},
             "output_tokens": {"median": 8, "sigma": 0.5, "min": 2, "max": 16}}
    alone = _serve_run("batch-closed-64", tmp_path / "a", clients=1, **sizes)
    crowd = _serve_run("batch-closed-64", tmp_path / "c", clients=6, **sizes)
    assert max(alone["check_numbers"]["served_logprob_gap"]) < 6e-5  # 4-decimal rounding
    assert max(crowd["check_numbers"]["served_logprob_gap"]) > 2e-4
