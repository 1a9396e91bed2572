"""The output check has to be able to fail.

Two kinds of test, each at the tiny widths of ``rehearse.json``: the training
cells under their own limits, the serving cells under the ``rehearse_limits``
of their kind (float32 on the CPU serves the reference's own tokens). The
control: the reference in the next lower precision, put in the program's
place, comes out NOT correct. The broken run: everything a run does after the
look for a chip, with the timed path broken underneath (a train step that
never moves the weights; an engine that alters tokens where it emits them),
reports ``correct: false``; the same run unbroken reports true. The serving
cells are not declared yet (PERF.md section 7): their entries wait in
``pending/serving-long.json`` and the tests here append them to the bench
dict themselves. The last test pins the fault of the program that keeps them
out, which is why the sound runs before it serve one request at a time.
"""

import json
import os

import jax
import jax.numpy as jnp
import pytest

from benchmark import control, run as harness

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCHMARK_JSON = os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")
with open(BENCHMARK_JSON) as _f:
    DECLARED = json.load(_f)
with open(os.path.join(BENCH, "pending", "serving-long.json")) as _f:
    WAITING = json.load(_f)
CELLS = [w["name"] for w in DECLARED["workloads"]]
TRAIN_CELLS = [c for c in CELLS if harness._load(os.path.join(
    BENCH, "traffic", harness._load(os.path.join(BENCH, "workloads", c + ".json"))["traffic"]
    + ".json"))["kind"] == "train_job"]
SERVE_CELLS = [w["name"] for w in WAITING["workloads"] if w["name"] not in CELLS]
CLOSED_CELL = "internlm2-1_8b.serve-closed"
# one request at a time: see test_concurrent_prefill_changes_served_tokens
ALONE = {"clients": 1, "rate_per_s": 1.0}


@pytest.fixture
def waiting_declared(monkeypatch):
    """The harness reads a ``BENCHMARK.json`` that also holds the waiting
    entries, as it will once they are declared."""
    load = harness._load

    def merged(path):
        got = load(path)
        if os.path.abspath(path) == os.path.abspath(BENCHMARK_JSON):
            for key in ("configs", "workloads", "end_to_end", "per_layer"):
                got[key] = got[key] + [e for e in WAITING[key]
                                       if e["name"] not in {x["name"] for x in got[key]}]
        return got

    monkeypatch.setattr(harness, "_load", merged)


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


def _serve_run(name, seed=5, **mix_over):
    """A waiting serving cell at tiny widths through everything a run does
    after the look for a chip; ``mix_over`` changes numbers of its mix."""
    return harness.run_cell(name, seed, 2.0, False, rehearse=True, quiet=True,
                            mix_overrides=mix_over)


@pytest.mark.parametrize("name", SERVE_CELLS)
def test_serving_run_sound_is_correct_and_altered_tokens_are_not(name, waiting_declared,
                                                                 monkeypatch):
    from mlx_cuda_distributed_pretraining_tpu.serve.engine import BatchEngine

    sound = _serve_run(name, **ALONE)
    assert sound["correct"] and sound["failed"] == 0, sound["check_numbers"]
    assert sound["checks"]["served_token_gap"] == {"value": 0.0, "limit": 0.0}
    emit = BatchEngine._emit

    def altered(self, req, tok, lp):  # every third token replaced where it is produced
        if len(req.tokens) % 3 == 2:
            tok = (int(tok) + 7) % self.args.vocab_size
        return emit(self, req, tok, lp)

    monkeypatch.setattr(BatchEngine, "_emit", altered)
    broken = _serve_run(name, **ALONE)
    assert not broken["correct"], broken["check_numbers"]
    assert broken["check_numbers"]["served_token_gap"] > 0.01


@pytest.mark.parametrize("name", SERVE_CELLS)
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_serving_control_comes_out_not_correct(name, seed, waiting_declared):
    """On the same sampled requests, float8 operands fail one of the cell's
    numbers: the tokens they put first lie further below the reference's best
    than the limit allows, or their log-probabilities of the served tokens
    leave the reference's by more than the program's may."""
    _, _, config, mix = harness.load_cell(name, rehearse=True)
    precision = control.control_precision(config, mix["kind"])
    assert precision == "fp8"
    line = harness.run_cell(name, seed, 2.0, False, rehearse=True, quiet=True,
                            control_precision=precision, mix_overrides=ALONE)
    assert line["correct"], line["check_numbers"]  # the program itself is sound
    assert not control.serve_control_inside(line["check_numbers"], line["checks"])


def test_a_number_with_no_limit_is_never_inside(waiting_declared, monkeypatch):
    """The waiting cells' records state no limits (they are read on the
    repaired engine): without the rehearsal's own, a sound run is not correct."""
    from benchmark.traffic_kinds import serving

    monkeypatch.setattr(serving, "limits_of", lambda ctx: {})
    run = _serve_run(CLOSED_CELL, **ALONE)
    assert not run["correct"]
    assert run["checks"]["served_token_gap"] == {"value": 0.0, "limit": None}
    assert control.serve_control_inside({"control_gap": 9.0, "control_logprob_gap": [9.0]},
                                        run["checks"])  # and the control fails nothing


def test_concurrent_prefill_changes_served_tokens(waiting_declared):
    """Why the serving cells wait (PERF.md section 7, first entry): with
    several requests in the engine, a prompt longer than one prefill chunk is
    served differently from the same prompt alone, because the decode step of
    the other rows writes a masked row's token 0 into position 0 of the blocks
    of a row that is still prefilling (``serve/engine.py::_decode_paged`` hands
    the step every row's block table). The log-probability the program reports
    for its own tokens then leaves the reference's; alone it does not. When
    the program is repaired this test fails: rename it
    ``..._does_not_change_...``, hold the crowd to the alone limit, and drop
    ``ALONE`` from the tests above."""
    sizes = {"check_requests": 8, "num_requests": 64,
             "prompt_tokens": {"median": 120, "sigma": 0.3, "min": 70, "max": 160},
             "output_tokens": {"median": 8, "sigma": 0.5, "min": 2, "max": 16}}
    alone = _serve_run(CLOSED_CELL, clients=1, **sizes)
    crowd = _serve_run(CLOSED_CELL, clients=6, **sizes)
    assert max(alone["check_numbers"]["served_logprob_gap"]) < 6e-5  # 4-decimal rounding
    assert max(crowd["check_numbers"]["served_logprob_gap"]) > 2e-4
    assert alone["correct"] and not crowd["correct"]  # and the check sees it
