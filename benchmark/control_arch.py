#!/usr/bin/env python3
"""``control.py`` for cells of the traffic kind ``train_job_arch``: the same
comparison with the architecture's reference put in the program's place and
computed in the nearest precision below the one the configuration states. It
has to come out NOT correct; the benchmark's own runs never run it.

    python3 benchmark/control_arch.py --workload <cell> --seeds 11,12,13

(``control.py`` sends any kind but ``train_job`` down its serving branch, so
this kind's control is a file of its own; the batches, the comparison and the
last line are that file's.) Exits 1 if any control came out correct.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import control  # noqa: E402
from benchmark import run as harness  # noqa: E402


def train_control(cell, config, mix, seed: int, precision: str, rehearse: bool = False, say=print):
    from benchmark.traffic_kinds import train_job, train_job_arch

    workdir = tempfile.mkdtemp(prefix="bench_control_")
    try:
        ctx = harness.Context(cell, config, mix, seed, 0.0, False, rehearse, workdir)
        batches = control.first_batches(mix, int(config["vocab_size"]), seed, workdir)
        want = train_job_arch.reference_steps(ctx, batches, "float32")
        got = train_job_arch.reference_steps(ctx, batches, precision)
        train_job_arch.say_worst_leaves(got, want, say)
        return train_job.compare(got, want, cell["limits"], say)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True, help="comma-separated")
    p.add_argument("--precision", default=None,
                   help="another precision of the reference than the configuration's control")
    args = p.parse_args(argv)
    _, cell, config, mix = harness.load_cell(args.workload)
    if mix["kind"] != "train_job_arch":
        raise SystemExit(f"{args.workload} is of kind {mix['kind']!r}: use control.py")
    harness.check_devices(cell)
    harness.enable_compile_cache()
    precision = args.precision or config["precision"]["control"]
    any_correct = False
    for seed in (int(s) for s in args.seeds.split(",")):
        verdict = train_control(cell, config, mix, seed, precision)
        any_correct = any_correct or verdict["ok"]
        print(json.dumps({"control": precision, "workload": args.workload, "seed": seed,
                          "control_came_out_correct": bool(verdict["ok"]),
                          "numbers": verdict["numbers"]}), flush=True)
    return 1 if any_correct else 0


if __name__ == "__main__":
    sys.exit(main())
