#!/usr/bin/env python3
"""The control of a cell's output check: the same comparison with the
reference put in the program's place and computed in the nearest precision
below the one the configuration states (``precision.control`` of its file).
It has to come out NOT correct; the benchmark's own runs never run it.

    python3 benchmark/control.py --workload <cell> --seeds 11,12,13 [--seconds 12]

Training cells need no window and no program: the reference's first steps in
the control precision are compared with its float32 steps, on batches cut from
the same seeded shards. Serving cells run the cell for a short window at its
own load, then read, on the same sampled requests, the gap of the token the
control precision puts first beside the gap of the token the program served.
Prints one JSON line per seed; exits 1 if any control came out correct.
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

import numpy as np  # noqa: E402

from benchmark import run as harness  # noqa: E402
from benchmark import synthetic  # noqa: E402


def control_precision(config, kind: str) -> str:
    c = config["precision"]["control"]
    return c if isinstance(c, str) else c["train" if kind == "train_job" else "serve"]


def first_batches(mix, vocab_size: int, seed: int, workdir: str):
    """The first ``checked_steps`` batches straight from the seeded shard."""
    shard_dir = os.path.join(workdir, "shards")
    synthetic.write_token_shards(mix, vocab_size, seed, shard_dir, int(mix["checked_steps"]) + 1)
    with open(os.path.join(shard_dir, "index.json")) as f:
        index = json.load(f)
    flat = np.fromfile(os.path.join(shard_dir, index["files"][0]), dtype=index["dtype"])
    w, b = int(mix["seq_len"]) + 1, int(mix["batch_size"])
    out = []
    for s in range(int(mix["checked_steps"])):
        rows = flat[s * b * w:(s + 1) * b * w].reshape(b, w).astype(np.int32)
        out.append({"inputs": rows[:, :-1], "targets": rows[:, 1:]})
    return out


def train_control(cell, config, mix, seed: int, rehearse: bool, say=print):
    from benchmark.traffic_kinds import train_job

    workdir = tempfile.mkdtemp(prefix="bench_control_")
    try:
        ctx = harness.Context(cell, config, mix, seed, 0.0, False, rehearse, workdir)
        batches = first_batches(mix, int(config["vocab_size"]), seed, workdir)
        want = train_job.reference_steps(ctx, batches, "float32")
        got = train_job.reference_steps(ctx, batches, control_precision(config, mix["kind"]))
        return train_job.compare(got, want, cell["limits"], say)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


def serve_control_inside(numbers, checks) -> bool:
    """Whether the control's readings would pass as correct under the limits
    the run's own ``checks`` carry: it has to fail one of the cell's numbers,
    not each. A number with no limit yet fails nothing."""
    readings = {"served_token_gap": numbers["control_gap"],
                "served_logprob_gap": max(numbers["control_logprob_gap"])}
    return all(checks[name]["limit"] is None or readings[name] <= checks[name]["limit"]
               for name in readings)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True, help="comma-separated")
    p.add_argument("--seconds", type=float, default=12.0)
    p.add_argument("--check-requests", type=int, default=None,
                   help="serving: compare this many finished requests, not the mix's few")
    p.add_argument("--precision", default=None,
                   help="another precision of the reference than the configuration's control")
    p.add_argument("--rehearse", action="store_true")
    args = p.parse_args(argv)
    _, cell, config, mix = harness.load_cell(args.workload, args.rehearse)
    device = None if args.rehearse else harness.check_devices(cell)
    harness.enable_compile_cache()
    precision = args.precision or control_precision(config, mix["kind"])
    any_correct = False
    for seed in (int(s) for s in args.seeds.split(",")):
        if mix["kind"] == "train_job":
            verdict = train_control(cell, config, mix, seed, args.rehearse)
            ok, numbers = verdict["ok"], verdict["numbers"]
        else:
            line = harness.run_cell(args.workload, seed, args.seconds, False,
                                    rehearse=args.rehearse, control_precision=precision,
                                    device=device, mix_overrides=(
                                        {"check_requests": args.check_requests}
                                        if args.check_requests else None))
            numbers = line["check_numbers"]
            ok = serve_control_inside(numbers, line["checks"])
        any_correct = any_correct or ok
        print(json.dumps({"control": precision, "workload": args.workload, "seed": seed,
                          "control_came_out_correct": bool(ok), "numbers": numbers}), flush=True)
    return 1 if any_correct else 0


if __name__ == "__main__":
    sys.exit(main())
