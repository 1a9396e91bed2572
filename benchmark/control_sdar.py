#!/usr/bin/env python3
"""``control_arch.py`` for cells of the traffic kind ``train_job_sdar``: that
file refuses any kind but ``train_job_arch`` by name and feeds the reference
clean batches, so this one registers the architecture (importing the kind),
noises the first batches as the program's loader would (the job's seed, the
batch's index) and runs the same comparison and the same last lines: the
reference in float32 against the reference with every matmul operand rounded
through float8, on the same noise.

    python3 benchmark/control_sdar.py --workload <cell> --seeds 11,12,13

Exits 1 if any control came out correct.
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
from benchmark.traffic_kinds import train_job_arch  # noqa: E402
from benchmark.traffic_kinds import train_job_sdar as kind  # noqa: E402  (registers "sdar_moe")


def noised_first_batches(config, mix, seed: int, workdir: str):
    """The first ``checked_steps`` batches from the seeded shard, ids below
    the MASK id, each with the noise the trainer's loader draws for it."""
    import numpy as np

    from mlx_cuda_distributed_pretraining_tpu.data.block_diffusion import noise_batch

    clean = control.first_batches(mix, int(config["mask_token_id"]), seed, workdir)
    job_seed = int(seed % (2 ** 31))  # train_job.trainer_config's system.seed
    return [kind.reference_batch(noise_batch(
        {"inputs": b["inputs"], "mask": np.ones(b["inputs"].shape, np.float32)}, job_seed, i,
        int(config["block_length"]), float(config["noise_eps"]), int(config["mask_token_id"])))
        for i, b in enumerate(clean)]


def train_control(cell, config, mix, seed: int, precision: str, rehearse: bool = False, say=print):
    workdir = tempfile.mkdtemp(prefix="bench_control_")
    try:
        ctx = harness.Context(cell, config, mix, seed, 0.0, False, rehearse, workdir)
        batches = noised_first_batches(config, mix, seed, workdir)
        want = train_job_arch.reference_steps(ctx, batches, "float32")
        got = train_job_arch.reference_steps(ctx, batches, precision)
        train_job_arch.say_worst_leaves(got, want, say)
        return kind.compare(got, want, cell["limits"], say)
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
    if mix["kind"] != "train_job_sdar":
        raise SystemExit(f"{args.workload} is of kind {mix['kind']!r}: use control.py or control_arch.py")
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
