#!/usr/bin/env python3
"""``control_arch.py`` for cells of the traffic kind ``train_job_sambay``:
that file refuses any kind but ``train_job_arch`` by name, so this one
registers the architecture (importing the kind) and runs the same
``train_control`` and the same last lines.

    python3 benchmark/control_sambay.py --workload <cell> --seeds 11,12,13

Exits 1 if any control came out correct.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import control_arch  # noqa: E402
from benchmark import run as harness  # noqa: E402
from benchmark.traffic_kinds import train_job_sambay  # noqa: E402,F401  (registers "sambay")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True, help="comma-separated")
    p.add_argument("--precision", default=None,
                   help="another precision of the reference than the configuration's control")
    args = p.parse_args(argv)
    _, cell, config, mix = harness.load_cell(args.workload)
    if mix["kind"] != "train_job_sambay":
        raise SystemExit(f"{args.workload} is of kind {mix['kind']!r}: use control.py or control_arch.py")
    harness.check_devices(cell)
    harness.enable_compile_cache()
    precision = args.precision or config["precision"]["control"]
    any_correct = False
    for seed in (int(s) for s in args.seeds.split(",")):
        verdict = control_arch.train_control(cell, config, mix, seed, precision)
        any_correct = any_correct or verdict["ok"]
        print(json.dumps({"control": precision, "workload": args.workload, "seed": seed,
                          "control_came_out_correct": bool(verdict["ok"]),
                          "numbers": verdict["numbers"]}), flush=True)
    return 1 if any_correct else 0


if __name__ == "__main__":
    sys.exit(main())
