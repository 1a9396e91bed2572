#!/usr/bin/env python3
"""Find an open-loop cell's knee once: the same cell at a few fixed rates in
one process, each with its own short window, printing the tails and how many
requests were in the system when the window opened and closed. The knee is the
highest rate at which that backlog does not grow over the window (and nearly
every first token still comes within a stated limit); the cell's
traffic file then states 0.8 of it as a number. Run when the cell is defined,
and again by a later benchmark PR once an optimisation has moved the knee.

    python3 benchmark/sweep.py --workload <cell> --rates 3,4,5,6,7,8 --seconds 30
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run as harness  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", required=True, help="comma-separated requests/s")
    p.add_argument("--seconds", type=float, default=30.0)
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--ttft-limit-ms", type=float, default=200.0,
                   help="print the share of requests whose first token came within this")
    p.add_argument("--rehearse", action="store_true")
    args = p.parse_args(argv)
    bench = harness._load(os.path.join(ROOT, "BENCHMARK.json"))
    device = None if args.rehearse else harness.check_devices(
        harness.find_cell(bench, args.workload))
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        line = harness.run_cell(args.workload, args.seed + i, args.seconds, False,
                                rehearse=args.rehearse, device=device,
                                mix_overrides={"rate_per_s": rate})
        b = line["backlog"] or [None]
        print(json.dumps({
            "rate_per_s": rate, "seed": args.seed + i, "attempted": line["attempted"],
            "failed": line["failed"], "correct": line["correct"],
            "ttft_p95_ms": line["end_to_end"]["ttft_p95_ms"],
            "itl_p95_ms": line["end_to_end"]["itl_p95_ms"],
            f"share_ttft_within_{args.ttft_limit_ms:g}_ms":
                sum(t <= args.ttft_limit_ms for t in line["ttft_ms"]) / len(line["ttft_ms"]),
            "in_system_at_window_start": b[0], "in_system_at_window_end": b[-1],
            "in_system_highest": max(x for x in b if x is not None) if b[0] is not None else None,
            "setup_s": line["end_to_end"]["setup_s"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
