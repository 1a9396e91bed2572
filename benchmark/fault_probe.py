#!/usr/bin/env python3
"""Witness runs for a fault of the serving path: one closed-loop serving cell
at its own lengths, in a crowd and alone on the same seeds, each run printing
the numbers the output check compares. Never run by ``run.py``.

    python3 benchmark/fault_probe.py --workload internlm2-1_8b.serve-closed \
        --seeds 11,12,13 --clients 64,1 --seconds 12

PERF.md section 7 has what it showed: with prompts longer than one prefill
chunk, ``serve/engine.py::_decode_paged`` writes into rows that still prefill,
so a crowd is served other tokens than one client is.
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
    p.add_argument("--workload", required=True, help="a closed-loop serving cell")
    p.add_argument("--seeds", required=True, help="comma-separated")
    p.add_argument("--clients", default="64,1", help="comma-separated client counts")
    p.add_argument("--seconds", type=float, default=12.0)
    p.add_argument("--check-requests", type=int, default=None,
                   help="compare this many finished requests, not the mix's own count")
    p.add_argument("--precision", default=None, help="also read the control in this precision")
    p.add_argument("--rehearse", action="store_true")
    args = p.parse_args(argv)
    _, cell, _, _ = harness.load_cell(args.workload, args.rehearse)
    device = None if args.rehearse else harness.check_devices(cell)
    over = {"check_requests": args.check_requests} if args.check_requests else {}
    for seed in (int(s) for s in args.seeds.split(",")):
        for clients in (int(c) for c in args.clients.split(",")):
            line = harness.run_cell(args.workload, seed, args.seconds, False,
                                    rehearse=args.rehearse, device=device,
                                    control_precision=args.precision,
                                    mix_overrides=dict(over, clients=clients))
            n = line["check_numbers"]
            print(json.dumps({
                "workload": args.workload, "seed": seed, "clients": clients,
                "finished": line["attempted"], "correct": line["correct"],
                "served_token_gap": n["served_token_gap"], "mean_gap": n["served"]["mean"],
                "flipped_share": n["served"]["flipped_share"],
                "served_logprob_gap": n["served_logprob_gap"], "control_gap": n["control_gap"],
                "control_logprob_gap": n["control_logprob_gap"],
                "serve_out_tokens_per_s": line["end_to_end"].get("serve_out_tokens_per_s"),
                "setup_s": line["end_to_end"]["setup_s"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
