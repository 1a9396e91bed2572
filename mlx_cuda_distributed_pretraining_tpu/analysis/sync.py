"""graftsync CLI — thread-ownership & lock-discipline gate.

    python -m mlx_cuda_distributed_pretraining_tpu.analysis.sync [paths...]

Checks ``paths`` (files or directories; default: the package itself)
against the concurrency contracts declared in source (``# graftsync:
owner=...`` / ``guarded-by=...`` annotations — see ``sync_rules``),
subtracts ``# graftsync: disable=`` inline suppressions and the
committed ``sync_baseline.json``, and exits nonzero when any NEW finding
remains. Flags, exit codes, JSON schema, and stale-baseline hygiene are
identical to graftlint's CLI — one triage workflow for both gates.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import List

from .core import (
    PACKAGE_NAME,
    load_baseline,
    result_to_json,
    run_lint,
    write_baseline,
)
from .lint import _covers_package, _default_paths, _prune_stale
from .sync_rules import SYNC_SUPPRESS_RE, all_sync_rules


def default_sync_baseline_path() -> str:
    return os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "sync_baseline.json")


def run_sync(paths: List[str], baseline=None):
    """In-process entry point (the tests'): graftlint's
    runner with the sync rule registry and the graftsync comment tag."""
    return run_lint(paths, baseline=baseline, rules=all_sync_rules(),
                    suppress_re=SYNC_SUPPRESS_RE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog=f"python -m {PACKAGE_NAME}.analysis.sync",
        description="host-side concurrency static analysis "
                    "(thread ownership / lock guards / blocking-under-lock "
                    "/ lock-order cycles)")
    ap.add_argument("paths", nargs="*", help="files or directories "
                    f"(default: the {PACKAGE_NAME} package)")
    ap.add_argument("--format", choices=("text", "json"), default="text")
    ap.add_argument("--baseline", default=None,
                    help="baseline file "
                    f"(default: {default_sync_baseline_path()})")
    ap.add_argument("--no-baseline", action="store_true",
                    help="ignore the baseline: every finding is new")
    ap.add_argument("--write-baseline", action="store_true",
                    help="regenerate the baseline from current findings "
                    "(keeps reasons of entries that still match) and exit 0")
    ap.add_argument("--prune-stale", action="store_true",
                    help="rewrite the baseline without entries that no "
                    "longer match any finding, then exit by the usual rules")
    ap.add_argument("--list-rules", action="store_true")
    args = ap.parse_args(argv)

    rules = all_sync_rules()
    if args.list_rules:
        for rid in sorted(rules):
            print(f"{rid}: {' '.join(rules[rid].description.split())}")
        return 0

    paths = args.paths or _default_paths()
    missing = [p for p in paths if not os.path.exists(p)]
    if missing:
        print(f"graftsync: no such path: {', '.join(missing)}",
              file=sys.stderr)
        return 2

    baseline_path = args.baseline or default_sync_baseline_path()
    baseline = [] if args.no_baseline else load_baseline(baseline_path)
    result = run_sync(paths, baseline=baseline)

    if args.write_baseline:
        write_baseline(baseline_path, result.findings, old_entries=baseline,
                       tool="graftsync")
        print(f"graftsync: wrote {len(result.findings)} finding(s) to "
              f"{baseline_path}", file=sys.stderr)
        return 0

    stale_gate = False
    if result.stale_baseline and not args.no_baseline \
            and _covers_package(paths):
        if args.prune_stale:
            n = _prune_stale(baseline_path, baseline, result.stale_baseline,
                             tool="graftsync")
            print(f"graftsync: pruned {n} stale baseline entr"
                  f"{'y' if n == 1 else 'ies'} from {baseline_path}",
                  file=sys.stderr)
            result.stale_baseline = []
        else:
            stale_gate = True

    if args.format == "json":
        print(json.dumps(result_to_json("graftsync", result)))
        if stale_gate:
            print("graftsync: stale baseline entries — run --prune-stale",
                  file=sys.stderr)
    else:
        for f in result.new:
            print(f"{f.path}:{f.line}:{f.col}: [{f.rule}] {f.message}")
        for e in result.stale_baseline:
            print(f"{'error' if stale_gate else 'note'}: stale baseline "
                  f"entry (fixed?): [{e.get('rule')}] {e.get('path')} — "
                  f"{e.get('message')}", file=sys.stderr)
        if stale_gate:
            print("graftsync: baseline has stale entries — run "
                  f"`python -m {PACKAGE_NAME}.analysis.sync --prune-stale` "
                  "to drop them", file=sys.stderr)
        summary = (f"graftsync: {len(result.new)} new, "
                   f"{len(result.baselined)} baselined, "
                   f"{len(result.suppressed)} suppressed")
        print(summary, file=sys.stderr)
    return 1 if (result.new or stale_gate) else 0


if __name__ == "__main__":
    sys.exit(main())
