"""graftlint CLI.

    python -m mlx_cuda_distributed_pretraining_tpu.analysis.lint [paths...]

Lints ``paths`` (files or directories; default: the package itself) with
every registered rule, subtracts inline suppressions and the committed
baseline, and exits nonzero when any NEW finding remains. ``--format
json`` emits one machine-readable document (used by the tests);
``--write-baseline`` regenerates the baseline from the
current findings, preserving the reasons of entries that still match.

Stale-baseline hygiene: a full-package run that finds baseline entries
matching nothing (the grandfathered finding was fixed) exits nonzero
with a ``--prune-stale`` hint; ``--prune-stale`` rewrites the baseline
without them, so baseline.json cannot rot. Partial-path runs skip the
stale gate — entries for files outside the linted set are out of scope,
not stale.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import List, Sequence

from .core import (
    PACKAGE_NAME,
    all_rules,
    default_baseline_path,
    load_baseline,
    result_to_json,
    run_lint,
    write_baseline,
    write_baseline_entries,
)


def _default_paths() -> List[str]:
    return [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]


def _covers_package(paths: Sequence[str]) -> bool:
    """True when the linted paths include the whole package — only then
    is an unmatched baseline entry evidence of a fixed finding rather
    than an out-of-scope file."""
    pkg = os.path.abspath(_default_paths()[0])
    for p in paths:
        ap = os.path.abspath(p)
        if ap == pkg or pkg.startswith(ap + os.sep):
            return True
    return False


def _prune_stale(baseline_path: str, baseline, stale,
                 tool: str = "graftlint") -> int:
    """Rewrite the baseline minus the stale entries (multiset removal on
    (rule, path, message); surviving entries keep their reasons)."""
    drop = {}
    for e in stale:
        k = (e.get("rule"), e.get("path"), e.get("message"))
        drop[k] = drop.get(k, 0) + 1
    kept = []
    for e in baseline:
        k = (e.get("rule"), e.get("path"), e.get("message"))
        if drop.get(k, 0) > 0:
            drop[k] -= 1
        else:
            kept.append(e)
    write_baseline_entries(baseline_path, kept, tool=tool)
    return len(baseline) - len(kept)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog=f"python -m {PACKAGE_NAME}.analysis.lint",
        description="JAX-aware static analysis "
                    "(recompile/RNG/host-sync/donation rules)")
    ap.add_argument("paths", nargs="*", help="files or directories "
                    f"(default: the {PACKAGE_NAME} package)")
    ap.add_argument("--format", choices=("text", "json"), default="text")
    ap.add_argument("--baseline", default=None,
                    help=f"baseline file (default: {default_baseline_path()})")
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

    rules = all_rules()
    if args.list_rules:
        for rid in sorted(rules):
            print(f"{rid}: {' '.join(rules[rid].description.split())}")
        return 0

    paths = args.paths or _default_paths()
    missing = [p for p in paths if not os.path.exists(p)]
    if missing:
        print(f"graftlint: no such path: {', '.join(missing)}",
              file=sys.stderr)
        return 2

    baseline_path = args.baseline or default_baseline_path()
    baseline = [] if args.no_baseline else load_baseline(baseline_path)
    result = run_lint(paths, baseline=baseline)

    if args.write_baseline:
        write_baseline(baseline_path, result.findings, old_entries=baseline)
        print(f"graftlint: wrote {len(result.findings)} finding(s) to "
              f"{baseline_path}", file=sys.stderr)
        return 0

    stale_gate = False
    if result.stale_baseline and not args.no_baseline \
            and _covers_package(paths):
        if args.prune_stale:
            n = _prune_stale(baseline_path, baseline, result.stale_baseline)
            print(f"graftlint: pruned {n} stale baseline entr"
                  f"{'y' if n == 1 else 'ies'} from {baseline_path}",
                  file=sys.stderr)
            result.stale_baseline = []
        else:
            stale_gate = True

    if args.format == "json":
        print(json.dumps(result_to_json("graftlint", result)))
        if stale_gate:
            print("graftlint: stale baseline entries — run --prune-stale",
                  file=sys.stderr)
    else:
        for f in result.new:
            print(f"{f.path}:{f.line}:{f.col}: [{f.rule}] {f.message}")
        for e in result.stale_baseline:
            print(f"{'error' if stale_gate else 'note'}: stale baseline "
                  f"entry (fixed?): [{e.get('rule')}] {e.get('path')} — "
                  f"{e.get('message')}", file=sys.stderr)
        if stale_gate:
            print("graftlint: baseline has stale entries — run "
                  f"`python -m {PACKAGE_NAME}.analysis.lint --prune-stale` "
                  "to drop them", file=sys.stderr)
        summary = (f"graftlint: {len(result.new)} new, "
                   f"{len(result.baselined)} baselined, "
                   f"{len(result.suppressed)} suppressed")
        print(summary, file=sys.stderr)
    return 1 if (result.new or stale_gate) else 0


if __name__ == "__main__":
    sys.exit(main())
