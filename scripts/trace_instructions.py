#!/usr/bin/env python3
"""Device self time of every HLO instruction under one scope of a recorded trace.

    python scripts/trace_instructions.py <file.xplane.pb> [--scope moe_experts] [--top 40]

``benchmark/trace_scopes.py`` sums a trace by scope; this splits one scope (the
innermost vocabulary name of an operation's name stack, as there; a name the
closed vocabulary lacks, such as ``kda_proj``, is taken wherever it stands in
the stack, as ``benchmark/layer_metrics/_named_scopes.py`` reads it) by what the
instructions are: a row for every (pass, the jax primitive the name stack ends
in, the instruction's result shapes), with its events a step and its self time
a step over the whole steps of the trace. ``pass`` is ``recomputed`` under
``rematted_computation``, else ``backward`` under a ``transpose(``, else
``forward``. A fusion carries the name stack of its root, so a row is a
fusion's whole time under its root's primitive. The rows add up to the scope's
line in ``trace_scopes.py``'s table (to the ``step_device_ms.<scope>`` row for a
name outside the vocabulary).
"""

from __future__ import annotations

import argparse
import collections
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import trace_reduce, trace_scopes  # noqa: E402

_SHAPE = re.compile(r"\b([a-z]+\d*\[[\d,]*\])")


def passes_of(op_name: str) -> str:
    if trace_scopes.is_recompute(op_name):
        return "recomputed"
    return "backward" if "transpose(" in op_name else "forward"


def result_shapes(text: str) -> str:
    """``%fusion.1 = (bf16[8,4]{1,0}, f32[8]{0}) fusion(...)`` -> ``bf16[8,4] f32[8]``."""
    head = text.partition(" = ")[2]
    depth = 0
    for i, ch in enumerate(head):
        depth += ch == "("
        depth -= ch == ")"
        if ch == " " and depth == 0:
            head = head[:i]
            break
    return " ".join(_SHAPE.findall(head)) or "?"


def rows_of(path: str, scope: str):
    """``(steps, {(pass, primitive, shapes): [events, seconds]})`` of one device plane."""
    planes = trace_scopes.read_planes(path)
    devs = [p for p in planes["devices"] if p["lines"].get(trace_reduce.STEPS_LINE)]
    if not devs:
        raise SystemExit("no device plane with a Steps line in this trace")
    p = devs[0]
    steps = p["lines"][trace_reduce.STEPS_LINE]
    lo, hi = min(s for _, s, _ in steps), max(e for _, _, e in steps)
    ops = [(m, max(s, lo), min(e, hi)) for m, s, e in p["lines"].get(trace_reduce.OPS_LINE, [])
           if min(e, hi) > max(s, lo)]
    rows = collections.defaultdict(lambda: [0, 0.0])
    if scope in trace_scopes.VOCABULARY:
        inside = lambda op_name: trace_scopes.scope_of(op_name) == scope
    else:
        inside = lambda op_name: scope in trace_scopes._SPLIT.split(op_name)
    for m, t in trace_reduce.self_times(ops):
        rec = p["events"].get(m, {})
        op_name = rec.get("tf_op") or ""
        if not inside(op_name):
            continue
        primitive = op_name.rstrip(":/").rpartition("/")[2]
        row = rows[(passes_of(op_name), primitive, result_shapes(rec.get("name", "")))]
        row[0] += 1
        row[1] += t
    return len(steps), rows


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("trace")
    p.add_argument("--scope", default="moe_experts")
    p.add_argument("--top", type=int, default=40)
    a = p.parse_args()
    steps, rows = rows_of(a.trace, a.scope)
    total = sum(t for _, t in rows.values())
    print(f"{a.scope}: {1e3 * total / steps:.3f} ms/step over {steps} whole step(s), "
          f"{sum(n for n, _ in rows.values()) / steps:.1f} events/step")
    by_pass = collections.Counter()
    for (which, _, _), (_, t) in rows.items():
        by_pass[which] += t
    for which, t in by_pass.most_common():
        print(f"  {which:10s} {1e3 * t / steps:9.3f} ms/step")
    for (which, primitive, shapes), (n, t) in sorted(rows.items(), key=lambda kv: -kv[1][1])[:a.top]:
        print(f"{1e3 * t / steps:9.3f} ms/step {n / steps:7.1f} events/step  {which:10s} "
              f"{primitive:28s} {shapes}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
