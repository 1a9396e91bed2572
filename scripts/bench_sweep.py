"""Full-step knob sweep for a bench case: run `bench.py --one CASE` under
combinations of the bench env knobs and report each as a matrix row.

The kernel-level sweep (scripts/bench_attention.py) picked the flash
defaults; this sweeps knobs in the context of the FULL train step at a
real scale — where the MFU actually lives (VERDICT r3 item 2):

  FLASH_BLOCK_Q / FLASH_BLOCK_KV   flash kernel tiling
  BENCH_CE_CHUNK                   fused-CE rows per chunk
  BENCH_SCAN_LAYERS                lax.scan stack vs unrolled layers
  BENCH_REMAT                      remat policy (none/dots/full/save_attn)
  BENCH_XLA_FLAGS                  named XLA flag set (parallel/xla_flags.py)

``--mfu`` runs the MFU-campaign matrix instead of the per-case combo
list: the remat-policy x scan x flag-set cross product (axes trimmable
via --remat/--scan/--flags), and folds the graftprof overlap/idle
fractions into the summary table so the flag-set effect on exposed
collectives is visible next to tok/s.

Each combo runs in its own subprocess (a compile hung in C can only be
SIGKILLed, and this parent never touches JAX, so it holds no chip) and
prints a ``BENCHCASE`` line whose case id carries the combo (e.g.
``400m_flash@SCAN=0``). Ordered best-guess-first: a run that fits only
two combos still answers the biggest questions. Exit code 0 = every combo
produced a row.

    python scripts/bench_sweep.py --case 400m_flash [--steps 10]
        [--timeout 600] [--combo FLASH_BLOCK_Q=512,FLASH_BLOCK_KV=1024]
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CASE_MARK = "BENCHCASE "

# Short labels keep the merged case ids readable.
_SHORT = {
    "FLASH_BLOCK_Q": "BQ",
    "FLASH_BLOCK_KV": "BKV",
    "BENCH_CE_CHUNK": "CE",
    "BENCH_SCAN_LAYERS": "SCAN",
    "BENCH_REMAT": "REMAT",
    "BENCH_MEGASTEP": "MEGA",
    "BENCH_XLA_FLAGS": "XLA",
}

# --mfu axes (MFU-campaign sweep). Defaults cover every named remat
# policy (models/llama.py), both layer-stack forms, and both flag sets;
# each axis can be trimmed on the command line.
MFU_REMAT = ["none", "dots", "save_attn", "full"]
MFU_SCAN = ["0", "1"]
MFU_FLAGS = ["none", "latency_hiding"]


def mfu_combos(remat_axis, scan_axis, flags_axis):
    return [
        {"BENCH_REMAT": r, "BENCH_SCAN_LAYERS": s, "BENCH_XLA_FLAGS": f}
        for f in flags_axis for s in scan_axis for r in remat_axis
    ]

# Megastep-first: BENCH_MEGASTEP compiles K steps into one dispatch, so
# the first combo separates host dispatch overhead from chip compute and
# later combos measure their knob on top of megastep, where dispatch
# noise can't mask a small kernel-level win.
# The bare megastep points (2m_mega/100m_mega/400m_mega) are first-class
# bench cases; the sweeps here measure the TUNING knobs on top of them.
DEFAULT_COMBOS = {
    "400m_flash": [
        {"BENCH_MEGASTEP": "10", "BENCH_SCAN_LAYERS": "0"},
        {"BENCH_MEGASTEP": "10", "FLASH_BLOCK_Q": "512", "FLASH_BLOCK_KV": "1024"},
        {"BENCH_MEGASTEP": "10", "FLASH_BLOCK_Q": "512", "FLASH_BLOCK_KV": "512"},
        {"BENCH_MEGASTEP": "10", "BENCH_CE_CHUNK": "4096"},
        {"BENCH_MEGASTEP": "10", "BENCH_CE_CHUNK": "1024"},
        {"BENCH_MEGASTEP": "10", "FLASH_BLOCK_Q": "1024", "FLASH_BLOCK_KV": "1024"},
    ],
    "100m_flash": [
        {"BENCH_MEGASTEP": "10", "BENCH_SCAN_LAYERS": "1"},
        {"BENCH_MEGASTEP": "10", "FLASH_BLOCK_Q": "512", "FLASH_BLOCK_KV": "1024"},
        {"BENCH_MEGASTEP": "10", "BENCH_CE_CHUNK": "4096"},
        {"BENCH_MEGASTEP": "10", "BENCH_REMAT": "dots"},
    ],
}


def parse_combo(text):
    combo = {}
    for part in text.split(","):
        k, _, v = part.partition("=")
        combo[k.strip()] = v.strip()
    return combo


def combo_label(combo):
    return ",".join(f"{_SHORT.get(k, k)}={v}" for k, v in sorted(combo.items()))


_child = None


def _on_term(signum, frame):  # noqa: ARG001
    """An outer `timeout` SIGTERMs only this process; without this handler
    the in-flight bench.py child would be orphaned still holding the chip
    (a chip belongs to one process at a time; a compile hung in C needs
    SIGKILL), starving every later job."""
    if _child is not None and _child.poll() is None:
        _child.kill()
    sys.exit(143)


def main():
    global _child
    signal.signal(signal.SIGTERM, _on_term)
    ap = argparse.ArgumentParser()
    ap.add_argument("--case", required=True)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--timeout", type=int, default=600)
    ap.add_argument("--combo", action="append", default=[],
                    help="K=V[,K=V...] (repeatable; default: built-in list)")
    ap.add_argument("--mfu", action="store_true",
                    help="sweep the MFU-campaign matrix: remat policy x "
                         "scan x xla flag set")
    ap.add_argument("--remat", default=",".join(MFU_REMAT),
                    help="--mfu remat axis (comma list)")
    ap.add_argument("--scan", default=",".join(MFU_SCAN),
                    help="--mfu scan axis (comma list of 0/1)")
    ap.add_argument("--flags", default=",".join(MFU_FLAGS),
                    help="--mfu flag-set axis (comma list)")
    ap.add_argument("--skip-done", default=None,
                    help="out-file from a previous attempt: combos whose "
                         "case id already has a row there are not re-run, "
                         "so a retried sweep resumes instead of restarting")
    a = ap.parse_args()

    if a.mfu:
        combos = ([parse_combo(c) for c in a.combo]
                  or mfu_combos(a.remat.split(","), a.scan.split(","),
                                a.flags.split(",")))
    else:
        combos = ([parse_combo(c) for c in a.combo]
                  or DEFAULT_COMBOS.get(a.case))
    if not combos:
        sys.exit(f"no default combos for case {a.case!r}; pass --combo")

    already = set()
    if a.skip_done and os.path.exists(a.skip_done):
        with open(a.skip_done) as f:
            for ln in f:
                if ln.startswith(CASE_MARK):
                    try:
                        already.add(json.loads(ln[len(CASE_MARK):])["case"])
                    except (json.JSONDecodeError, KeyError):
                        pass

    failures = 0
    rows = []
    for combo in combos:
        label = combo_label(combo)
        if f"{a.case}@{label}" in already:
            print(f"[sweep] {label}: already captured, skipping",
                  file=sys.stderr)
            continue
        # combo values win over --steps so BENCH_STEPS can itself be swept.
        env = {**os.environ, "BENCH_STEPS": str(a.steps), **combo}
        t0 = time.perf_counter()
        _child = subprocess.Popen(
            [sys.executable, os.path.join(REPO, "bench.py"), "--one", a.case],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
        try:
            out, err = _child.communicate(timeout=a.timeout)
            rc = _child.returncode
        except subprocess.TimeoutExpired:
            _child.kill()
            _child.communicate()
            print(f"[sweep] {label}: TIMEOUT after {a.timeout}s", file=sys.stderr)
            failures += 1
            continue
        finally:
            _child = None
        line = next((ln for ln in out.splitlines()
                     if ln.startswith(CASE_MARK)), None)
        if line is None:
            print(f"[sweep] {label}: no result (rc={rc}) "
                  f"{err[-200:]}", file=sys.stderr)
            failures += 1
            continue
        try:
            row = json.loads(line[len(CASE_MARK):])
        except json.JSONDecodeError:
            print(f"[sweep] {label}: truncated result line", file=sys.stderr)
            failures += 1
            continue
        row["case"] = f"{a.case}@{label}"
        row["sweep_combo"] = combo
        rows.append(row)
        print(CASE_MARK + json.dumps(row), flush=True)
        print(f"[sweep] {label}: tok_s={row.get('tok_s')} mfu={row.get('mfu')}"
              f" ({time.perf_counter() - t0:.0f}s)", file=sys.stderr)
    if rows:
        print_table(rows)
    sys.exit(1 if failures else 0)


def print_table(rows):
    """Aligned sweep summary on stderr. The graftprof fraction columns
    (prof_* from bench.py's in-run profile) appear whenever any row has
    them — overlap_frac next to tok/s is how a flag set proves it moved
    collectives off the critical path, not just the step time."""
    cols = ["case", "tok_s", "mfu"]
    for c in ("prof_compute_frac", "prof_comm_frac", "prof_overlap_frac",
              "prof_idle_frac"):
        if any(c in r for r in rows):
            cols.append(c)
    head = [c.replace("prof_", "") for c in cols]
    table = [head] + [
        ["" if r.get(c) is None else str(r.get(c, "")) for c in cols]
        for r in rows]
    widths = [max(len(row[i]) for row in table) for i in range(len(cols))]
    for row in table:
        print("[sweep] " + "  ".join(v.ljust(w) for v, w in zip(row, widths)),
              file=sys.stderr)


if __name__ == "__main__":
    main()
