#!/usr/bin/env python3
"""Does a late step account for itself? Induce five delays in a benchmark
cell and print what the trainer's step records say of each.

    python3 scripts/stall_probe.py --workload <cell> [--seed N] [--seconds S]
        [--kinds sleep,busy,gc,stop,neighbours | --kinds none] [--trace 1]
        [--memory-stats] [--cost]

Runs the cell through ``benchmark.run.run_cell`` (imported, nothing edited)
with a ``wrap_step`` that, at chosen steps of the timed window and before the
call into the step, does one of:

    sleep       ``time.sleep(2)``: the loop's thread waits, the process idles
    busy        a 2 s busy loop on the loop's thread
    gc          ``gc.collect()`` over a heap of millions of cyclic objects
                (built during set-up), then its release and a second collection
    stop        a child sends this process SIGSTOP, and SIGCONT 2 s later
    neighbours  as many busy child processes as the machine has CPUs, for 5 s

and then prints, for each disturbed step and an undisturbed one, the fields of
its ``step_window`` event (``slow`` is the step's own record with a step a
window) and whether the trainer raised a ``step_stall`` event for it.
``--kinds none`` disturbs nothing: the run is the cell's own, and what is
printed is every ``step_stall`` the trainer raised by itself, and the share of
the window its steps spent over their median, from the program's events
(the declared metric ``step_stall_pct``) and from the harness's own stamps
around the step call. ``--memory-stats`` first times ``memory_stats()``
against a device that is busy: the allocator's counters ask the runtime and do
not wait for the device. ``--cost`` times, on this host and with the device
live, what the records add to a step of the loop. Like ``run.py`` it fails
without a chip, unless ``--rehearse``.
"""

from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import os
import statistics
import subprocess
import sys
import time
from typing import Any, Dict, List, Optional, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from mlx_cuda_distributed_pretraining_tpu.obs.events import events_path, iter_events  # noqa: E402
from mlx_cuda_distributed_pretraining_tpu.obs.steprecord import ANNOTATED  # noqa: E402

KINDS = ("sleep", "busy", "gc", "stop", "neighbours")
GAP = 3             # steps from one disturbance to the next
DELAY_S = 2.0       # of sleep, busy and stop
HEAP_OBJECTS = 12_000_000
_BUSY_CHILD = "import time\nt = time.time() + {s}\nwhile time.time() < t: pass\n"
_STOP_CHILD = ("import os, signal, time\np = os.getppid()\nos.kill(p, signal.SIGSTOP)\n"
               "time.sleep({s})\nos.kill(p, signal.SIGCONT)\n")


class Probe:
    """The ``wrap_step``: counts the calls into the step (the trainer's step
    numbers) and disturbs the chosen ones."""

    def __init__(self, kinds: Tuple[str, ...]):
        self.kinds = kinds
        self.n = 0
        self.plan: Dict[int, str] = {}
        self.events: List[Dict[str, Any]] = []
        self.timed: List[Dict[str, float]] = []   # the harness's stamps of the window's steps
        self.trace_span: Optional[List[float]] = None   # where a traced run's profiler began
        self.children: List[subprocess.Popen] = []
        self.heap: List[list] = []

    def wrap(self, rec):
        """``rec`` is the harness's own wrapper around ``trainer.train_step``."""
        first = rec.first_timed + 2
        self.plan = {first + GAP * i: kind for i, kind in enumerate(self.kinds)}
        if "gc" in self.kinds:
            gc.disable()          # the heap is built without the collector walking it
            for _ in range(HEAP_OBJECTS):
                cell: list = []
                cell.append(cell)
                self.heap.append(cell)
            gc.enable()
            gc.collect()          # into the oldest generation, during set-up

        def step(state, batch):
            self.n += 1
            kind = self.plan.get(self.n)
            if kind:
                getattr(self, "_" + kind)()
            try:
                return rec(state, batch)
            except Exception:  # the harness ends the job by raising from the step
                self.events = list(iter_events(events_path(rec.tr.run_dir)))
                self.timed = rec.timed()
                self.trace_span = rec.trace_span
                raise

        return step

    def _sleep(self) -> None:
        time.sleep(DELAY_S)

    def _busy(self) -> None:
        end = time.perf_counter() + DELAY_S
        while time.perf_counter() < end:
            pass

    def _gc(self) -> None:
        gc.collect()
        self.heap.clear()
        gc.collect()

    def _stop(self) -> None:
        self.children.append(subprocess.Popen(
            [sys.executable, "-c", _STOP_CHILD.format(s=DELAY_S)]))

    def _neighbours(self) -> None:
        for _ in range(os.cpu_count() or 1):
            self.children.append(subprocess.Popen(
                [sys.executable, "-c", _BUSY_CHILD.format(s=5)]))

    def reap(self) -> None:
        for child in self.children:
            child.wait(timeout=30)


COLUMNS = ("step", "wall_s", "x_median", "dispatch_s", "thread_cpu_s", "proc_cpu_s", "nivcsw",
           "gc_n", "gc_s", "busy_s", "steal_s", "psi_cpu_us", "allocs", "stall")


def row_of(event: Dict[str, Any], stalled: bool) -> Dict[str, Any]:
    """One table row: the step's own record, then the window's counters."""
    row = dict(event.get("slow", {}))
    row.update({k: event.get(k) for k in ("gc_n", "gc_s")})
    row.update({k: event.get("machine", {}).get(k) for k in ("busy_s", "steal_s", "psi_cpu_us")})
    row["allocs"] = event.get("hbm", {}).get("allocs")
    row["stall"] = "yes" if stalled else "no"
    return row


def table(probe: Probe) -> List[Dict[str, Any]]:
    windows = {int(e["step"]): e for e in probe.events if e.get("type") == "step_window"}
    stalled = {int(e["step"]) for e in probe.events if e.get("type") == "step_stall"}
    rows = []
    for step, kind in sorted(probe.plan.items()):
        last = step + (5 if kind == "neighbours" else 0)  # neighbours stay for several steps
        for s in range(step, last + 1):
            if s in windows:
                rows.append({"what": kind, **row_of(windows[s], s in stalled)})
    clean = min(probe.plan) - 1
    if clean in windows:
        rows.insert(0, {"what": "clean", **row_of(windows[clean], clean in stalled)})
    return rows


def stall_shares(probe: Probe) -> Dict[str, Optional[float]]:
    """``step_stall_pct`` of the window twice: from the program's events (the
    declared reader, over the harness's window) and the same sum over the
    harness's own stamps around the step call. In a traced run both leave out
    the step in which the harness started its profiler, as the reader does."""
    from benchmark import run as brun

    if len(probe.timed) < 2:
        return {}
    window = (probe.timed[0]["t0"], probe.timed[-1]["t1"])
    events = [e for e in probe.events if e.get("type") == "step_window"
              and probe.timed[0]["i"] <= int(e["step"]) <= probe.timed[-1]["i"]]
    spec = importlib.util.spec_from_file_location(
        "step_stall_pct", os.path.join(brun.HERE, "layer_metrics", "step_stall_pct.py"))
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    sources = {"step_window_events": events, "window": window, "timed_steps": probe.timed,
               "trace_span": probe.trace_span}
    own = {int(e["step"]) for e in reader.own_events(sources, "step_s_max")}
    took = [s["t1"] - s["t0"] for s in probe.timed if s["i"] in own]
    median = statistics.median(took)
    return {"program": reader.read(sources),
            "harness": 100.0 * sum(max(0.0, t - median) for t in took) / (window[1] - window[0]),
            "steps": len(took), "median_step_ms": 1e3 * median, "max_step_ms": 1e3 * max(took)}


def memory_stats_probe() -> None:
    """Time ``memory_stats()`` of every local device while the device works."""
    import jax
    import jax.numpy as jnp

    from mlx_cuda_distributed_pretraining_tpu.obs import hoststats

    @jax.jit
    def work(x):
        return jax.lax.fori_loop(0, 200, lambda _, a: (a @ a) * 1e-4, x)

    x = jnp.ones((8192, 8192), jnp.bfloat16)
    jax.block_until_ready(work(x))
    t0 = time.perf_counter()
    y = work(x)
    t1 = time.perf_counter()
    stats = hoststats.hbm_totals()
    t2 = time.perf_counter()
    jax.block_until_ready(y)
    t3 = time.perf_counter()
    print(json.dumps({"memory_stats_probe": {
        "dispatch_s": t1 - t0, "memory_stats_s": t2 - t1, "device_busy_after_s": t3 - t2,
        "keys": sorted(jax.local_devices()[0].memory_stats() or {}), "hbm": stats}}), flush=True)


def cost_probe(n: int = 2000) -> None:
    """Microseconds a step of what the records add to the loop: the same calls
    in the same order, the event's growth encoded as the event log would."""
    import jax

    from mlx_cuda_distributed_pretraining_tpu.obs import compiles, hoststats, steprecord
    from mlx_cuda_distributed_pretraining_tpu.obs.trace import Tracer

    tracer = Tracer("probe", enabled=False)
    recs = steprecord.StepRecords()
    seen = hoststats.window_totals()
    parts = {"record": 0.0, "window_reads": 0.0, "encode": 0.0}
    for i in range(n):
        t0 = time.perf_counter()
        rec = recs.turn(i, False, compiles.totals()[0], 0.0) or dict.fromkeys(ANNOTATED, 0)
        recs.note(data_get_s=1e-4, queue_depth=2)
        recs.note(dispatch_s=0.9)
        recs.note(loss_sync_s=1e-4)
        recs.note(log_window_s=3e-4)
        with jax.profiler.TraceAnnotation("train.step_record", **{k: rec[k] for k in ANNOTATED}):
            pass
        with tracer.phase("train.step_close", step=i):
            pass
        time.perf_counter()   # the stamp before the loop's capture block
        t1 = time.perf_counter()
        now = hoststats.window_totals()
        fields = hoststats.window_fields(seen, now)
        seen = now
        t2 = time.perf_counter()
        json.dumps({**fields, **recs.window()}, separators=(",", ":"))
        t3 = time.perf_counter()
        parts["record"] += t1 - t0
        parts["window_reads"] += t2 - t1
        parts["encode"] += t3 - t2
    out = {k: round(1e6 * v / n, 2) for k, v in parts.items()}
    print(json.dumps({"cost_probe_us_a_step": out, "total_us": round(sum(out.values()), 2),
                      "steps": n}), flush=True)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=40.0)
    p.add_argument("--kinds", default=",".join(KINDS),
                   help="comma-separated, in the order they are induced; 'none' for a clean run")
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--memory-stats", action="store_true")
    p.add_argument("--cost", action="store_true")
    p.add_argument("--rehearse", action="store_true",
                   help="tiny widths on whatever JAX finds: proves the probe's paths only")
    args = p.parse_args(argv)
    from benchmark import run as brun

    bench = brun._load(os.path.join(ROOT, "BENCHMARK.json"))
    device = None if args.rehearse else brun.check_devices(brun.find_cell(bench, args.workload))
    kinds = tuple(k for k in args.kinds.split(",") if k and k != "none")
    if set(kinds) - set(KINDS):
        raise SystemExit(f"--kinds takes {KINDS} or 'none', not {args.kinds!r}")
    if args.memory_stats and not args.rehearse:  # minutes of matmuls on a CPU
        memory_stats_probe()
    if args.cost:
        cost_probe()
    probe = Probe(kinds)
    try:
        line = brun.run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                             rehearse=args.rehearse, wrap_step=probe.wrap, quiet=True,
                             device=device)
    finally:
        probe.reap()
    rows = table(probe) if kinds else []
    if rows:
        print(" | ".join(("what",) + COLUMNS))
    for row in rows:
        print(" | ".join(str(row.get(c, "")) for c in ("what",) + COLUMNS))
    stalls = [e for e in probe.events if e.get("type") == "step_stall"]
    for e in stalls:
        print("step_stall " + json.dumps(e))
    print(json.dumps({
        "workload": args.workload, "seed": args.seed, "correct": line["correct"],
        "plan": probe.plan, "step_stall_steps": [e["step"] for e in stalls],
        "step_stall_pct": stall_shares(probe), "end_to_end": line["end_to_end"],
        "metrics": {k: v["value"] for k, v in line["metrics"].items()}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
