#!/usr/bin/env python3
"""One run of one benchmark cell: one process, one last line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

This file names no cell, configuration, traffic mix or metric. A cell is the
entry of ``BENCHMARK.json``'s ``workloads`` with that name; its configuration
is ``configs/<config>.json``, its traffic ``traffic/<traffic>.json``, the code
that generates that kind of traffic ``traffic_kinds/<kind>.py``, its own
record (limits of the output check, who sends such traffic)
``workloads/<cell>.json``, and each per-layer metric a reader
``layer_metrics/<metric>.py``. Adding a cell adds files and entries and edits
nothing (see README.md).

The run fails, with no result line, unless JAX finds TPU chips of a kind in
``peaks.py`` and exactly as many as the cell asks for. ``--rehearse`` runs the
same control flow at the tiny widths of ``rehearse.json`` on whatever JAX
finds; it proves paths, never speed, and its last line says so instead of
reporting metrics.
"""

from __future__ import annotations

import argparse
import importlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import threading
import time
from typing import Any, Callable, Dict, Optional

_T_IMPORT = time.time()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"


def _load(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def process_start_time() -> float:
    """Wall-clock time at which this process was created (so that imports and
    interpreter start count as set-up); falls back to this module's import."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/stat") as f:
            btime = next(int(l.split()[1]) for l in f if l.startswith("btime"))
        return btime + start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, StopIteration, IndexError):
        return _T_IMPORT


def merge_into(base: Dict[str, Any], over: Dict[str, Any]) -> Dict[str, Any]:
    out = dict(base)
    for k, v in over.items():
        out[k] = merge_into(out[k], v) if isinstance(v, dict) and isinstance(out.get(k), dict) else v
    return out


class Context:
    """What a traffic kind gets: the cell's data and the harness's services."""

    def __init__(self, cell, config, mix, seed: int, seconds: float, trace: bool,
                 rehearse: bool, workdir: str, control_precision: Optional[str] = None,
                 wrap_step: Optional[Callable] = None, quiet: bool = False):
        self.cell, self.config, self.mix = cell, config, mix
        self.seed, self.seconds, self.trace, self.rehearse = seed, float(seconds), trace, rehearse
        self.workdir = workdir
        self.control_precision = control_precision
        self.wrap_step = wrap_step or (lambda step: step)
        self.quiet = quiet
        self._t_proc = process_start_time()
        self._tracing = False
        self._stopper: Optional[threading.Thread] = None

    def say(self, message: str) -> None:
        if not self.quiet:
            print(message, flush=True)

    def since_process_start(self) -> float:
        return time.time() - self._t_proc

    def trace_path(self) -> str:
        return os.path.join(self.workdir, "trace")

    def start_trace(self, path: str) -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0   # the Python tracer slows the host loops
        opts.host_tracer_level = 2
        jax.profiler.start_trace(path, profiler_options=opts)
        self._tracing = True

    def stop_trace(self, background: bool = False) -> None:
        """Stop the profiler (seconds of host work). ``background`` does it on
        a helper thread so a load generator keeps its schedule; the thread is
        joined by the next plain call."""
        import jax

        if self._tracing:
            self._tracing = False
            if background:
                self._stopper = threading.Thread(target=jax.profiler.stop_trace,
                                                 name="stop-trace")
                self._stopper.start()
                return
            jax.profiler.stop_trace()
        if self._stopper is not None and not background:
            self._stopper.join(timeout=300)
            self._stopper = None

    def device_memory(self) -> Dict[str, Any]:
        """Allocator statistics of every local device, read at the window's
        end, before the reference allocates anything."""
        import jax

        out = {}
        for d in jax.local_devices():
            stats = d.memory_stats()
            if stats:
                out[d.id] = {k: int(stats[k]) for k in
                             ("peak_bytes_in_use", "bytes_in_use", "bytes_limit") if k in stats}
        return out


def find_cell(bench: Dict[str, Any], name: str) -> Dict[str, Any]:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json; it has "
                     f"{[w['name'] for w in bench['workloads']]}")


def load_cell(workload: str, rehearse: bool = False):
    """(BENCHMARK.json, the cell's entry merged with its own record, its
    configuration, its traffic mix), at ``rehearse.json``'s widths if asked."""
    bench = _load(os.path.join(ROOT, "BENCHMARK.json"))
    cell = dict(find_cell(bench, workload))
    cell.update(_load(os.path.join(HERE, "workloads", workload + ".json")))
    config = _load(os.path.join(ROOT, next(c["file"] for c in bench["configs"]
                                            if c["name"] == cell["config"])))
    mix = _load(os.path.join(HERE, "traffic", cell["traffic"] + ".json"))
    if rehearse:
        tiny = _load(os.path.join(HERE, "rehearse.json"))
        config = merge_into(config, tiny["config"])
        mix = merge_into(mix, tiny["traffic_kinds"][mix["kind"]])
    return bench, cell, config, mix


def check_devices(cell: Dict[str, Any]) -> Dict[str, Any]:
    import jax

    from benchmark import peaks

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}
    if device["platform"] != "tpu":
        raise SystemExit(f"JAX found no TPU (platform {device['platform']!r}): "
                         f"a cell is measured on the chip or not at all")
    peaks.peak(device["kind"])  # an unknown kind is an error, not a default
    if device["count"] != int(cell["chips"]):
        raise SystemExit(f"cell {cell['name']} needs {cell['chips']} chip(s), "
                         f"JAX reports {device['count']}")
    return device


def enable_compile_cache() -> str:
    """The persistent cache: where the environment says, else a fixed
    directory inside the checkout (the path is part of the cache's key)."""
    import jax

    path = os.environ.get(CACHE_DIR_ENV) or os.path.join(ROOT, ".jax_cache")
    os.makedirs(path, exist_ok=True)
    if not os.environ.get(CACHE_DIR_ENV):
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def reported_by(metric: Dict[str, Any], cell_name: str, end_to_end_of_cell) -> bool:
    if "workloads" in metric:
        return cell_name in metric["workloads"]
    return metric.get("moves", metric["name"]) in end_to_end_of_cell


def read_layer_metrics(bench, cell_name: str, sources: Dict[str, Any], say) -> Dict[str, Any]:
    out = {}
    readers = os.path.join(HERE, "layer_metrics")
    if readers not in sys.path:
        sys.path.insert(0, readers)  # readers share layer_metrics/_common.py
    for m in bench["per_layer"]:
        if "workloads" in m and cell_name not in m["workloads"]:
            continue
        path = os.path.join(HERE, "layer_metrics", m["name"] + ".py")
        spec = importlib.util.spec_from_file_location("layer_metric_" + m["name"].replace(".", "_"),
                                                      path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        value = mod.read(sources)
        if value is None:
            say(f"per-layer {m['name']}: nothing to read in this cell")
            continue
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
        say(f"per-layer {m['name']} = {float(value)!r} {m['unit']} "
            f"(layer: {m['layer']}; source: {m['source']}; moves {m['moves']})")
    return out


def run_cell(workload: str, seed: int, seconds: float, trace: bool, rehearse: bool = False,
             control_precision: Optional[str] = None, wrap_step: Optional[Callable] = None,
             quiet: bool = False, device: Optional[Dict[str, Any]] = None,
             keep_trace: Optional[str] = None,
             mix_overrides: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """Everything a run does after the look for a chip. Returns the result
    line as a dict (plus ``check_numbers`` for the control script)."""
    bench, cell, config, mix = load_cell(workload, rehearse)
    mix = merge_into(mix, mix_overrides or {})  # sweep.py and tests; never the CLI
    import jax

    if device is None:
        d = jax.devices()[0]
        device = {"platform": d.platform, "kind": d.device_kind, "count": len(jax.devices())}
    cache = enable_compile_cache()
    workdir = tempfile.mkdtemp(prefix="bench_")
    ctx = Context(cell, config, mix, seed, seconds, trace, rehearse, workdir,
                  control_precision, wrap_step, quiet)
    ctx.say(f"cell {workload}: config {cell['config']} traffic {cell['traffic']} "
            f"({mix['kind']}) seed {seed} window {seconds} s trace {int(trace)}"
            + (" REHEARSAL at tiny widths: proves paths, not speed" if rehearse else ""))
    ctx.say(f"device: {json.dumps(device)}; compile cache {cache} "
            f"({len(os.listdir(cache))} entries)")
    try:
        kind = importlib.import_module("benchmark.traffic_kinds." + mix["kind"])
        res = kind.run(ctx)
        ctx.stop_trace()
        e2e_names = {m["name"] for m in bench["end_to_end"]
                     if reported_by(m, workload, res["end_to_end"])}
        end_to_end = {m["name"]: {"value": float(res["end_to_end"][m["name"]]), "unit": m["unit"]}
                      for m in bench["end_to_end"] if m["name"] in e2e_names}
        for name, mv in end_to_end.items():
            ctx.say(f"end-to-end {name} = {mv['value']!r} {mv['unit']}")
        peak_bytes = max((m.get("peak_bytes_in_use", 0) for m in res["memory"].values()),
                         default=0)
        device = dict(device, memory_peak_bytes=int(peak_bytes))
        line: Dict[str, Any] = {"correct": bool(res["correct"]), "attempted": int(res["attempted"]),
                                "failed": int(res["failed"])}
        if trace:
            from benchmark import peaks, trace_reduce

            sources = res["sources"]
            sources["peaks"] = None if rehearse else peaks.peak(device["kind"])
            red = None
            if sources.get("trace_dir") and sources.get("trace_span"):
                red = trace_reduce.reduce_trace(sources["trace_dir"])
                if keep_trace:
                    os.makedirs(keep_trace, exist_ok=True)
                    shutil.copy(trace_reduce.find_xplane(sources["trace_dir"]), keep_trace)
                ctx.say(f"trace: {red['device_planes']} device plane(s), busy "
                        f"{red['busy_s']:.4f} s of {red['window_s']:.4f} s, "
                        f"{red['steps']} step(s) on the Steps line")
                for name, secs in red["device_ops"]:
                    ctx.say(f"trace op {secs:10.6f} s  {name}")
                for name, secs in red["idle_gaps"][:5]:
                    ctx.say(f"trace gap {secs:9.6f} s  {name}")
            sources["trace"] = red if red and (red["device_planes"] or rehearse) else None
            line["metrics"] = read_layer_metrics(bench, workload, sources, ctx.say)
            if red:
                device.update(busy_s=red["busy_s"], window_s=red["window_s"])
                line["breakdown"] = {"device_ops": red["device_ops"],
                                     "idle_gaps": red["idle_gaps"]}
        else:
            line["metrics"] = end_to_end
        line["device"] = device
        if res.get("checks"):  # a kind that pairs each compared number with its limit
            line["checks"] = res["checks"]
        line["check_numbers"] = res.get("check_numbers")
        line["end_to_end"] = res["end_to_end"]
        line["backlog"] = res["sources"].get("backlog")
        line["ttft_ms"] = (res["sources"].get("latency") or {}).get("ttft_ms")
        return line
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=None)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse", action="store_true",
                   help="tiny widths on whatever JAX finds; prints no metrics")
    p.add_argument("--keep-trace", default=None, metavar="DIR",
                   help="with --trace 1: copy the recorded .xplane.pb here")
    args = p.parse_args(argv)
    bench = _load(os.path.join(ROOT, "BENCHMARK.json"))
    cell = find_cell(bench, args.workload)
    seconds = args.seconds if args.seconds is not None else float(bench["run_seconds"])
    device = None if args.rehearse else check_devices(cell)
    line = run_cell(args.workload, args.seed, seconds, bool(args.trace),
                    rehearse=args.rehearse, device=device, keep_trace=args.keep_trace)
    for extra in ("check_numbers", "end_to_end", "backlog", "ttft_ms"):
        line.pop(extra, None)
    checks = line.pop("checks", None)
    if args.rehearse:
        print(json.dumps({"rehearsal": "passed", "correct_at_tiny_widths": line["correct"],
                          "device": {k: line["device"][k] for k in ("platform", "kind", "count")},
                          "proves": "control flow only; no metric is reported"}), flush=True)
        return 0
    if checks:  # the numbers compared, each beside its limit: last on both streams
        line["checks"] = checks
        for name, c in checks.items():
            print(f"check {name} = {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
        sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
