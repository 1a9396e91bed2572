"""Process-wide counters of what the host was doing, beside ``compiles.py``.

Every read is cumulative (since import, since the process began, or since
the machine booted): the trainer's loop writes the difference between two
reads into its ``step_window`` events, so a late step can be put down to
the process working, the process waiting, the collector, the machine's
other tenants or the device's allocator. Three reads, by what they cost:

* :func:`step_totals`, every step: two clock reads and one ``getrusage``
  (CPU seconds of the calling thread and of the process, involuntary
  context switches);
* :func:`gc_totals`: collections and the seconds inside them, by
  generation, counted by a ``gc.callbacks`` listener registered on import;
* :func:`process_start_t`, once a run: two ``/proc`` files, for the ``compile``
  event's account of set-up;
* :func:`machine_totals` and :func:`hbm_totals`, every window: ``/proc/stat``'s
  first line, ``/proc/pressure/cpu`` where the kernel has it, and
  ``memory_stats()`` of every local device, which asks the runtime and
  does not wait for the device.

A source the machine does not have reads ``None``, never an error.
"""

from __future__ import annotations

import gc
import os
import resource
import time
from typing import Dict, Optional, Tuple

import jax

STEP_FIELDS = ("thread_cpu_s", "proc_cpu_s", "nivcsw")


def step_totals() -> Tuple[float, float, int]:
    """``STEP_FIELDS``: CPU seconds of the calling thread and of the whole
    process (every thread, the runtime's included), and the times the
    scheduler took a CPU from the process for another task."""
    return (time.thread_time(), time.process_time(),
            resource.getrusage(resource.RUSAGE_SELF).ru_nivcsw)


# -- the collector -----------------------------------------------------------------
# Collections never nest and run under the interpreter lock, on whichever
# thread allocated last: one start stamp is enough.
_gc_n = [0, 0, 0]
_gc_s = [0.0, 0.0, 0.0]
_gc_t0 = 0.0


def _on_gc(phase: str, info: Dict[str, int]) -> None:
    global _gc_t0
    if phase == "start":
        _gc_t0 = time.perf_counter()
    elif _gc_t0:
        gen = min(int(info.get("generation", 2)), 2)
        _gc_n[gen] += 1
        _gc_s[gen] += time.perf_counter() - _gc_t0
        _gc_t0 = 0.0


gc.callbacks.append(_on_gc)


def gc_totals() -> Tuple[int, int, int, float, float, float]:
    """Collections of generation 0, 1, 2 since import, then the seconds
    spent inside each generation's."""
    return (*_gc_n, *_gc_s)


# -- the machine -------------------------------------------------------------------
_TICK = float(os.sysconf("SC_CLK_TCK")) if hasattr(os, "sysconf") else 100.0
_CPUS = os.cpu_count()
_STAT = "/proc/stat"
_PSI_CPU = "/proc/pressure/cpu"


# Descriptors kept open from a file's first read (-1: the machine has no such
# source): a pread of an open /proc file costs a tenth of open, read and close.
_fds: Dict[str, int] = {}


def _read(path: str) -> str:
    """The head of a /proc file; "" where it cannot be read."""
    fd = _fds.get(path)
    if fd is None:
        try:
            fd = os.open(path, os.O_RDONLY)
        except OSError:
            fd = -1
        _fds[path] = fd
    if fd < 0:
        return ""
    try:
        return os.pread(fd, 512, 0).decode("ascii", "replace")
    except OSError:
        return ""


def machine_totals() -> Dict[str, Optional[float]]:
    """Seconds every CPU of the machine together spent busy (user, nice,
    system, irq, softirq) and stolen by a hypervisor since boot (None where
    the kernel's line holds only zeros); ``cpus``; microseconds some runnable
    task waited for a CPU (``psi_cpu_us``: the ``some`` line's ``total=``)."""
    out: Dict[str, Optional[float]] = {
        "busy_s": None, "steal_s": None, "cpus": _CPUS, "psi_cpu_us": None}
    parts = _read(_STAT).partition("\n")[0].split()
    if len(parts) >= 9 and parts[0] == "cpu" and all(x.isdigit() for x in parts[1:9]):
        user, nice, system, idle, _, irq, softirq, steal = (int(x) for x in parts[1:9])
        if user + system + idle:
            out.update(busy_s=(user + nice + system + irq + softirq) / _TICK,
                       steal_s=steal / _TICK)
        else:
            # A sandboxed kernel's line of zeros (the chip machine's) counts
            # nothing and never will: no source, and no further reads.
            os.close(_fds[_STAT])
            _fds[_STAT] = -1
    some = _read(_PSI_CPU).partition("\n")[0]
    total = some.rpartition("total=")[2]
    if some.startswith("some ") and total.isdigit():
        out["psi_cpu_us"] = int(total)
    return out


def process_start_t() -> Optional[float]:
    """When this process was created, on ``time.time()``'s clock: the boot
    time of ``/proc/stat`` plus the start ticks of ``/proc/self/stat``, so that
    the interpreter's start and the imports count as set-up. None where
    ``/proc`` has neither."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open(_STAT) as f:
            btime = next(int(line.split()[1]) for line in f if line.startswith("btime"))
    except (OSError, ValueError, StopIteration, IndexError):
        return None
    return btime + ticks / _TICK


# -- the device's allocator ----------------------------------------------------------
_HBM_KEYS = {"reserved": "bytes_reserved", "peak": "peak_bytes_in_use",
             "largest_free": "largest_free_block_bytes", "allocs": "num_allocs"}


def hbm_totals() -> Optional[Dict[str, int]]:
    """The allocator's statistics, each the largest over the local devices,
    under short names (``_HBM_KEYS``; only those the backend reports); None
    on a backend without statistics. ``allocs`` is cumulative, the rest are
    levels."""
    out: Dict[str, int] = {}
    for d in jax.local_devices():
        stats = d.memory_stats()
        for short, key in _HBM_KEYS.items():
            if stats and key in stats:
                out[short] = max(out.get(short, 0), int(stats[key]))
    return out or None


# -- a window's differences ----------------------------------------------------------
def window_totals() -> Dict[str, object]:
    """One read of everything a window's close reads."""
    return {"gc": gc_totals(), "machine": machine_totals(), "hbm": hbm_totals()}


def window_fields(before: Dict[str, object], after: Dict[str, object]) -> Dict[str, object]:
    """What a ``step_window`` event carries of two :func:`window_totals`:
    ``gc_n``, ``gc_s``; ``machine`` (differences, ``cpus`` as it is; a source
    that was not read is left out); ``hbm`` (levels as they are, ``allocs``
    as a difference), left out on a backend without statistics."""
    g0, g1 = before["gc"], after["gc"]
    out: Dict[str, object] = {"gc_n": sum(g1[:3]) - sum(g0[:3]),
                              "gc_s": round(sum(g1[3:]) - sum(g0[3:]), 6)}
    machine = {}
    for k, v in after["machine"].items():
        v0 = before["machine"].get(k)
        if v is None or v0 is None:
            continue
        machine[k] = v if k == "cpus" else round(v - v0, 6)
    if machine:
        out["machine"] = machine
    if after["hbm"]:
        h0 = before["hbm"] or {}
        out["hbm"] = {k: (v - h0.get(k, v) if k == "allocs" else v)
                      for k, v in after["hbm"].items()}
    return out
