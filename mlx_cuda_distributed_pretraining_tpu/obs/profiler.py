"""Idempotent jax.profiler capture with auto-attribution on stop.

The trainer has three paths that used to call
``jax.profiler.start_trace``/``stop_trace`` inline (the configured
profile window, the SIGUSR2 on-demand capture, and the end-of-run
``finally``); ``ProfileCapture`` is the single owner of that state:

- ``start()`` is a no-op (returns False) when a trace is already
  running, and never raises — the XLA profiler can only record one
  session per process, and a capture request must not kill training.
- ``stop()`` is a no-op (returns None) when no trace is running.
  Otherwise it synchronizes the device (caller-provided ``sync``: the
  in-flight step must land inside the trace, not after it), stops the
  trace, and — unless reporting is disabled — runs the graftprof
  attribution (obs/profile_report.py) over the fresh dump, writes the
  JSON summary, and returns the report dict for the caller to fan out
  into gauges / event fields / log lines.

Report generation is best-effort: a torn or unparseable dump logs a
warning and returns None; the trace files themselves are always left
on disk for offline analysis.py.prof runs.
"""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, Optional

from .profile_report import generate_report, write_summary


class ProfileCapture:
    """One ``jax.profiler`` session per process, with attribution.

    Parameters:
      dump_dir      where start_trace dumps (``<run_dir>/profile``)
      log           line logger (``Trainer.logger.log``-shaped)
      sync          called before stop_trace to drain in-flight work
                    (e.g. ``lambda: jax.block_until_ready(state)``)
      analytic_fn   lazily builds the analytic join dict for the report
                    (tokens_per_step / *_flops_per_token); called at
                    stop time so it sees final trainer state
      summary_path  where stop() writes the JSON summary (None: skip)
      report        master switch (logging.profile_report.enabled)
      top_k         op-table rows in the generated report
    """

    def __init__(self, dump_dir: str,
                 log: Optional[Callable[[str], None]] = None,
                 sync: Optional[Callable[[], None]] = None,
                 analytic_fn: Optional[Callable[[], Dict[str, Any]]] = None,
                 summary_path: Optional[str] = None,
                 report: bool = True, top_k: int = 12):
        self.dump_dir = dump_dir
        self.active = False
        self.started_t = 0.0  # time.time() of the running session's start
        self._log = log or (lambda msg: None)
        self._sync = sync
        self._analytic_fn = analytic_fn
        self.summary_path = summary_path
        self.report_enabled = bool(report)
        self.top_k = int(top_k)
        self.last_report: Optional[Dict[str, Any]] = None

    def start(self, step: Optional[int] = None) -> bool:
        """Begin a trace; False (logged, no exception) when one is
        already running or the profiler refuses to start."""
        if self.active:
            return False
        try:
            import jax.profiler as _prof

            _prof.start_trace(self.dump_dir)
        except Exception as e:  # noqa: BLE001 - capture is best-effort
            self._log(f"profiler: unavailable ({e})")
            return False
        self.active = True
        self.started_t = time.time()
        at = f" at step {step}" if step is not None else ""
        self._log(f"profiler: trace started{at}")
        return True

    def stop(self, step: Optional[int] = None) -> Optional[Dict[str, Any]]:
        """End the trace and attribute it. Returns the graftprof report
        dict (None when idle, when reporting is off, or when the dump
        yields nothing attributable)."""
        if not self.active:
            return None
        if self._sync is not None:
            try:
                self._sync()
            except Exception as e:  # noqa: BLE001 - sync is advisory
                self._log(f"profiler: device sync before stop failed ({e})")
        import jax.profiler as _prof

        _prof.stop_trace()
        self.active = False
        self._log(f"profiler: trace written to {self.dump_dir}")
        if not self.report_enabled:
            return None
        try:
            analytic = self._analytic_fn() if self._analytic_fn else None
            report = generate_report(self.dump_dir, analytic=analytic,
                                     top_k=self.top_k)
        except Exception as e:  # noqa: BLE001 - never kill training
            self._log(f"graftprof: report failed "
                      f"({type(e).__name__}: {e}); trace kept on disk")
            return None
        if report is None:
            self._log("graftprof: no attributable device ops in the dump")
            return None
        self.last_report = report
        if self.summary_path:
            try:
                write_summary(report, self.summary_path)
            except OSError as e:
                self._log(f"graftprof: could not write "
                          f"{self.summary_path}: {e}")
        return report
