"""Process-wide count of XLA compilations, through ``jax.monitoring``.

JAX reports every backend compile as a duration event, whether XLA
compiled the program or the persistent cache handed it back; importing
this module registers one listener that adds them up. ``totals()`` is
cumulative since import: the trainer writes the difference between two
reads into each ``step_window`` event (``xla_compiles``,
``xla_compile_s``), so a step that recompiles inside a window shows; the
serving engine exposes the totals in ``/metrics``.
"""

from __future__ import annotations

import threading
from typing import Tuple

import jax.monitoring

# jax/_src/dispatch.py BACKEND_COMPILE_EVENT: wraps compile_or_get_cached,
# so it fires for cold compiles and persistent-cache loads alike.
_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_lock = threading.Lock()
_count = 0
_seconds = 0.0


def _on_duration(event: str, duration_secs: float, **_: object) -> None:
    global _count, _seconds
    if event == _BACKEND_COMPILE_EVENT:
        with _lock:
            _count += 1
            _seconds += float(duration_secs)


jax.monitoring.register_event_duration_secs_listener(_on_duration)


def totals() -> Tuple[int, float]:
    """(compilations, seconds spent in them) since this module's import."""
    with _lock:
        return _count, _seconds
