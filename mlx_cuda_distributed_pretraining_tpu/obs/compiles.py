"""What JAX compiled in this process, by stage and by function, through
``jax.monitoring``.

JAX stamps three stages of every program it builds with ``time.time()`` and
the function's name (``jax/_src/dispatch.py``): the trace of the Python
function to a jaxpr, the lowering of that jaxpr to an MLIR module (Mosaic
kernels are lowered inside it), and the backend compile, which fires whether
XLA compiled the program or the persistent cache handed it back. Beside them
it reports each cache hit, each entry it writes to the cache (a miss) and the
seconds a retrieval took. Importing this module registers one listener for
each kind; nothing here starts a thread or opens a file.

**A stage's seconds are the union of its spans on a thread, never the sum of
durations.** A jitted function called inside another's trace fires its own
trace event inside the caller's (one train step of a large model fires tens of
thousands), so only outermost spans are kept: a span that closes swallows the
closed spans of its stage and thread that began after it did. What stays is
one record for every program built at top level.

Every read is cumulative since import; callers write differences. ``totals()``
is what it was (backend compiles, cold and cached alike, and the seconds in
them): the trainer writes its difference into each ``step_window`` event
(``xla_compiles``, ``xla_compile_s``) and the serving engine exposes it in
``/metrics``. ``stages()``, ``spans()``, ``functions()`` and ``inside()`` are
what the trainer's ``compile`` event, its ``xla_compiled`` field and the ring's
``compile.*`` spans are made of.
"""

from __future__ import annotations

import threading
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax.monitoring

# jax/_src/dispatch.py: JAXPR_TRACE_EVENT, JAXPR_TO_MLIR_MODULE_EVENT and
# BACKEND_COMPILE_EVENT (which wraps compile_or_get_cached, so it fires for cold
# compiles and persistent-cache loads alike).
STAGES = ("trace", "lower", "backend")
_STAGE_OF = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
# jax/_src/compiler.py and compilation_cache.py: both fire inside the backend
# compile's span, on its thread. A miss is an entry written: a program compiled
# with the cache off, or too small or too quick to be stored, is neither.
_CACHE_OF = {"/jax/compilation_cache/cache_hits": "hit",
             "/jax/compilation_cache/cache_misses": "miss"}
_CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"


class Span(NamedTuple):
    """An outermost span. ``fun``: the function's name as the trace reports it
    (lowering and the backend say ``jit(<name>)``; the wrapping is taken off).
    ``cache``: ``hit`` | ``miss`` | None, on a backend span."""

    stage: str
    fun: str
    start: float
    end: float
    cache: Optional[str] = None

    @property
    def seconds(self) -> float:
        return self.end - self.start


# Bounded: past _KEEP records a list lets go of those that closed more than
# _OPEN_S before the newest began. Nothing stays open that long (a step's trace is
# tens of seconds, a cold compile minutes), so nothing still to be swallowed goes,
# and the seconds and counts below are kept apart from the records.
_KEEP = 4096
_OPEN_S = 3600.0

_lock = threading.Lock()
# (stage, thread) -> (start, end, the name as JAX gave it, cache), by start: plain
# tuples, because the listener runs tens of thousands of times inside one trace
_kept: Dict[Tuple[str, int], List[Tuple[float, float, str, Optional[str]]]] = {}
_seconds = dict.fromkeys(STAGES, 0.0)           # the union, kept as spans close
_counts = dict.fromkeys(STAGES, 0)              # every span reported, nested ones too
_cache = {"hit": 0, "miss": 0}
_cache_load_s = 0.0
_outcome = threading.local()                    # this thread's open backend compile: hit or miss


def _on_span(event: str, start_time: float, end_time: float, fun_name: str = "",
             **_: object) -> None:
    stage = _STAGE_OF.get(event)
    if stage is None:
        return
    cache = None
    if stage == "backend":
        cache, _outcome.cache = getattr(_outcome, "cache", None), None
    key = (stage, threading.get_ident())
    with _lock:
        kept = _kept.get(key)
        if kept is None:
            kept = _kept[key] = []
        inside = 0.0
        while kept and kept[-1][0] >= start_time:
            swallowed = kept.pop()
            inside += swallowed[1] - swallowed[0]
        if len(kept) >= _KEEP and kept[0][1] < start_time - _OPEN_S:
            old = next((i for i, k in enumerate(kept) if k[1] >= start_time - _OPEN_S), len(kept))
            del kept[:old]
        kept.append((start_time, end_time, fun_name, cache))
        _seconds[stage] += (end_time - start_time) - inside
        _counts[stage] += 1


def _span(stage: str, start: float, end: float, fun_name: object, cache: Optional[str]) -> Span:
    fun = str(fun_name)
    if stage != "trace" and fun.endswith(")") and "(" in fun:
        fun = fun[fun.index("(") + 1:-1]   # jit(<name>)
    return Span(stage, fun, float(start), float(end), cache)


def _on_event(event: str, **_: object) -> None:
    outcome = _CACHE_OF.get(event)
    if outcome is not None:
        _outcome.cache = outcome
        with _lock:
            _cache[outcome] += 1


def _on_duration(event: str, duration_secs: float, **_: object) -> None:
    global _cache_load_s
    if event == _CACHE_LOAD_EVENT:
        with _lock:
            _cache_load_s += float(duration_secs)


jax.monitoring.register_event_time_span_listener(_on_span)
jax.monitoring.register_event_listener(_on_event)
jax.monitoring.register_event_duration_secs_listener(_on_duration)


def totals() -> Tuple[int, float]:
    """(compilations, seconds spent in them) since this module's import."""
    with _lock:
        return _counts["backend"], _seconds["backend"]


def stages() -> Dict[str, Any]:
    """Cumulative: each stage's seconds (the union) and the spans it reported,
    the cache's hits and misses and the seconds its retrievals took."""
    with _lock:
        out: Dict[str, Any] = {}
        for s in STAGES:
            out[s + "_s"] = round(_seconds[s], 6)
            out[s + "_n"] = _counts[s]
        out.update(cache_hits=_cache["hit"], cache_misses=_cache["miss"],
                   cache_load_s=round(_cache_load_s, 6))
        return out


def spans(since_t: float = 0.0) -> List[Span]:
    """The kept outermost spans that closed after ``since_t``, by start."""
    with _lock:
        found = [_span(stage, *k) for (stage, _), kept in _kept.items() for k in kept
                 if k[1] > since_t]
    return sorted(found, key=lambda s: s.start)


def functions(since_t: float = 0.0) -> Dict[str, Dict[str, Any]]:
    """By function name, from its outermost spans: ``trace_s``, ``lower_s``,
    ``backend_s`` and ``cache`` (its backend compile's outcome; a miss among
    several wins)."""
    out: Dict[str, Dict[str, Any]] = {}
    for s in spans(since_t):
        f = out.setdefault(s.fun, {"trace_s": 0.0, "lower_s": 0.0, "backend_s": 0.0, "cache": None})
        f[s.stage + "_s"] += s.seconds
        if s.cache is not None and f["cache"] != "miss":
            f["cache"] = s.cache
    return out


def inside(t0: float, t1: float, found: Optional[List[Span]] = None) -> Dict[str, float]:
    """Seconds of each stage that fell in ``[t0, t1]``: the kept spans, cut to it."""
    out = dict.fromkeys((s + "_s" for s in STAGES), 0.0)
    for s in spans(t0) if found is None else found:
        out[s.stage + "_s"] += max(0.0, min(s.end, t1) - max(s.start, t0))
    return out
