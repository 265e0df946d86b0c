"""Spans, scopes and counters of the program, on the profiler's clock.

``span(name)`` is a ``jax.profiler.TraceAnnotation`` (it records nothing
unless a profiler session is open, and then lands on the trace's host
plane, on the device ops' clock) that also adds its wall time to a count,
total and max per name kept in memory; the span's ``seconds`` give the
caller its duration.  ``scope(name)`` puts a function's device ops under
a ``jax.named_scope``.  ``count(name, n)`` adds to a counter.  On import,
listeners count JAX's persistent compilation cache hits and misses, time
each backend compile (span ``jax.compile``, a cache load included), and a
``gc.callbacks`` hook records Python's collections as span ``python.gc``.
``snapshot()`` returns the aggregates, ``delta`` the part of one snapshot
since another and ``line`` a log line of it; ``recent(name)`` the last
``RECENT`` calls or counts of a name with the time each ended, for a
reading over one stretch of a run; ``reset()`` clears them.
Nothing is written out: when a timeline is wanted, the profiler's trace
is it.
"""
from __future__ import annotations

import collections
import functools
import gc
import threading
import time

import jax

_LOCK = threading.RLock()     # re-entered when a collection starts in _add
_GC = "python.gc"
# name -> [count, total seconds, max seconds]; the collections' entry is
# always there, so that a collection inside snapshot() adds no key
_SPANS: dict = {_GC: [0, 0.0, 0.0]}
_COUNTS: dict = {}
RECENT = 256
# name -> its last RECENT (perf_counter_ns at the end, seconds or count)
_RECENT: dict = {_GC: collections.deque(maxlen=RECENT)}


def _note(name: str, value) -> None:
    _RECENT.setdefault(name, collections.deque(maxlen=RECENT)).append(
        (time.perf_counter_ns(), value))


def _add(name: str, seconds: float) -> None:
    with _LOCK:
        agg = _SPANS.setdefault(name, [0, 0.0, 0.0])
        agg[0] += 1
        agg[1] += seconds
        agg[2] = max(agg[2], seconds)
        _note(name, seconds)


def count(name: str, n: int = 1) -> None:
    with _LOCK:
        _COUNTS[name] = _COUNTS.get(name, 0) + n
        _note(name, n)


class span:
    """``with span("train.wait") as s: ...``; then ``s.seconds``."""

    def __init__(self, name: str):
        self.name, self.seconds = name, 0.0
        self._ann = jax.profiler.TraceAnnotation(name)

    def __enter__(self):
        self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        self.seconds = (time.perf_counter_ns() - self._t0) * 1e-9
        self._ann.__exit__(*exc)
        _add(self.name, self.seconds)
        return False


def scope(name: str):
    """Decorator: the function's ops traced under ``jax.named_scope(name)``,
    which changes only their HLO metadata; a fresh scope per call, since
    one scope object is not re-entrant."""
    def wrap(fn):
        @functools.wraps(fn)
        def scoped(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return scoped
    return wrap


def snapshot() -> dict:
    """``{"spans": {name: {"count", "total_s", "max_s"}}, "counts": {..}}``."""
    with _LOCK:
        return {"spans": {k: {"count": c, "total_s": t, "max_s": m}
                          for k, (c, t, m) in _SPANS.items() if c},
                "counts": dict(_COUNTS)}


def recent(name: str) -> list:
    """The last ``RECENT`` ``(perf_counter_ns at the end, seconds or n)``
    of span or counter ``name``, oldest first."""
    with _LOCK:
        return list(_RECENT.get(name, ()))


def delta(new: dict, old: dict) -> dict:
    """Counts and seconds of ``new`` less those of ``old`` (``max_s`` left
    out), for the names that moved."""
    def less(k, d):
        o = old["spans"].get(k, {"count": 0, "total_s": 0.0})
        return {"count": d["count"] - o["count"],
                "total_s": d["total_s"] - o["total_s"]}

    spans = {k: less(k, d) for k, d in new["spans"].items()}
    counts = {k: n - old["counts"].get(k, 0) for k, n in new["counts"].items()}
    return {"spans": {k: d for k, d in spans.items() if d["count"]},
            "counts": {k: n for k, n in counts.items() if n}}


def line(d: dict) -> str:
    """A log line of a ``delta``: each span's mean milliseconds and calls,
    then the counters."""
    parts = [f"{k} {v['total_s'] / v['count'] * 1e3:.3f} ms x{v['count']}"
             for k, v in sorted(d["spans"].items())]
    return "; ".join(parts + [f"{k} {n}" for k, n in
                              sorted(d["counts"].items())]) or "no spans"


def reset() -> None:
    with _LOCK:
        _SPANS.clear()
        _SPANS[_GC] = [0, 0.0, 0.0]
        _COUNTS.clear()
        _RECENT.clear()
        _RECENT[_GC] = collections.deque(maxlen=RECENT)


_EVENTS = {"/jax/compilation_cache/cache_hits": "compile_cache.hits",
           "/jax/compilation_cache/cache_misses": "compile_cache.misses"}
_gc_span: list = []


def _on_event(event: str, **_) -> None:
    if event in _EVENTS:
        count(_EVENTS[event])


def _on_duration(event: str, seconds: float, **_) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        _add("jax.compile", seconds)


def _on_gc(phase: str, info: dict) -> None:
    # a collection starts and stops in one thread, and never nests
    if phase == "start":
        _gc_span.append(span(_GC).__enter__())
    elif _gc_span:
        _gc_span.pop().__exit__(None, None, None)


jax.monitoring.register_event_listener(_on_event)
jax.monitoring.register_event_duration_secs_listener(_on_duration)
gc.callbacks.append(_on_gc)
