"""Wall-time trace spans with thread-local nesting.

Two context managers:

:func:`span`
    Host-side wall-time span for code that runs eagerly (plan build,
    plan execute, exporter flush).  Always enters
    ``jax.profiler.TraceAnnotation``, so the span lands on the host
    plane of a ``jax.profiler`` trace, on the device ops' clock, even
    with obs off (about a microsecond while no profiler session is
    active).  Records an event into the process buffer when
    ``REPRO_OBS=trace``.

:func:`stage`
    For code that runs *under a jax trace* (engine schedule stages,
    kernel dispatch).  Always enters ``jax.named_scope`` — that is
    trace-time-only metadata, free at runtime, and makes the stage
    visible in XLA HLO names and ``jax.profiler`` output even with obs
    off.  When tracing is enabled it additionally records a span event;
    since the wrapped code executes at *trace* time for jitted paths,
    the recorded duration is the tracing/staging cost of that stage,
    not device runtime (device-side timing comes from ``jax.profiler``
    via the same named scopes).

Events use the Chrome-trace "complete" (``ph: "X"``) model: name,
category, start timestamp and duration in microseconds, plus the
nesting depth at record time.  The buffer is bounded; overflow bumps a
dropped-events counter rather than growing without limit.
"""
from __future__ import annotations

import threading
import time
from contextlib import contextmanager
from typing import Any, Dict, Iterator, List, Optional

import jax

from repro.obs import config as _cfg

_EPOCH = time.perf_counter()      # process-relative origin for timestamps
_MAX_EVENTS = 100_000

_lock = threading.Lock()
_events: List[Dict[str, Any]] = []
_dropped = 0
_tls = threading.local()


def _stack() -> list:
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = []
    return st


def _now_us() -> float:
    return (time.perf_counter() - _EPOCH) * 1e6


def _record(name: str, cat: str, ts_us: float, dur_us: float, depth: int,
            args: Optional[Dict[str, Any]]) -> None:
    global _dropped
    ev = {"name": name, "cat": cat, "ts": ts_us, "dur": dur_us,
          "depth": depth, "tid": threading.get_ident()}
    if args:
        ev["args"] = args
    with _lock:
        if len(_events) >= _MAX_EVENTS:
            _dropped += 1
        else:
            _events.append(ev)


def span(name: str, *, cat: str = "host", sync: Any = None, **attrs: Any):
    """Wall-time span around eager host code.

    ``sync`` — an optional value (array / pytree) passed to
    ``jax.block_until_ready`` before the clock stops, so the span covers
    device work dispatched inside it rather than dispatch alone.
    """
    if not _cfg.trace_enabled():
        if sync is not None:
            jax.block_until_ready(sync)
        return jax.profiler.TraceAnnotation(name)
    return _recorded(name, cat, sync, attrs)


@contextmanager
def _recorded(name: str, cat: str, sync: Any,
              attrs: Dict[str, Any]) -> Iterator[None]:
    st = _stack()
    depth = len(st)
    st.append(name)
    t0 = _now_us()
    try:
        with jax.profiler.TraceAnnotation(name):
            yield
            if sync is not None:
                jax.block_until_ready(sync)
    finally:
        t1 = _now_us()
        st.pop()
        _record(name, cat, t0, t1 - t0, depth, attrs or None)


def stage(name: str, **attrs: Any):
    """Scope for code executing under a jax trace (see module docstring)."""
    scope = jax.named_scope(name)
    if not _cfg.trace_enabled():
        return scope

    @contextmanager
    def _staged() -> Iterator[None]:
        st = _stack()
        depth = len(st)
        st.append(name)
        t0 = _now_us()
        try:
            with scope:
                yield
        finally:
            t1 = _now_us()
            st.pop()
            _record(name, "stage", t0, t1 - t0, depth, attrs or None)

    return _staged()


def events() -> List[Dict[str, Any]]:
    """Snapshot of recorded span events (oldest first)."""
    with _lock:
        return list(_events)


def dropped_events() -> int:
    with _lock:
        return _dropped


def reset() -> None:
    """Clear the event buffer (test hook)."""
    global _dropped
    with _lock:
        _events.clear()
        _dropped = 0
