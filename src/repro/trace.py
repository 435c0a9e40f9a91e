"""Spans and a compile counter for the serving path, read from the
profiler's own trace.

:func:`span` is ``jax.profiler.TraceAnnotation`` and nothing more: while a
profiler trace is active (``jax.profiler.start_trace``) it records a host
event on the calling thread, with its keyword stats, in the same
``.xplane.pb`` as the device ops and on the same clock; otherwise it costs
one object.  Stats known only at the end of a span are added with
``set_metadata``.  The trace is the only sink: nothing is buffered here.

Spans are opened per batch, never per request: per-request facts (queue
wait, admission time) are counted and stamped on the batch's spans.
"""
from __future__ import annotations

import threading

import jax

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_lock = threading.Lock()
_listening = False
_compiles = 0


def span(name: str, **stats) -> jax.profiler.TraceAnnotation:
    """A host span named ``name`` with ``stats`` (ints, floats, strings)."""
    return jax.profiler.TraceAnnotation(name, **stats)


def count_compiles() -> None:
    """Start counting backend compiles (a program compiled or loaded from the
    persistent cache) in this process.  The listener is registered once."""
    global _listening
    with _lock:
        if not _listening:
            jax.monitoring.register_event_duration_secs_listener(_on_duration)
            _listening = True


def _on_duration(event: str, duration: float, **_) -> None:
    global _compiles
    if event == COMPILE_EVENT:
        with _lock:
            _compiles += 1


def compiles() -> int:
    """Backend compiles since :func:`count_compiles` was first called."""
    return _compiles
