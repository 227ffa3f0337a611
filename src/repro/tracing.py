"""Host spans of the program, on the profiler's clock and on JAX's monitoring.

``with span("/repro/train/drain", step=s): ...`` marks a stretch of host work
twice: as a :class:`jax.profiler.TraceAnnotation`, so a profiler trace holds
it on the same clock as the device's ops (its keyword arguments become the
event's stats), and, on exit, as a :func:`jax.monitoring.record_event_time_span`
in ``time.time()`` seconds, the clock of JAX's own compile spans, with the
name of the enclosing span of the same thread as ``parent``.  Names follow
JAX's ``/jax/...``: ``/repro/<layer>/<what>``.  With no profiler running and
no listener registered a span costs a microsecond or two.
"""
from __future__ import annotations

import threading
import time

import jax

_open = threading.local()   # per thread: names of the spans entered


class span:
    """Context manager marking one host span (see the module docstring)."""

    __slots__ = ("name", "attrs", "_annotation", "_parent", "_t0")

    def __init__(self, name: str, **attrs: int | str):
        self.name, self.attrs = name, attrs

    def __enter__(self) -> "span":
        stack = _open.__dict__.setdefault("names", [])
        self._parent = stack[-1] if stack else ""
        stack.append(self.name)
        self._annotation = jax.profiler.TraceAnnotation(self.name, **self.attrs)
        self._annotation.__enter__()
        self._t0 = time.time()
        return self

    def __exit__(self, *exc) -> None:
        t1 = time.time()
        try:
            self._annotation.__exit__(*exc)
        finally:
            _open.names.pop()
            jax.monitoring.record_event_time_span(
                self.name, self._t0, t1, parent=self._parent, **self.attrs)


def mark(name: str, **attrs: int) -> None:
    """An instant host span whose arguments the trace event's name keeps:
    ``name#k=v,k=v``.  A span's keyword arguments reach the profiler trace
    as event stats, which a reader of event names alone loses; left
    unclosed by ``#``, the name is kept whole.  ``jax.monitoring``
    listeners get ``name`` and the arguments as keywords, as from
    :class:`span`."""
    stack = getattr(_open, "names", None)
    label = name + "#" + ",".join(f"{k}={v}" for k, v in attrs.items())
    with jax.profiler.TraceAnnotation(label):
        t = time.time()
    jax.monitoring.record_event_time_span(
        name, t, t, parent=stack[-1] if stack else "", **attrs)
