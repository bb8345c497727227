"""Spans and counters of the program's own layers.

``span(name, **attrs)`` is a ``jax.profiler.TraceAnnotation`` that also times
itself: after the ``with`` block, ``sp.s`` holds its seconds by
``time.perf_counter()``.  Callers that feed ``CostModel.observe`` read that
value, so each boundary is timed once.  The annotation lands on the host plane
of the profiler's trace, on the same clock as the device's ops, and is recorded
only while a profiler session is active; its keyword attributes then become the
event's stats.  With no session a span costs one TraceMe, two clock reads and
one check that no session is active, and nothing is kept.  A span's parent is
the span that encloses it on the same thread.  Names are constant strings, and
attributes are passed as values, so nothing is formatted when tracing is off.

``root(name, **attrs)`` opens the span of one scan (a stream or a query) with a
``scan`` id attribute; a root opened inside another on the same thread joins
its scan.  ``current_scan()`` gives the id to work handed to other threads,
whose spans carry it as an attribute.

One process-wide registry, read by ``snapshot()``, records while a profiler
session is active, and only then:

* the seconds of every span, summed under its name; a root opened inside
  another adds nothing, as its time is in the outer root's;
* counters, ``inc(name, n)``.  There is one, ``plan_changes``: plans whose
  issue order or per-column decisions differ from the previous plan over the
  same column set.

The registry is never reset: over a process with one profiler session, as a
traced benchmark run, it holds that session's totals.
"""
from __future__ import annotations

import itertools
import threading
import time

from jax.profiler import TraceAnnotation

COUNTERS = ("plan_changes",)

_registry: dict[str, float] = dict.fromkeys(COUNTERS, 0)
_lock = threading.Lock()
_scan_ids = itertools.count(1)
_local = threading.local()


class span:
    """A timed ``TraceAnnotation``; ``s`` is its duration in seconds once
    the block has exited."""

    __slots__ = ("_ann", "_name", "_t0", "s")

    def __init__(self, name: str, **attrs):
        self._ann = TraceAnnotation(name, **attrs)
        self._name = name
        self.s = 0.0

    def __enter__(self) -> "span":
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self.s = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        if self._name is not None and TraceAnnotation.is_enabled():
            with _lock:
                _registry[self._name] = _registry.get(self._name, 0.0) + self.s
        return False


class root(span):
    """The span of one scan: carries ``scan``, a new id unless a scan is
    already open on this thread, whose id it then shares."""

    __slots__ = ("scan", "_outer")

    def __init__(self, name: str, **attrs):
        self._outer = current_scan()
        self.scan = next(_scan_ids) if self._outer is None else self._outer
        super().__init__(name, scan=self.scan, **attrs)
        if self._outer is not None:
            self._name = None           # counted in the outer root's time

    def __enter__(self) -> "root":
        _local.scan = self.scan
        return super().__enter__()

    def __exit__(self, *exc) -> bool:
        _local.scan = self._outer
        return super().__exit__(*exc)


def current_scan() -> int | None:
    """The id of the scan open on this thread, or None."""
    return getattr(_local, "scan", None)


def inc(name: str, n: int = 1) -> None:
    """Add ``n`` to counter ``name`` (one of ``COUNTERS``) while a profiler
    session is active."""
    if name not in COUNTERS:
        raise KeyError(name)
    if TraceAnnotation.is_enabled():
        with _lock:
            _registry[name] += n


def snapshot() -> dict[str, float]:
    """The registry now: each counter, and the seconds of each span name
    that closed under a profiler session."""
    with _lock:
        return dict(_registry)
