"""Spans and counters of the serving path, on the profiler's clock.

``span(name, **meta)`` times one block of host work in two places. It
opens a ``jax.profiler.TraceAnnotation``, so that in a profiled run the
span lies on the host's plane of the same ``.xplane.pb`` as the device's
ops, and it adds one call and its wall nanoseconds
(``time.perf_counter_ns``) to a process-wide table. ``count(name, n)``
adds to an integer counter in the same table; ``snapshot()`` copies the
table out, and the serve engine reports it as ``stats["trace"]``.

Spans nest by thread: the parent of a span is the span that encloses it
on its own thread, on the profiler's timeline as on the stack that
``tag`` reads. There is no switch. With no profiler running a span costs
a few microseconds, so spans mark rounds, groups, buckets and requests,
never single node ids.

``fetch(x)`` is ``np.asarray(x)`` for the serving path: a device array's
copy to the host, and the wait for the device that precedes it, is timed
as the span ``threadle.dispatch.fetch``. A list or tuple comes over in
one ``jax.device_get``, under one span.
"""

from __future__ import annotations

import threading
import time

import jax
import numpy as np
from jax.profiler import TraceAnnotation

__all__ = ["span", "tag", "count", "fetch", "snapshot"]

FETCH = "threadle.dispatch.fetch"

_lock = threading.Lock()
_spans: dict[str, list[int]] = {}  # name -> [calls, total ns]
_counters: dict[str, int] = {}
_local = threading.local()


def _open() -> list:
    """The spans open on this thread, innermost last."""
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


class span:
    """``with span(name, **meta):`` times the block (see the module)."""

    __slots__ = ("name", "_ann", "_stack", "_t0")

    def __init__(self, name: str, **meta):
        self.name = name
        self._ann = TraceAnnotation(name, **meta)

    def __enter__(self) -> "span":
        self._stack = _open()
        self._stack.append(self)
        self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        ns = time.perf_counter_ns() - self._t0
        self._ann.__exit__(*exc)
        self._stack.pop()
        with _lock:
            entry = _spans.get(self.name)
            if entry is None:
                _spans[self.name] = [1, ns]
            else:
                entry[0] += 1
                entry[1] += ns


def tag(**meta) -> None:
    """Add metadata to the innermost span open on this thread (a value
    known only after the span opened, such as a request's engine id)."""
    stack = _open()
    if stack:
        stack[-1]._ann.set_metadata(**meta)


def count(name: str, n: int = 1) -> None:
    with _lock:
        _counters[name] = _counters.get(name, 0) + int(n)


def fetch(x, dtype=None):
    """``np.asarray(x, dtype)``, timed and counted where ``x`` is a
    device array; a list or tuple -> a list of host arrays, all copied
    in one ``jax.device_get``."""
    if isinstance(x, (list, tuple)):
        if not any(isinstance(a, jax.Array) for a in x):
            return [np.asarray(a, dtype) for a in x]
        with span(FETCH):
            return [np.asarray(a, dtype) for a in jax.device_get(list(x))]
    if not isinstance(x, jax.Array):
        return np.asarray(x, dtype)
    with span(FETCH):
        return np.asarray(x, dtype)


def snapshot() -> dict:
    """-> {"spans": {name: [calls, total_ns]}, "counters": {name: n}}."""
    with _lock:
        return {
            "spans": {k: list(v) for k, v in _spans.items()},
            "counters": dict(_counters),
        }
