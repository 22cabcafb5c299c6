"""Host spans around the program's layer boundaries, for a traced run.

The program has no spans of its own. In a ``--trace 1`` run the benchmark
wraps a few of its functions in ``jax.profiler.TraceAnnotation`` so that
the profiler's trace shows, on the host's timeline beside the device's,
what the server was doing:

* ``frontend.line`` — a wire request on its connection thread, from the
  line read to the reply written (``serve/frontend.py``);
* ``engine.pump`` — one pump round (``serve/graph_engine.py``);
* ``exec.<kind>`` — one coalesced group's executor (``core/request.py``);
* ``dispatch.plan`` — one bucket plan (``core/dispatch.py``).

A function that a later version of the program renames is skipped, and
its span goes missing from the trace; no metric depends on these spans,
only the labels of ``breakdown.idle_gaps``. ``Spans`` undoes every
wrapper on exit.
"""

from __future__ import annotations

import functools
import importlib

# (module, class or None, attribute, span name)
WRAPPED = (
    ("repro.serve.frontend", "_Handler", "_handle_line", "frontend.line"),
    ("repro.serve.graph_engine", "GraphServeEngine", "pump", "engine.pump"),
    ("repro.core.dispatch", None, "plan_buckets", "dispatch.plan"),
)
EXECUTORS = ("repro.core.request", "_EXECUTORS")


def _annotated(fn, name: str):
    from jax.profiler import TraceAnnotation

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        with TraceAnnotation(name):
            return fn(*args, **kwargs)

    return wrapper


class Spans:
    """Context manager that installs the wrappers and takes them off."""

    def __init__(self):
        self._undo: list = []

    def __enter__(self) -> "Spans":
        for mod_name, cls_name, attr, span in WRAPPED:
            try:
                owner = importlib.import_module(mod_name)
                if cls_name is not None:
                    owner = getattr(owner, cls_name)
                fn = getattr(owner, attr)
            except (ImportError, AttributeError):
                continue
            setattr(owner, attr, _annotated(fn, span))
            self._undo.append((owner, attr, fn))
        try:
            table = getattr(importlib.import_module(EXECUTORS[0]),
                            EXECUTORS[1])
        except (ImportError, AttributeError):
            table = {}
        for kind, fn in list(table.items()):
            table[kind] = _annotated(fn, f"exec.{kind}")
            self._undo.append((table, kind, fn))
        return self

    def __exit__(self, *exc) -> None:
        for owner, attr, fn in reversed(self._undo):
            if isinstance(owner, dict):
                owner[attr] = fn
            else:
                setattr(owner, attr, fn)
        self._undo.clear()
