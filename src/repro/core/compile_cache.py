"""JAX persistent compilation cache for the entry points.

Every degree bucket, walk fleet and kernel shape is its own XLA program,
so a cold process spends much of its first requests compiling. The entry
points (``chip_smoke.py``, ``repro.core.cli``, ``benchmarks/run.py``)
call :func:`enable` once, before their first compile:

* ``JAX_COMPILATION_CACHE_DIR`` set — JAX already reads it; nothing here
  names another directory.
* unset — the cache lives at ``<checkout>/.jax_cache``, a fixed path, so
  a second run from the same checkout finds what the first one wrote.

Caching thresholds drop to zero so that the small per-bucket programs,
which compile in well under JAX's default one second, are cached too.
:func:`stats` counts the cache requests and hits this process made, and
the compiles (or loads from the cache) of each program by name.
"""

from __future__ import annotations

import os
import threading
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"

_counts = {"requests": 0, "hits": 0}
_programs: dict[str, list] = {}  # fun_name -> [compiles, seconds]
_lock = threading.Lock()
_listening = False


def _on_event(event: str, **_kw) -> None:
    key = {
        "/jax/compilation_cache/compile_requests_use_cache": "requests",
        "/jax/compilation_cache/cache_hits": "hits",
    }.get(event)
    if key is not None:
        with _lock:
            _counts[key] += 1


def _on_duration(event: str, duration: float, **kw) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        name = str(kw.get("fun_name", "?"))
        with _lock:
            entry = _programs.setdefault(name, [0, 0.0])
            entry[0] += 1
            entry[1] += float(duration)


def enable() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    global _listening
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    if not _listening:
        jax.monitoring.register_event_listener(_on_event)
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _listening = True
    return path


def stats() -> dict:
    """{"requests", "hits", "misses"} of this process's cached compiles,
    and "by_program": {fun_name: [compiles, seconds]}."""
    with _lock:
        req, hits = _counts["requests"], _counts["hits"]
        by_program = {k: list(v) for k, v in _programs.items()}
    return {"requests": req, "hits": hits, "misses": req - hits,
            "by_program": by_program}
