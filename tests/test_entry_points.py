"""Entry-point plumbing the chip depends on, checked in child processes.

* The persistent compilation cache (core/compile_cache.py) lives where
  ``JAX_COMPILATION_CACHE_DIR`` says, else at ``<checkout>/.jax_cache``,
  and a second identical process hits it.
* Importing the engine creates no JAX backend: a parent that imports
  ``repro`` and then starts a JAX child must not hold the device.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]

_CACHED_RUN = """
import json, jax, jax.numpy as jnp
from repro.core import compile_cache
path = compile_cache.enable()
jax.jit(lambda x: x * 3 + 1)(jnp.arange(5)).block_until_ready()
print(json.dumps({"path": path, **compile_cache.stats()}))
"""


def _child(code: str, **env) -> str:
    full = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"))
    full.pop("JAX_COMPILATION_CACHE_DIR", None)
    full.update(env)
    out = subprocess.run(
        [sys.executable, "-c", code], env=full, cwd=ROOT,
        capture_output=True, text=True, check=True, timeout=300,
    )
    return out.stdout.strip().splitlines()[-1]


def test_cache_dir_from_env_is_used_and_hit(tmp_path):
    cache = tmp_path / "cache"
    first = json.loads(_child(_CACHED_RUN, JAX_COMPILATION_CACHE_DIR=str(cache)))
    assert first["path"] == str(cache)
    assert first["requests"] >= 1 and first["hits"] == 0
    assert any(cache.iterdir())
    second = json.loads(
        _child(_CACHED_RUN, JAX_COMPILATION_CACHE_DIR=str(cache))
    )
    assert second["hits"] >= 1 and second["misses"] == 0


def test_cache_dir_defaults_to_checkout():
    first = json.loads(_child(_CACHED_RUN))
    assert first["path"] == str(ROOT / ".jax_cache")
    assert any((ROOT / ".jax_cache").iterdir())
    second = json.loads(_child(_CACHED_RUN))
    assert second["hits"] >= 1 and second["misses"] == 0


def test_engine_import_creates_no_backend():
    code = (
        "import sys; sys.path.insert(0, 'benchmarks'); "
        "import repro.core, repro.serve, repro.kernels.ops, repro.core.cli; "
        "import run; "
        "from jax._src import xla_bridge; "
        "print(xla_bridge.backends_are_initialized())"
    )
    assert _child(code) == "False"
