"""A configuration, a traffic mix and a per-layer metric added as new files
and manifest entries are found by name, with no edit to the harness.

The test copies ``BENCHMARK.json`` and ``bench/`` to a temporary checkout,
adds a ring network (``configs/ring.json`` + its generator ``ring.py``),
a closed-loop mix of degree and khop requests (``traffic/degrees.json``)
and a counter reader
(``metrics/served.py``), appends their entries, and runs the new cell
there on the CPU with ``--rehearse``.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent

RING = '''
import numpy as np

from graphref import OneModeRaw, PlainNetwork


def generate(cfg, seed):
    n = int(cfg["nodes"])
    ids = np.arange(n, dtype=np.int32)
    return PlainNetwork(n, {"Ring": OneModeRaw(n, ids, (ids + 1) % n)})
'''

SERVED = '''
def read(ctx):
    eng0, eng1 = ctx.engine
    return float(eng1["served"] - eng0["served"])
'''


def _copy_checkout(dst: Path) -> None:
    shutil.copy(ROOT / "BENCHMARK.json", dst / "BENCHMARK.json")
    shutil.copytree(BENCH, dst / "bench", ignore=shutil.ignore_patterns(
        ".jax_cache", ".traces", "__pycache__"))
    (dst / "src").symlink_to(ROOT / "src")


def test_new_cell_is_found_by_name(tmp_path):
    _copy_checkout(tmp_path)
    bench = tmp_path / "bench"
    (bench / "configs" / "ring.json").write_text(json.dumps(
        {"name": "ring", "nodes": 64, "rehearsal": {}}))
    (bench / "configs" / "ring.py").write_text(RING)
    (bench / "traffic" / "degrees.json").write_text(json.dumps(
        {"loop": "closed", "sessions": 2, "max_requests": 400,
         "warmup_s": 1, "keys": "nonisolated",
         "mix": [{"kind": "degree", "weight": 0.5, "nodes_min": 1,
                  "nodes_max": 2, "layers": None},
                 {"kind": "khop", "weight": 0.5, "k": 2,
                  "max_frontier": 4, "layers": ["Ring"]}]}))
    (bench / "metrics" / "served.py").write_text(SERVED)
    manifest = json.loads((tmp_path / "BENCHMARK.json").read_text())
    manifest["configs"].append(
        {"name": "ring", "source": "a ring", "file": "bench/configs/ring.json",
         "reduced": [], "why": "a test configuration"})
    manifest["workloads"].append(
        {"name": "ring.degrees", "config": "ring", "traffic": "degrees",
         "chips": 1, "why": "a test cell"})
    manifest["per_layer"].append(
        {"name": "served.ring", "unit": "req", "better": "higher",
         "source": "program_counter", "layer": "engine",
         "moves": "done_per_s", "workloads": ["ring.degrees"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "ring.degrees",
         "--seed", "3", "--seconds", "1", "--trace", "1", "--rehearse"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["checks"]["wrong_answers"]["value"] == 0, proc.stderr
    assert line["checks"]["answers_compared"]["value"] == line["attempted"]
    assert line["attempted"] > 0 and line["failed"] == 0
    # the two sessions' last requests may be served after the window
    assert line["metrics"]["served.ring"]["value"] >= line["attempted"] - 2
    assert "batch_occupancy.point" not in line["metrics"]
    assert line["correct"] is False  # a CPU rehearsal
    assert proc.returncode == 1


def test_harness_alone_prints_no_result(tmp_path):
    """In a directory with only BENCHMARK.json and bench/ (no program),
    the run fails and prints no result line."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "bench", ignore=shutil.ignore_patterns(
        ".jax_cache", ".traces", "__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "register10m.point",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_no_accelerator_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "register10m.point",
         "--seed", str(2**31 + 5), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 2
    assert proc.stdout.strip() == ""
