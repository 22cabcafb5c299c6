"""The comparison that decides ``correct`` fails where the program is wrong.

Each case drives a whole run of a cell (data, network, frontend,
load generator child, reference) with the timed path broken underneath,
and sees the reference comparison fail; the sound run beside them passes.
Under pytest the cells run at their rehearsal sizes on the CPU, where the
harness's look for a chip is skipped (``rehearse=True``).

The control breaks the guarantee ``register10m`` states, exact answers
over every membership:

* ``width_cap``: rows wider than the widest default bucket (128) are cut
  to it, the shortcut a later change to the bucket planner might take;
  hub rows (more than 128 memberships) lose groups.

The faults a served cell can have:

* ``altered`` — one answer of every executor batch is altered where it is
  produced;
* ``half_batch`` — the second half of every coalesced batch gets the
  first half's answers.

Run as a script on the chip, at the cells' own sizes and on several
seeds, it prints each run's compared numbers:

    python bench/tests/test_perfbench_control.py \\
        --workload register10m.point --controls sound,width_cap \\
        --seeds 5,6,7 --seconds 10
"""

from __future__ import annotations

import contextlib
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import pytest  # noqa: E402

from manifest import Cell, load_module  # noqa: E402

# by path: the repository has another module named ``run``
run = load_module(BENCH / "run.py")

WIDTH_CAP = 128


@contextlib.contextmanager
def _patched(owner, attr, value):
    old = getattr(owner, attr)
    setattr(owner, attr, value)
    try:
        yield
    finally:
        setattr(owner, attr, old)


@contextlib.contextmanager
def _executor_fault(wrap):
    from repro.core import request

    table = request._EXECUTORS
    saved = dict(table)
    for kind, fn in saved.items():
        table[kind] = wrap(fn)
    try:
        yield
    finally:
        table.update(saved)


def _altered(fn):
    def executor(net, group_key, creqs):
        values = list(fn(net, group_key, creqs))
        v = values[0]
        if isinstance(v, (int, float)):
            values[0] = v + 1
        elif isinstance(v, list) and v and isinstance(v[0], dict):
            values[0] = [dict(v[0], count=v[0]["count"] + 1)] + v[1:]
        else:
            values[0] = list(v)[:-1] if len(v) else [0]
        return values
    return executor


def _half_batch(fn):
    def executor(net, group_key, creqs):
        if len(creqs) < 2:
            return fn(net, group_key, creqs)
        half = (len(creqs) + 1) // 2
        values = list(fn(net, group_key, creqs[:half]))
        return values + values[: len(creqs) - half]
    return executor


def _width_cap():
    from repro.core import dispatch

    plan = dispatch.plan_buckets

    def capped(deg, max_width, widths=dispatch.DEFAULT_BUCKET_WIDTHS):
        return [(idx, min(w, WIDTH_CAP))
                for idx, w in plan(deg, max_width, widths)]

    return _patched(dispatch, "plan_buckets", capped)


CONTROLS = {
    "sound": contextlib.nullcontext,
    "width_cap": _width_cap,
    "altered": lambda: _executor_fault(_altered),
    "half_batch": lambda: _executor_fault(_half_batch),
}


def run_with(control: str, workload: str, seed: int, seconds: float,
             rehearse: bool, mix_update: dict | None = None,
             prepared: tuple | None = None) -> dict:
    with CONTROLS[control]():
        return run.run_cell(Cell(workload), seed, seconds, False,
                            rehearse=rehearse, mix_update=mix_update,
                            prepared=prepared)


# the rehearsal rate of ``point`` (20 req/s) rarely coalesces two
# requests on the CPU; the half-batch fault needs batches of two or more
BATCHED = {"rate_per_s": 120}

CASES = [
    ("register10m.point", "sound", None, True),
    ("register10m.point", "width_cap", None, False),
    ("register10m.point", "altered", None, False),
    ("register10m.point", "half_batch", BATCHED, False),
]


@pytest.mark.parametrize("workload,control,mix_update,sound", CASES)
def test_comparison_separates_sound_from_broken(workload, control,
                                                mix_update, sound):
    out = run_with(control, workload, seed=20260 + len(control),
                   seconds=2.0, rehearse=True, mix_update=mix_update)
    assert out["checked"] > 0
    assert out["sound"] is sound, out["line"]["checks"]
    # a rehearsal on the CPU is never correct, whatever it compared
    assert out["line"]["correct"] is False


def main(argv=None) -> int:
    import argparse
    import json
    import os

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--controls", default="sound")
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(run.JAX_CACHE)
    from repro.core import compile_cache

    compile_cache.enable()
    for seed in [int(s) for s in args.seeds.split(",")]:
        # one network per seed, served under each control in turn
        prepared = run.prepare(Cell(args.workload), seed, args.rehearse)
        for control in args.controls.split(","):
            out = run_with(control, args.workload, seed, args.seconds,
                           args.rehearse, prepared=prepared)
            line = out["line"]
            print(json.dumps({"control": control, "seed": seed,
                              "sound": out["sound"],
                              "checked": out["checked"],
                              "checks": line["checks"],
                              "attempted": line["attempted"],
                              "failed": line["failed"],
                              "metrics": line["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
