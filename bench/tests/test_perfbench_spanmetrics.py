"""The six readers of the program's span and counter table, on hand-built
engine snapshots (``ctx.engine``), and silent on a program without one."""

from __future__ import annotations

import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH)]

import pytest  # noqa: E402

from manifest import load_module  # noqa: E402
from run import Context  # noqa: E402

MS = 1_000_000  # ns


def _snap(spans: dict, counters: dict, dispatched: int) -> dict:
    return {"batches": {"getedge": 1}, "dispatched": {"getedge": dispatched},
            "trace": {"spans": {k: list(v) for k, v in spans.items()},
                      "counters": dict(counters)}}


BEFORE = _snap(
    {"threadle.frontend.request": (100, 900 * MS),
     "threadle.frontend.wait": (100, 800 * MS),
     "threadle.engine.round": (10, 2_000 * MS),
     "threadle.engine.group": (20, 1_500 * MS),
     "threadle.dispatch.fetch": (40, 100 * MS)},
    {"engine.popped.point": 100, "engine.queue_wait_ns.point": 3_000 * MS,
     "dispatch.buckets": 30},
    dispatched=100,
)
AFTER = _snap(
    {"threadle.frontend.request": (300, 3_900 * MS),
     "threadle.frontend.wait": (300, 3_400 * MS),
     "threadle.engine.round": (60, 42_000 * MS),
     "threadle.engine.group": (120, 41_500 * MS),
     "threadle.dispatch.fetch": (240, 1_100 * MS)},
    {"engine.popped.point": 300, "engine.queue_wait_ns.point": 13_000 * MS,
     "dispatch.buckets": 130},
    dispatched=300,
)

# over the window: 200 requests, 50 rounds, 100 groups, 50 s
EXPECTED = {
    "frontend_ms": (3_000 - 2_600) / 200,
    "queue_wait_ms": 10_000 / 200,
    "pump_busy": 100.0 * 40.0 / 50.0,
    "exec_host_ms": (40_000 - 1_000) / 100,
    "fetch_ms": 1_000 / 100,
    "buckets": 100 / 200,
}


def _read(name: str, before: dict, after: dict, seconds: float = 50.0):
    reader = load_module(BENCH / "metrics" / f"{name}.py")
    return reader.read(Context(engine=(before, after), seconds=seconds))


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_reads_the_change_over_the_window(name):
    assert _read(name, BEFORE, AFTER) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_is_silent_without_the_programs_table(name):
    """A program without the table (an older checkout) gives None."""
    old = {k: v for k, v in BEFORE.items() if k != "trace"}
    assert _read(name, old, old) is None


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_is_silent_where_its_denominator_is_zero(name):
    assert _read(name, BEFORE, BEFORE, seconds=0.0) is None
