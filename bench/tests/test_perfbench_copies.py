"""The reader of ``copies_per_kcand.khop``: the segment-copy kernel's
window copies per thousand candidates over the window, silent on a
program without the counter; and a CPU rehearsal of ``kron22.khop``
through the kernel stays sound."""

from __future__ import annotations

import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import pytest  # noqa: E402

from manifest import Cell, load_module  # noqa: E402

run = load_module(BENCH / "run.py")

CELL = "kron22.khop"
METRIC = "copies_per_kcand.khop"


def _snap(counters) -> dict:
    return {"batches": {"khop": 1}, "dispatched": {"khop": 1},
            "trace": {"spans": {}, "counters": dict(counters)}}


BEFORE = _snap({"traversal.cand": 1_000, "traversal.copies": 40})
AFTER = _snap({"traversal.cand": 501_000, "traversal.copies": 1_040})


def _read(before, after):
    ctx = run.Context(engine=(before, after), seconds=50.0)
    return Cell(CELL).reader(METRIC).read(ctx)


def test_cell_lists_the_reader():
    listed = {m["name"]: m for m in Cell(CELL).per_layer}
    assert listed[METRIC]["layer"] == "kernels"
    assert listed[METRIC]["workloads"] == [CELL]


@pytest.mark.parametrize("before,after,want", [
    (BEFORE, AFTER, 1000 * 1_000 / 500_000),
    # every run one id: one copy per candidate
    (BEFORE, _snap({"traversal.cand": 2_000, "traversal.copies": 1_040}),
     1000.0),
])
def test_reader_reads_the_change_over_the_window(before, after, want):
    assert _read(before, after) == pytest.approx(want)


@pytest.mark.parametrize("before,after", [
    # a program without the table (an older checkout)
    ({"batches": {}, "dispatched": {}}, {"batches": {}, "dispatched": {}}),
    # a program with the table but without the counter (the parent's)
    (_snap({"traversal.cand": 1_000}), _snap({"traversal.cand": 9_000})),
    # no hop in the window
    (BEFORE, BEFORE),
])
def test_reader_is_silent_without_copies(before, after):
    assert _read(before, after) is None


def test_rehearsal_through_the_kernel_is_sound():
    from repro.core import obs

    before = obs.snapshot()["counters"].get("traversal.copies", 0)
    out = run.run_cell(Cell(CELL), 2**31 + 1717, 2.0, False, rehearse=True)
    assert out["checked"] > 0
    assert out["sound"] is True, out["line"]["checks"]
    assert obs.snapshot()["counters"]["traversal.copies"] > before
