"""The trace reduction on a small recorded trace.

``data/trace_slice.json`` holds the events of a 40 ms slice of a traced
window of ``khop`` searches on a Graph500 scale-22 graph, served on one
TPU v5e chip: the ``XLA Ops`` line of the device plane and the host's
window span, cut from the profiler's ``.xplane.pb``.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH)]

import pytest  # noqa: E402

from manifest import load_module  # noqa: E402

trace = load_module(BENCH / "trace.py")


@pytest.fixture(scope="module")
def recorded():
    doc = json.loads((Path(__file__).parent / "data" /
                      "trace_slice.json").read_text())
    lo, hi = doc["slice"]
    evs = [tuple(e) for e in doc["events"] if e[2] != trace.WINDOW_SPAN]
    # the slice stands in for the window
    evs.append(("/host:CPU", "python", trace.WINDOW_SPAN, lo, hi))
    return evs, lo, hi


def _busy_by_hand(evs, lo, hi):
    ops = sorted((max(s, lo), min(e, hi)) for p, line, _, s, e in evs
                 if p.startswith("/device:") and line == trace.OPS_LINE
                 and e > lo and s < hi)
    busy, end = 0.0, lo
    for s, e in ops:
        if e > end:
            busy += e - max(s, end)
            end = e
    return busy / 1e9


def test_busy_time_is_the_union_of_ops_in_the_window(recorded):
    evs, lo, hi = recorded
    out = trace.reduce(evs)
    assert out["chips"] == 1
    assert out["window_s"] == pytest.approx((hi - lo) / 1e9)
    assert out["busy_s"] == pytest.approx(_busy_by_hand(evs, lo, hi))
    assert 0 < out["busy_s"] < out["window_s"]


def test_gaps_and_ops_add_up(recorded):
    evs, lo, hi = recorded
    out = trace.reduce(evs)
    assert len(out["device_ops"]) <= trace.TOP
    assert len(out["idle_gaps"]) <= trace.TOP
    ops = [d for _, d in out["device_ops"]]
    assert ops == sorted(ops, reverse=True)
    gaps = sum(d for _, d in out["idle_gaps"])
    labels = {n for n, _ in out["idle_gaps"]}
    assert labels <= {trace.NO_REQUEST, "frontend.line", "engine.pump",
                      "dispatch.plan"} | {n for n in labels
                                          if n.startswith("exec.")}
    if len(out["idle_gaps"]) < trace.TOP:
        assert gaps == pytest.approx(out["window_s"] - out["busy_s"])


def test_innermost_span_labels_a_gap():
    evs = [
        ("/device:TPU:0", "XLA Ops", "fusion", 10.0, 20.0),
        ("/device:TPU:0", "XLA Ops", "sort", 30.0, 35.0),
        ("/device:TPU:0", "XLA Ops", "sort", 26.0, 29.0),
        ("/device:TPU:0", "XLA Modules", "jit_step", 10.0, 35.0),
        ("/host:CPU", "python", "bench.window", 0.0, 50.0),
        ("/host:CPU", "pump", "engine.pump", 15.0, 40.0),
        ("/host:CPU", "pump", "exec.alters", 22.0, 28.0),
        ("/host:CPU", "conn", "frontend.line", 0.0, 40.0),
    ]
    out = trace.reduce(evs)
    assert out["busy_s"] == pytest.approx(18e-9)
    assert out["window_s"] == pytest.approx(50e-9)
    # gaps: 0-10 (a connection waits), 20-26 (inside exec.alters), 29-30
    # (the pump round around it), 35-50 (nothing in flight)
    assert dict(out["idle_gaps"]) == pytest.approx({
        "frontend.line": 10e-9, "exec.alters": 6e-9,
        "engine.pump": 1e-9, trace.NO_REQUEST: 15e-9})


def test_no_device_plane_reads_nothing():
    assert trace.reduce([("/host:CPU", "python", "bench.window", 0, 1)]) \
        is None
