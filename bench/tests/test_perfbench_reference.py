"""The plain reference's answers and byte counts on a hand-checked network,
and the latency percentiles with failed requests counted as missing."""

from __future__ import annotations

import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH)]

import numpy as np  # noqa: E402
import pytest  # noqa: E402

import latency  # noqa: E402
from graphref import OneModeRaw, PlainNetwork, TwoModeRaw  # noqa: E402
from manifest import load_module  # noqa: E402


@pytest.fixture
def tiny():
    # W: node 0 in groups {0, 1}, node 1 in {1} (listed twice), node 2 in
    # {0, 1, 2}, node 3 in none. C: edges 0-1, 1-2, a self-loop on 1 and a
    # repeat of 0-1.
    w = TwoModeRaw(4, 3, nodes=[0, 0, 1, 1, 2, 2, 2],
                   groups=[0, 1, 1, 1, 0, 1, 2])
    c = OneModeRaw(4, src=[0, 1, 1, 1], dst=[1, 2, 1, 0])
    return PlainNetwork(4, {"W": w, "C": c})


def test_answers(tiny):
    reqs = [
        {"kind": "getedge", "layer": "W", "u": 0, "v": 2},
        {"kind": "getedge", "layer": "C", "u": 2, "v": 1},
        {"kind": "alters", "u": 0, "layers": ["W"], "max_alters": 8},
        {"kind": "alters", "u": 2, "layers": ["W", "C"], "max_alters": 1},
        {"kind": "degree", "u": [0, 3]},
        {"kind": "degree", "u": [1]},
        {"kind": "khop", "sources": 0, "k": 2, "max_frontier": 4,
         "layers": ["C"]},
    ]
    assert tiny.answers(reqs) == [
        2.0, 1.0, [1, 2], [0], [3, 0], 3,
        [{"source": 0, "count": 2, "nodes": [1, 2], "hops": [1, 2]}],
    ]


@pytest.mark.parametrize("req,nbytes", [
    # rows of u and v located (2 x 8 B), their 2 + 3 group ids read
    ({"kind": "getedge", "layer": "W", "u": 0, "v": 2}, 16 + 4 * 5),
    # u's row and 2 groups (8 + 8 B), then group 0 (8 + 2 x 4 B) and
    # group 1 (8 + 3 x 4 B)
    ({"kind": "alters", "u": 0, "layers": ["W"], "max_alters": 8},
     8 + 8 + 16 + 20),
    # one row per node and layer: 2 nodes x 2 layers x 8 B
    ({"kind": "degree", "u": [0, 3]}, 32),
    # hop 1 expands node 0 (8 + 1 x 4 B), hop 2 node 1 (8 + 2 x 4 B)
    ({"kind": "khop", "sources": 0, "k": 2, "max_frontier": 4,
      "layers": ["C"]}, 12 + 16),
])
def test_request_bytes(tiny, req, nbytes):
    answer = tiny.answers([req])[0]
    assert tiny.request_bytes(req, answer) == nbytes


def test_roofline_reader_divides_bytes_by_bandwidth_and_busy_time():
    reader = load_module(BENCH / "metrics" / "hbm_roofline.py")

    class Ctx:
        trace = {"busy_s": 2.0, "window_s": 10.0}
        peaks = {"hbm_bytes_per_s": 1e9}
        window_bytes = 5e7

    assert reader.read(Ctx) == pytest.approx(2.5)
    Ctx.trace = None
    assert reader.read(Ctx) is None


def test_percentiles_count_failures_as_missing():
    lat = [float(x) for x in range(10, 0, -1)]
    assert latency.percentile(lat, 0, 50) == 5.0
    assert latency.percentile(lat, 0, 95) == 10.0
    # one failure of eleven: the 95th percentile lands on it
    assert latency.percentile(lat, 1, 50) == 6.0
    assert latency.percentile(lat, 1, 95) == latency.MISSED_MS
    assert latency.percentile([], 3, 50) == latency.MISSED_MS


def test_spread_is_the_quartile_gap_over_the_median():
    vals = [10.0, 11.0, 12.0, 13.0, 14.0, 15.0]
    q1, q2, q3 = latency.quantiles(vals)
    assert latency.spread(vals) == pytest.approx((q3 - q1) / q2)
    assert q2 == pytest.approx(12.5)


def test_replies_are_classified():
    run = load_module(BENCH / "run.py")
    reqs = [{"kind": "degree", "u": i} for i in range(5)]
    rec = [
        [0, 0.0, 0.0, 0.0, 0.1, '{"id": 0, "ok": true, "result": 3}', None],
        [1, 0.0, 0.0, 0.0, 0.1, '{"id": 1, "ok": false, "code": "shed"}',
         None],
        [2, 0.0, 0.0, 0.0, 0.1,
         '{"id": 2, "ok": true, "result": 1, "degraded": true}', None],
        [3, 0.0, 0.0, 0.0, 0.1,
         '{"id": 3, "ok": false, "code": "engine_error", "error": "x"}',
         None],
        [4, 0.0, 0.0, 0.0, 0.1, None, "ConnectionError: reset"],
    ]
    status = [r["status"] for r in run.classify(rec, reqs)]
    assert status == ["ok", "failed", "failed", "error", "failed"]


def test_failed_requests_never_compare():
    run = load_module(BENCH / "run.py")
    w = TwoModeRaw(3, 1, nodes=[0, 1], groups=[0, 0])
    plain = PlainNetwork(3, {"W": w})
    rows = [{"status": "ok", "req": {"kind": "degree", "u": 0},
             "result": 1},
            {"status": "ok", "req": {"kind": "degree", "u": 2},
             "result": 5},
            {"status": "failed", "req": {"kind": "degree", "u": 1},
             "result": None}]
    assert run.check(plain, rows) == {"checked": 2, "wrong": 1}
    assert np.isclose(rows[0]["want"], 1)
