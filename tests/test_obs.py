"""Spans and counters of the serving path (core/obs.py).

* the table: span calls and nanoseconds, counters, nesting by thread,
  ``tag`` on the innermost span, and exact totals under many threads;
* ``fetch`` times only device arrays;
* a served run profiled on the CPU: the eight ``threadle.*`` spans lie
  nested on the host plane of the profiler's ``.xplane.pb``;
* ``kernels.<name>`` counts each launch that takes the Pallas path, not
  each trace;
* an engine round on a ``Network`` hands its dispatchers host ids and
  fetches once per group, while the public queries keep returning jax
  arrays;
* the table reaches ``GraphServeEngine.stats`` and the frontend's
  ``/stats``.
"""

import json
import socket
import sys
import threading
import time

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import api, dispatch, obs
from repro.core.traversal import khop_neighborhood
from repro.serve import GraphServeClient, GraphServeEngine, run_request
from repro.serve import assert_results_equal


def _spans(name):
    return obs.snapshot()["spans"].get(name, [0, 0])


def _counter(name):
    return obs.snapshot()["counters"].get(name, 0)


@pytest.fixture(scope="module")
def net():
    n = 300
    net = api.createnetwork(api.createnodeset(n))
    net = api.generate(api.addlayer(net, "er", 1), "er",
                       type="er", p=0.03, seed=1)
    return api.generate(api.addlayer(net, "wk", 2), "wk",
                        type="2mode", h=30, a=4, seed=2)


# -- the table ----------------------------------------------------------------


def test_span_counts_calls_and_nanoseconds():
    c0, ns0 = _spans("test.obs.outer")
    i0, _ = _spans("test.obs.inner")
    for _ in range(3):
        with obs.span("test.obs.outer", round=1):
            time.sleep(0.002)
            with obs.span("test.obs.inner"):
                pass
    c1, ns1 = _spans("test.obs.outer")
    assert c1 - c0 == 3
    assert ns1 - ns0 >= 3 * 2_000_000
    assert _spans("test.obs.inner")[0] - i0 == 3


def test_nesting_is_per_thread_and_tag_reaches_the_innermost():
    seen = {}

    def worker(name):
        with obs.span(f"test.obs.{name}") as outer:
            with obs.span(f"test.obs.{name}.inner") as inner:
                seen[name] = (obs._open()[-1] is inner, len(obs._open()))
                obs.tag(rid=7)
            seen[name + ".after"] = obs._open()[-1] is outer

    threads = [threading.Thread(target=worker, args=(f"t{i}",))
               for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
        assert not t.is_alive()
    for i in range(4):
        assert seen[f"t{i}"] == (True, 2)
        assert seen[f"t{i}.after"]
    assert obs._open() == []
    obs.tag(rid=1)  # no open span: nothing to tag, no error


def test_span_closes_on_an_exception():
    c0 = _spans("test.obs.raises")[0]
    with pytest.raises(ValueError):
        with obs.span("test.obs.raises"):
            raise ValueError("boom")
    assert _spans("test.obs.raises")[0] == c0 + 1
    assert obs._open() == []


def test_counts_are_exact_under_threads():
    n_threads, n_iter = 16, 500
    c0 = _counter("test.obs.hits")
    s0 = _spans("test.obs.threaded")[0]
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def worker():
            for _ in range(n_iter):
                with obs.span("test.obs.threaded"):
                    obs.count("test.obs.hits")
                obs.count("test.obs.hits", 2)

        threads = [threading.Thread(target=worker) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(old)
    assert _counter("test.obs.hits") - c0 == 3 * n_threads * n_iter
    assert _spans("test.obs.threaded")[0] - s0 == n_threads * n_iter


def test_fetch_times_device_arrays_only():
    c0 = _spans(obs.FETCH)[0]
    host = obs.fetch(np.arange(4), np.int64)
    assert _spans(obs.FETCH)[0] == c0
    dev = obs.fetch(jnp.arange(4, dtype=jnp.int32), np.int64)
    assert _spans(obs.FETCH)[0] == c0 + 1
    assert dev.dtype == np.int64
    np.testing.assert_array_equal(host, dev)


# -- kernels.<name> counts launches, not traces -------------------------------


def _intersect(net):
    layer = net.layer("wk")
    u, v = np.arange(8), np.arange(8, 16)
    dispatch.bucketed_edge_value(layer, u, v, use_pallas=True, interpret=True)


def _segmented_union(net):
    dispatch.bucketed_node_alters(
        net.layer("wk"), np.arange(8), 64, use_pallas=True, interpret=True
    )


def _frontier(net):
    # the frontier kernel compacts the padded loop's hops, which a
    # two-mode layer takes (one-mode hops take the one-pass program)
    khop_neighborhood(net, jnp.arange(4, dtype=jnp.int32), 1,
                      max_frontier=32, layer_names=["wk"],
                      use_pallas=True, interpret=True)


@pytest.mark.parametrize("kernel, call", [
    ("intersect", _intersect),
    ("segmented_union", _segmented_union),
    ("frontier", _frontier),
])
def test_kernel_counter_counts_each_eager_launch(net, kernel, call):
    """Two calls of one shape are one trace and two launches: the counter
    reads 2 where a count of trace-time entries read 1."""
    c0 = _counter(f"kernels.{kernel}")
    call(net)
    one = _counter(f"kernels.{kernel}") - c0
    call(net)
    assert one == 1
    assert _counter(f"kernels.{kernel}") - c0 == 2


def test_bucket_launches_are_counted(net):
    layer = net.layer("wk")
    deg = np.diff(np.asarray(layer.memb.indptr))
    u = np.array([int(np.argmin(deg)), int(np.argmax(deg))])
    n_buckets = len(dispatch.plan_buckets(
        deg[u], layer.max_memberships, widths=(1,)))
    b0 = _counter("dispatch.buckets")
    l0 = _spans("threadle.dispatch.launch")[0]
    dispatch.bucketed_edge_value(layer, u, u, widths=(1,))
    assert _counter("dispatch.buckets") - b0 == n_buckets
    assert _spans("threadle.dispatch.launch")[0] - l0 == n_buckets


# -- host ids on the served path ------------------------------------------------


def test_engine_round_fetches_once_per_group_from_host_ids(net):
    reqs = [
        {"kind": "getedge", "layer": "wk", "u": 1, "v": 2},
        {"kind": "getedge", "layer": "wk", "u": 3, "v": 40},
        {"kind": "getedge", "layer": "wk", "u": 5, "v": 6},
        {"kind": "alters", "u": 7, "layers": ["wk"], "max_alters": 16},
        {"kind": "alters", "u": 8, "layers": ["wk"], "max_alters": 16},
        {"kind": "degree", "u": 10},
        {"kind": "degree", "u": [11, 12, 13]},
    ]
    eng = GraphServeEngine(net)
    rids = [eng.submit(r) for r in reqs]
    g0 = _spans("threadle.engine.group")[0]
    f0 = _spans(obs.FETCH)[0]
    h0 = _counter("dispatch.host_ids")
    d0 = _counter("dispatch.device_ids")
    assert eng.pump() == len(reqs)
    groups = _spans("threadle.engine.group")[0] - g0
    assert groups == 3
    assert _spans(obs.FETCH)[0] - f0 == groups
    assert _counter("dispatch.host_ids") > h0
    assert _counter("dispatch.device_ids") == d0
    for rid, req in zip(rids, reqs):
        got = eng.result(rid)
        assert got.error is None
        assert_results_equal(got.value, run_request(net, req))


@pytest.mark.parametrize("ids", ["int", "list", "device"])
def test_public_queries_still_return_jax_arrays(net, ids):
    make = {
        "int": lambda a: int(a[0]),
        "list": lambda a: a.tolist(),
        "device": jnp.asarray,
    }[ids]
    u = np.array([1, 4, 9], np.int32)
    v = np.array([2, 5, 8], np.int32)
    rich = np.arange(net.n_nodes) % 2 == 0
    out = [
        net.edge_value("wk", make(u), make(v)),
        net.edge_value("er", make(u), make(v)),
        *net.node_alters(make(u), 16, ["wk"]),
        *net.node_alters(make(u), 16),
        net.degree(make(u)),
        net.degree(make(u), node_filter=rich),
    ]
    for x in out:
        assert isinstance(x, jax.Array)
        assert x.shape[0] == (1 if ids == "int" else 3)


# -- stats ----------------------------------------------------------------------


def test_engine_stats_carry_the_table(net):
    eng = GraphServeEngine(net)
    eng.serve([{"kind": "getedge", "layer": "wk", "u": 1, "v": 2},
               {"kind": "alters", "u": 3, "max_alters": 16}])
    trace = eng.stats["trace"]
    assert set(trace) == {"spans", "counters"}
    for name in ("threadle.engine.submit", "threadle.engine.round",
                 "threadle.engine.group", "threadle.dispatch.plan",
                 "threadle.dispatch.launch", "threadle.dispatch.fetch"):
        calls, ns = trace["spans"][name]
        assert calls >= 1 and ns > 0
    assert trace["counters"]["engine.popped.point"] >= 2
    assert trace["counters"]["engine.queue_wait_ns.point"] > 0
    json.dumps(trace)


# -- the profiler's view --------------------------------------------------------

SPANS = ("threadle.frontend.request", "threadle.frontend.wait",
         "threadle.engine.submit", "threadle.engine.round",
         "threadle.engine.group", "threadle.dispatch.plan",
         "threadle.dispatch.launch", "threadle.dispatch.fetch")


def _host_spans(xplane):
    """{(plane, line index): [(name, start, end, stats)]} of threadle
    spans; a line is one thread (threads may share a line name)."""
    from jax.profiler import ProfileData

    out = {}
    for plane in ProfileData.from_file(str(xplane)).planes:
        if plane.name.startswith("/device:"):
            continue
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("threadle."):
                    s = float(ev.start_ns)
                    out.setdefault((plane.name, i), []).append(
                        (ev.name, s, s + float(ev.duration_ns),
                         dict(ev.stats)))
    return out


def _within(inner, outers):
    return any(o[1] <= inner[1] and inner[2] <= o[2] for o in outers)


def test_profiled_serving_nests_the_spans_on_the_host_plane(net, tmp_path):
    fe = api.servenet(net)
    try:
        with GraphServeClient(*fe.address) as c:
            c.query({"kind": "getedge", "layer": "wk", "u": 1, "v": 9})
            jax.profiler.start_trace(str(tmp_path))
            try:
                for i in range(6):
                    c.query({"kind": "getedge", "layer": "wk",
                             "u": 10 + i, "v": 40 + i})
                    c.query({"kind": "alters", "u": 20 + i,
                             "max_alters": 32})
                # the connection's next line starts after the last query's
                # spans closed, so none of them is cut by stop_trace
                c._call(c._envelope("ping"))
            finally:
                jax.profiler.stop_trace()
        stats = _http_stats(fe.address)
    finally:
        fe.close()
    xplane = sorted(tmp_path.glob("**/*.xplane.pb"))[-1]
    lines = _host_spans(xplane)
    names = {ev[0] for evs in lines.values() for ev in evs}
    assert names >= set(SPANS)
    assert all(plane.startswith("/host:") for plane, _ in lines)

    # the pump thread: round ⊃ group ⊃ plan / launch / fetch
    pump = [evs for evs in lines.values()
            if any(e[0] == "threadle.engine.round" for e in evs)]
    assert len(pump) == 1
    evs = pump[0]
    rounds = [e for e in evs if e[0] == "threadle.engine.round"]
    groups = [e for e in evs if e[0] == "threadle.engine.group"]
    assert groups and all(_within(g, rounds) for g in groups)
    assert {g[3]["kind"] for g in groups} == {"getedge", "alters"}
    for name in ("threadle.dispatch.plan", "threadle.dispatch.launch",
                 "threadle.dispatch.fetch"):
        inner = [e for e in evs if e[0] == name]
        assert inner and all(_within(e, groups) for e in inner), name
    launches = [e for e in evs if e[0] == "threadle.dispatch.launch"]
    assert all({"width", "rows"} <= set(e[3]) for e in launches)

    # a connection thread: request ⊃ submit, wait; both carry the rid
    conn = [evs for evs in lines.values()
            if any(e[0] == "threadle.frontend.request" for e in evs)]
    assert len(conn) == 1 and conn[0] is not pump[0]
    evs = conn[0]
    requests = [e for e in evs if e[0] == "threadle.frontend.request"
                and "rid" in e[3]]
    assert len(requests) == 12
    for name in ("threadle.engine.submit", "threadle.frontend.wait"):
        inner = [e for e in evs if e[0] == name]
        assert len(inner) == 12 and all(_within(e, requests) for e in inner)
    waits = [e for e in evs if e[0] == "threadle.frontend.wait"]
    assert sorted(e[3]["rid"] for e in waits) == \
        sorted(e[3]["rid"] for e in requests)

    trace = stats["engine"]["trace"]
    assert trace["spans"]["threadle.frontend.request"][0] >= 13


def _http_stats(addr) -> dict:
    s = socket.create_connection(addr, timeout=10)
    try:
        s.sendall(b"GET /stats HTTP/1.0\r\n\r\n")
        data = b""
        while chunk := s.recv(65536):
            data += chunk
    finally:
        s.close()
    head, body = data.split(b"\r\n\r\n", 1)
    assert head.startswith(b"HTTP/1.0 200")
    return json.loads(body)
