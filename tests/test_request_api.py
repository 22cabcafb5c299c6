"""The typed QueryRequest currency (core/request.py).

One dataclass describes a query across all four surfaces — api, CLI,
serve engine, wire frontend — with canonicalization and cache-key
fingerprinting living on it, so the surfaces cannot drift. Covers:
wire-dict round-trips, canonical/cache-key parity with the engine,
the unified ``filter=`` kwarg with its ``node_filter=`` deprecation
shim, and each surface constructing/consuming QueryRequest.
"""

import json
import warnings

import numpy as np
import pytest

from repro.core import api
from repro.core.cli import Session
from repro.core.request import (
    QueryRequest,
    QueryResult,
    canonical_request,
    merge_filter_kwargs,
    run_queries,
    run_query,
)
from repro.serve.graph_engine import GraphServeEngine, run_request


@pytest.fixture()
def net():
    n = 300
    net = api.createnetwork(api.createnodeset(n))
    net = api.generate(api.addlayer(net, "er", 1), "er",
                       type="er", p=0.03, seed=1)
    net = api.generate(api.addlayer(net, "wk", 2), "wk",
                       type="2mode", h=30, a=4, seed=2)
    net = api.setnodeattr(
        net, "grp", np.arange(n),
        np.random.default_rng(0).integers(0, 3, n).astype(np.int64),
    )
    return net


# -- construction + round-trips ----------------------------------------------


def test_wire_dict_round_trip():
    q = QueryRequest.khop([1, 2], 3, max_frontier=64,
                          filter={"attr": "grp", "op": "eq", "value": 1})
    d = q.to_dict()
    assert d["kind"] == "khop" and "u" not in d  # None fields omitted
    assert QueryRequest.from_dict(d) == q
    # the wire form is JSON-safe for spec filters
    assert QueryRequest.from_dict(json.loads(json.dumps(d))) == q


def test_from_dict_ignores_unknown_keys():
    q = QueryRequest.from_dict(
        {"kind": "degree", "u": 5, "x_extension": True}
    )
    assert q == QueryRequest.degree(5)


def test_from_any_passthrough_and_type_error():
    q = QueryRequest.degree(5)
    assert QueryRequest.from_any(q) is q
    with pytest.raises(TypeError):
        QueryRequest.from_any("degree 5")


def test_constructors_cover_every_kind(net):
    reqs = [
        QueryRequest.getedge("er", 3, 7),
        QueryRequest.alters(5, max_alters=64),
        QueryRequest.degree([1, 2, 3]),
        QueryRequest.khop([9], 2, max_frontier=64),
        QueryRequest.walkbatch([4, 5], 5, walkers=2, seed=11),
    ]
    for q in reqs:
        # run_query(QueryRequest) == run_request(wire dict): one engine
        a, b = run_query(net, q), run_request(net, q.to_dict())
        if isinstance(a, np.ndarray):
            np.testing.assert_array_equal(a, b)
        else:
            assert type(a) is type(b)


# -- canonicalization + cache keys on the dataclass ---------------------------


def test_canonical_matches_dict_form(net):
    flt = {"attr": "grp", "op": "eq", "value": 1}
    pairs = [
        (QueryRequest.getedge("er", 3, 7, filter=flt),
         {"kind": "getedge", "layer": "er", "u": 3, "v": 7, "filter": flt}),
        (QueryRequest.khop([1, 2], 2, max_frontier=64),
         {"kind": "khop", "sources": [1, 2], "k": 2, "max_frontier": 64}),
    ]
    for q, d in pairs:
        cq, cd = canonical_request(net, q), canonical_request(net, d)
        assert cq.group_key == cd.group_key
        assert cq.cache_key == cd.cache_key
        assert q.cache_key(net) == cd.cache_key


def test_canonical_rejects_bad_requests(net):
    with pytest.raises(ValueError, match="unknown request kind"):
        canonical_request(net, {"kind": "nope"})
    with pytest.raises(KeyError):
        canonical_request(net, {"kind": "getedge", "layer": "er", "u": 1})
    with pytest.raises(KeyError):
        canonical_request(net, QueryRequest.getedge("nolayer", 1, 2))


def test_run_queries_groups_like_engine(net):
    reqs = (
        [QueryRequest.degree(i) for i in range(8)]
        + [QueryRequest.getedge("wk", i, i + 1) for i in range(8)]
    )
    got = run_queries(net, reqs)
    want = [run_query(net, q) for q in reqs]
    assert got == want


# -- the unified filter= kwarg + deprecation shims ----------------------------


def test_node_filter_kwarg_warns_and_still_works(net):
    flt = np.zeros(net.n_nodes, bool)
    flt[::2] = True
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        old = api.getdegree(net, 5, node_filter=flt)
    assert any(issubclass(w.category, DeprecationWarning) for w in rec)
    assert old == api.getdegree(net, 5, filter=flt)


def test_node_filter_warns_on_every_api_surface(net):
    flt = np.ones(net.n_nodes, bool)
    calls = [
        lambda: api.checkedge(net, "er", 1, 2, node_filter=flt),
        lambda: api.getnodealters(net, 1, node_filter=flt),
        lambda: api.getdegree(net, 1, node_filter=flt),
        lambda: api.degreedist(net, node_filter=flt),
        lambda: api.countcomponents(net, node_filter=flt),
        lambda: api.khop(net, [1], 1, node_filter=flt),
        lambda: api.egosample(net, [1], node_filter=flt),
        lambda: api.walkbatch(net, [1], 2, node_filter=flt),
        lambda: api.componentsfast(net, node_filter=flt),
    ]
    for call in calls:
        with warnings.catch_warnings(record=True) as rec:
            warnings.simplefilter("always")
            call()
        assert any(issubclass(w.category, DeprecationWarning) for w in rec)


def test_wire_node_filter_key_maps_to_filter(net):
    flt = {"attr": "grp", "op": "eq", "value": 1}
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        q = QueryRequest.from_dict(
            {"kind": "degree", "u": 5, "node_filter": flt}
        )
    assert any(issubclass(w.category, DeprecationWarning) for w in rec)
    assert q.filter == flt
    assert run_query(net, q) == run_query(
        net, QueryRequest.degree(5, filter=flt)
    )


def test_both_filter_kwargs_is_an_error():
    with pytest.raises(ValueError, match="not both"):
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            merge_filter_kwargs({"attr": "a", "op": "has"},
                                {"attr": "b", "op": "has"})


# -- all four surfaces construct QueryRequest ---------------------------------


def test_api_and_cli_agree_through_queryrequest(net):
    # api surface
    deg_api = api.getdegree(net, 7)
    rec_api = api.khop(net, [3], 2, max_frontier=64)
    # CLI surface (same QueryRequest construction inside the handlers)
    cli = Session(mode="json")
    cli.env["net"] = net
    deg_cli = json.loads(cli.run_line("getdegree(net, 7)"))["result"]
    rec_cli = json.loads(
        cli.run_line("khop(net, 3, k=2, maxfrontier=64)")
    )["result"]
    assert deg_api == deg_cli
    assert [r["nodes"] for r in rec_api] == [r["nodes"] for r in rec_cli]


def test_engine_submit_accepts_queryrequest(net):
    eng = GraphServeEngine(net)
    q = QueryRequest.alters(5, max_alters=64)
    rid = eng.submit(q)
    eng.pump()
    res = eng.result(rid)
    assert res.error is None
    np.testing.assert_array_equal(res.value, run_query(net, q))


def test_engine_timeout_field_travels(net):
    eng = GraphServeEngine(net)
    rid = eng.submit(QueryRequest.degree(5, timeout=60.0))
    eng.pump()
    assert eng.result(rid).error is None
    with pytest.raises(ValueError, match="timeout"):
        eng.submit(QueryRequest.degree(5, timeout=-1.0))


def test_runquery_api_entry(net):
    assert api.runquery(net, {"kind": "degree", "u": 5}) == api.runquery(
        net, QueryRequest.degree(5)
    )


def test_result_record_copies_lists_and_converts_numpy():
    """``to_record`` gives an independent JSON-safe copy: a khop record's
    plain-int lists are copied whole, numpy values are converted, and a
    list holding any numpy value is converted element by element."""
    nodes = list(range(5000))
    value = [{"source": 3, "count": 5000, "nodes": nodes,
              "hops": [1] * 5000}]
    rec = QueryResult(7, "khop", value).to_record()
    assert rec["result"] == value
    assert rec["result"][0]["nodes"] is not nodes
    mixed = QueryResult(8, "alters", [np.int64(4), 2, (np.float32(0.5),),
                                      np.arange(2)]).to_record()["result"]
    assert mixed == [4, 2, [0.5], [0, 1]]
    assert json.loads(json.dumps(mixed)) == mixed
