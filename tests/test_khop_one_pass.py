"""The one-pass k-hop over one-mode layers against a plain BFS.

Graphs are Graph500 Kronecker graphs (A, B, C = 0.57, 0.19, 0.19, edge
factor 16, permuted labels) at scales 8 to 12, made here with numpy. The
plain BFS follows the benchmark reference's definition: an undirected
edge list without self-loops or repeats; each hop keeps the
``max_frontier`` smallest ids reached from the last hop's nodes that no
earlier hop kept.
"""

import numpy as np
import pytest

from repro.core import api, create_network, obs, one_mode_from_edges
from repro.core import traversal
from repro.core.csr import SENTINEL
from repro.core.layers import add_edges, delete_edges, has_overlay
from repro.serve import GraphServeClient


def kronecker(scale: int, seed: int, edge_factor: int = 16):
    rng = np.random.default_rng(seed)
    n, m = 1 << scale, edge_factor << scale
    i = np.zeros(m, np.int64)
    j = np.zeros(m, np.int64)
    for level in range(scale):
        ii = rng.random(m) > 0.57 + 0.19
        jj = rng.random(m) > np.where(ii, 0.19 / (1 - 0.76), 0.57 / 0.76)
        i |= ii.astype(np.int64) << level
        j |= jj.astype(np.int64) << level
    perm = rng.permutation(n)
    return n, perm[i], perm[j]


def adjacency(n, src, dst) -> list[np.ndarray]:
    keep = src != dst
    a = np.concatenate([src[keep], dst[keep]])
    b = np.concatenate([dst[keep], src[keep]])
    key = np.unique(a * n + b)
    rows = np.split(key % n, np.searchsorted(key // n, np.arange(1, n)))
    return [r.astype(np.int64) for r in rows]


def plain_bfs(adj, source: int, k: int, mf: int,
              nf=None) -> list[list[int]]:
    """Per hop, the sorted ids first reached there (at most ``mf``), only
    ids that ``nf`` selects when it is given."""
    visited, frontier, hops = {source}, [source], []
    for _ in range(k):
        cand = np.unique(np.concatenate([adj[f] for f in frontier]
                                        + [np.zeros(0, np.int64)]))
        new = [int(x) for x in cand
               if int(x) not in visited and (nf is None or nf[x])][:mf]
        visited.update(new)
        hops.append(new)
        frontier = new
    return hops


def served_hops(nodes, hops_of_slot, row, k) -> list[list[int]]:
    out = []
    for h in range(1, k + 1):
        g = nodes[row][hops_of_slot == h]
        out.append(g[g != SENTINEL].tolist())
    return out


_GRAPHS: dict = {}


def graph(scale: int):
    if scale not in _GRAPHS:
        n, src, dst = kronecker(scale, seed=100 + scale)
        net = create_network(n).with_layer(
            "g", one_mode_from_edges(n, src, dst)
        )
        _GRAPHS[scale] = (net, adjacency(n, src, dst))
    return _GRAPHS[scale]


def sources_of(adj, B: int, seed: int) -> np.ndarray:
    """B roots with repeats: the two largest hubs, an isolated vertex,
    and seeded draws over the non-isolated ones."""
    deg = np.array([a.size for a in adj])
    rng = np.random.default_rng(seed)
    hubs = list(np.argsort(-deg)[:2])
    isolated = list(np.flatnonzero(deg == 0)[:1])
    draws = list(rng.choice(np.flatnonzero(deg > 0), max(B, 1)))
    picked = (hubs + isolated + draws)[:B]
    if B > 4:
        picked[-1] = picked[0]  # a repeat
    return np.asarray(picked, np.int64)


def _counters():
    c = obs.snapshot()["counters"]
    return {k: c[k] for k in ("traversal.hops", "traversal.cand",
                               "traversal.slots", "traversal.launches")}


@pytest.mark.parametrize("scale", [8, 10, 12])
@pytest.mark.parametrize("k", [1, 2, 3])
@pytest.mark.parametrize("mf", [3, 64, None])
@pytest.mark.parametrize("B", [1, 5, 37])
def test_one_pass_matches_plain_bfs(scale, k, mf, B):
    net, adj = graph(scale)
    mf = net.n_nodes if mf is None else mf
    src = sources_of(adj, B, seed=scale * 100 + k + B)
    before = _counters()
    nodes, mask, hops = traversal.khop_host(net, src, k, max_frontier=mf)
    after = _counters()
    assert after["traversal.launches"] > before["traversal.launches"]
    assert nodes.shape == (B, 1 + k * mf)
    np.testing.assert_array_equal(nodes[:, 0], src)
    np.testing.assert_array_equal(mask, nodes != SENTINEL)
    for row, s in enumerate(src):
        assert served_hops(nodes, hops, row, k) == plain_bfs(
            adj, int(s), k, mf
        ), (int(s), row)


@pytest.mark.parametrize("chunk", [64, 1024])
def test_hop_over_one_chunk_takes_several_passes(monkeypatch, chunk):
    net, adj = graph(10)
    monkeypatch.setattr(traversal, "HOP_CHUNK", chunk)
    src = sources_of(adj, 5, seed=7)
    before = _counters()
    nodes, _, hops = traversal.khop_host(net, src, 2, max_frontier=256)
    after = _counters()
    d = {key: after[key] - before[key] for key in after}
    assert d["traversal.slots"] >= 4 * chunk  # several passes in a hop
    assert d["traversal.launches"] == d["traversal.hops"]
    for row, s in enumerate(src):
        assert served_hops(nodes, hops, row, 2) == plain_bfs(
            adj, int(s), 2, 256
        )


@pytest.mark.parametrize("hub", [False, True])
@pytest.mark.parametrize("mf", [4, 512])
def test_launches_per_hop_do_not_follow_frontier_or_hubs(hub, mf):
    """One launch per hop whatever the frontier's width or its degrees;
    padded slots at most the real candidates plus one chunk."""
    net, adj = graph(12)
    deg = np.array([a.size for a in adj])
    order = np.argsort(-deg)
    src = order[:3] if hub else order[-3 - np.sum(deg == 0):][:3]
    src = src[deg[src] > 0]
    before = _counters()
    traversal.khop_host(net, src, 2, max_frontier=mf)
    after = _counters()
    d = {key: after[key] - before[key] for key in after}
    assert d["traversal.hops"] >= 1
    assert d["traversal.launches"] == d["traversal.hops"]
    chunk = min(traversal.HOP_CHUNK, 1 << int(
        np.ceil(np.log2(net.layer("g").out.nnz))))
    assert d["traversal.slots"] <= 2 * d["traversal.cand"] \
        + d["traversal.hops"] * chunk
    assert d["traversal.slots"] >= d["traversal.cand"]


def test_khop_served_over_the_wire_matches_plain_bfs():
    net, adj = graph(10)
    src = sources_of(adj, 6, seed=3)
    with api.servenet(net) as fe, GraphServeClient(*fe.address) as c:
        for s in src:
            rec, = c.query({"kind": "khop", "sources": int(s), "k": 2,
                            "max_frontier": 32})
            want = plain_bfs(adj, int(s), 2, 32)
            assert rec["source"] == int(s)
            assert rec["nodes"] == want[0] + want[1]
            assert rec["hops"] == [1] * len(want[0]) + [2] * len(want[1])


def test_source_outside_the_node_range_is_reached_alone():
    """As in the padded loop, where a row read past indptr's end is
    empty: the source slot and nothing else."""
    net, adj = graph(8)
    src = np.array([net.n_nodes + 5, 3, -1])
    nodes, mask, hops = traversal.khop_host(net, src, 2, max_frontier=16)
    padded = [np.asarray(a) for a in traversal._khop_padded(
        net, src, 2, 16, None, None, None, None, None)]
    np.testing.assert_array_equal(nodes, padded[0])
    np.testing.assert_array_equal(mask, padded[1])
    assert mask[0].sum() == 1 and mask[2].sum() == 1
    assert served_hops(nodes, hops, 1, 2) == plain_bfs(adj, 3, 2, 16)


@pytest.mark.parametrize("filtered", [False, True])
def test_unfiltered_hop_reads_no_further_than_its_answer_needs(filtered):
    """Rows are sorted, so an unfiltered hop reads at most mf + (the row's
    visited count) ids of each frontier node's row; a filter can drop any
    of them, so a filtered hop reads whole rows. Both answer exactly."""
    net, adj = graph(12)
    hub = int(np.argmax([a.size for a in adj]))
    nf = np.ones(net.n_nodes, bool) if filtered else None
    before = _counters()
    nodes, _, hops = traversal.khop_host(net, np.array([hub]), 1,
                                         max_frontier=4, node_filter=nf)
    after = _counters()
    read = after["traversal.cand"] - before["traversal.cand"]
    assert read == (adj[hub].size if filtered else 4 + 1)
    assert served_hops(nodes, hops, 0, 1) == plain_bfs(adj, hub, 1, 4)


def mutated(layer, n, src, dst, seed: int):
    """``layer`` (built from src, dst) with an overlay: edges added at its
    hubs and elsewhere, then edges deleted at its hubs and elsewhere ->
    (layer, adjacency of the edges it then holds, by set arithmetic)."""
    rng = np.random.default_rng(seed)
    deg = np.bincount(np.concatenate([src, dst]), minlength=n)
    hubs = np.argsort(-deg)[:4]
    add_u = np.concatenate([np.repeat(hubs, 16), rng.integers(0, n, 64)])
    add_v = rng.integers(0, n, add_u.size)
    keep = add_u != add_v
    add_u, add_v = add_u[keep], add_v[keep]
    real = np.flatnonzero(src != dst)
    at_hub = real[np.isin(src[real], hubs)]
    pick = np.concatenate([rng.choice(at_hub, 32, replace=False),
                           rng.choice(real, 32, replace=False)])
    del_u, del_v = src[pick], dst[pick]
    layer = add_edges(layer, add_u, add_v, compact_ratio=None)
    layer = delete_edges(layer, del_u, del_v, compact_ratio=None)
    assert has_overlay(layer)

    def pairs(u, v):
        return {(min(a, b), max(a, b)) for a, b in zip(u.tolist(), v.tolist())
                if a != b}

    edges = (pairs(src, dst) | pairs(add_u, add_v)) - pairs(del_u, del_v)
    e = np.array(sorted(edges), np.int64).reshape(-1, 2)
    return layer, adjacency(n, e[:, 0], e[:, 1])


def union(adj_a, adj_b) -> list[np.ndarray]:
    return [np.union1d(a, b) for a, b in zip(adj_a, adj_b)]


def _check_one_pass(net, adj, k, mf, nf, B, seed, layer_names=None):
    """The one-pass hop (one launch per hop) against the plain BFS and,
    for an overlay, against the same network compacted."""
    src = sources_of(adj, B, seed)
    before = _counters()
    nodes, mask, hops = traversal.khop_host(
        net, src, k, max_frontier=mf, node_filter=nf,
        layer_names=layer_names,
    )
    after = _counters()
    d = {key: after[key] - before[key] for key in after}
    assert d["traversal.hops"] >= 1
    assert d["traversal.launches"] == d["traversal.hops"]
    np.testing.assert_array_equal(mask, nodes != SENTINEL)
    compacted = traversal.khop_host(
        net.compacted(), src, k, max_frontier=mf, node_filter=nf,
        layer_names=layer_names,
    )
    np.testing.assert_array_equal(nodes, compacted[0])
    for row, s in enumerate(src):
        assert served_hops(nodes, hops, row, k) == plain_bfs(
            adj, int(s), k, mf, nf
        ), (int(s), row)


def _filter(n: int, seed: int):
    return np.random.default_rng(seed).random(n) < 0.7


@pytest.mark.parametrize("filtered", [False, True])
@pytest.mark.parametrize("mf", [4, 64, 4096])
def test_overlay_rows_match_compacted_and_plain_bfs(filtered, mf):
    """Dirty rows are read from the overlay's delta, clean rows from the
    base CSR, in one program; hubs (dirty) and an isolated root included."""
    n, src, dst = kronecker(10, seed=31)
    layer, adj = mutated(one_mode_from_edges(n, src, dst), n, src, dst, 5)
    net = create_network(n).with_layer("g", layer)
    nf = _filter(n, 1) if filtered else None
    _check_one_pass(net, adj, 2, mf, nf, B=9, seed=mf + filtered)


@pytest.mark.parametrize("overlay", [False, True])
@pytest.mark.parametrize("filtered", [False, True])
@pytest.mark.parametrize("layer_names", [None, ["a", "b"]])
def test_two_one_mode_layers_hop_over_their_union(overlay, filtered,
                                                  layer_names):
    n, src, dst = kronecker(10, seed=47)
    half = np.arange(src.size) % 2 == 0
    a = one_mode_from_edges(n, src[half], dst[half])
    b = one_mode_from_edges(n, src[~half], dst[~half])
    adj_a = adjacency(n, src[half], dst[half])
    if overlay:
        b, adj_b = mutated(b, n, src[~half], dst[~half], 9)
    else:
        adj_b = adjacency(n, src[~half], dst[~half])
    net = create_network(n).with_layer("a", a).with_layer("b", b)
    nf = _filter(n, 2) if filtered else None
    for k, mf in ((1, 8), (2, 32), (3, 512)):
        _check_one_pass(net, union(adj_a, adj_b), k, mf, nf, B=6,
                        seed=k, layer_names=layer_names)
