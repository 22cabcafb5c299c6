"""Degree-bucketed dispatch parity: bucketed Pallas/jnp query paths must be
bit-identical to the global-max padded reference paths AND agree with the
materialized ``project_two_mode`` oracle — including hub nodes, empty rows,
size-1 hyperedges, and all-sentinel batches. The host forms the serve
executors call (host ids in, host arrays out) must equal both the padded
reference and the device-ids path."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import (
    add_edges,
    create_network,
    delete_edges,
    one_mode_from_edges,
    project_two_mode,
    two_mode_from_memberships,
)
from repro.core import dispatch, obs
from repro.core.csr import SENTINEL
from repro.core.layers import has_overlay
from repro.kernels import ops, ref


def _skewed_layer(seed=0, n_nodes=300, n_hyper=40):
    """Hub node 0 (~100x median memberships), one giant hyperedge, several
    size-1 hyperedges, and isolated nodes (ids >= n_nodes - 20)."""
    rng = np.random.default_rng(seed)
    nodes = rng.integers(0, n_nodes - 20, 600)
    hyper = rng.integers(0, n_hyper, 600)
    giant = rng.choice(n_nodes - 20, 120, replace=False)  # hyperedge 0
    singles = rng.integers(0, n_nodes - 20, 5)  # size-1 hyperedges
    hub_h = rng.choice(n_hyper, 35, replace=False)
    nodes = np.concatenate([nodes, giant, singles, np.zeros(35, int)])
    hyper = np.concatenate(
        [hyper, np.zeros(120, int), np.arange(n_hyper, n_hyper + 5), hub_h]
    )
    return two_mode_from_memberships(n_nodes, n_hyper + 5, nodes, hyper)


@pytest.fixture(scope="module")
def skewed():
    return _skewed_layer()


# ---------------------------------------------------------------------------
# plan_buckets
# ---------------------------------------------------------------------------


def test_plan_buckets_covers_batch_exactly():
    deg = np.array([0, 1, 8, 9, 32, 33, 128, 500, 2])
    buckets = dispatch.plan_buckets(deg, 500)
    seen = np.concatenate([idx for idx, _ in buckets])
    np.testing.assert_array_equal(np.sort(seen), np.arange(deg.size))
    for idx, w in buckets:
        assert (deg[idx] <= w).all(), f"degree exceeds bucket width {w}"


def test_plan_buckets_small_max_width():
    # max_width below every threshold -> single bucket at the max
    buckets = dispatch.plan_buckets(np.array([0, 1, 2]), 3)
    assert len(buckets) == 1 and buckets[0][1] == 3


# ---------------------------------------------------------------------------
# edge_value / check_edge
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("use_pallas", [False, True])
def test_edge_value_bucketed_vs_padded(skewed, use_pallas):
    rng = np.random.default_rng(1)
    B = 257  # not a multiple of any block size
    u = jnp.asarray(rng.integers(0, skewed.n_nodes, B), jnp.int32)
    v = jnp.asarray(rng.integers(0, skewed.n_nodes, B), jnp.int32)
    got = dispatch.bucketed_edge_value(skewed, u, v, use_pallas=use_pallas)
    want = skewed.edge_value_padded(u, v)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    ce = dispatch.bucketed_check_edge(skewed, u, v, use_pallas=use_pallas)
    np.testing.assert_array_equal(np.asarray(ce), np.asarray(want) > 0)


def test_edge_value_vs_projection_oracle(skewed):
    proj = project_two_mode(skewed)
    rng = np.random.default_rng(2)
    u = rng.integers(0, skewed.n_nodes, 400)
    v = rng.integers(0, skewed.n_nodes, 400)
    off = u != v  # projection has no self-loops
    got = np.asarray(skewed.edge_value(jnp.asarray(u), jnp.asarray(v)))
    want = np.asarray(proj.edge_value(jnp.asarray(u), jnp.asarray(v)))
    np.testing.assert_allclose(got[off], want[off])


def test_edge_value_hub_and_empty_rows(skewed):
    # hub (node 0), isolated nodes (no memberships), and hub-vs-isolated
    iso = skewed.n_nodes - 1
    u = jnp.asarray([0, iso, 0, iso], jnp.int32)
    v = jnp.asarray([1, 5, iso, iso], jnp.int32)
    got = dispatch.bucketed_edge_value(skewed, u, v)
    want = skewed.edge_value_padded(u, v)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert float(got[1]) == 0.0 and float(got[3]) == 0.0


def test_edge_value_all_sentinel_batch(skewed):
    # every query hits an isolated node -> every bucket row is all-SENTINEL
    iso = jnp.full((9,), skewed.n_nodes - 1, jnp.int32)
    got = dispatch.bucketed_edge_value(skewed, iso, iso)
    np.testing.assert_array_equal(np.asarray(got), np.zeros(9, np.float32))


def test_edge_value_traced_fallback_matches(skewed):
    rng = np.random.default_rng(3)
    u = jnp.asarray(rng.integers(0, skewed.n_nodes, 64), jnp.int32)
    v = jnp.asarray(rng.integers(0, skewed.n_nodes, 64), jnp.int32)
    jit_val = jax.jit(lambda a, b: skewed.edge_value(a, b))(u, v)
    np.testing.assert_array_equal(
        np.asarray(skewed.edge_value(u, v)), np.asarray(jit_val)
    )


def test_empty_batch(skewed):
    got = dispatch.bucketed_edge_value(
        skewed, jnp.zeros((0,), jnp.int32), jnp.zeros((0,), jnp.int32)
    )
    assert got.shape == (0,)
    va, ma = dispatch.bucketed_node_alters(
        skewed, jnp.zeros((0,), jnp.int32), 8
    )
    assert va.shape == (0, 8) and ma.shape == (0, 8)


# ---------------------------------------------------------------------------
# node_alters
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("use_pallas", [False, True])
def test_node_alters_bucketed_vs_padded(skewed, use_pallas):
    rng = np.random.default_rng(4)
    B, max_alters = 100, 256
    u = jnp.asarray(rng.integers(0, skewed.n_nodes, B), jnp.int32)
    gv, gm = dispatch.bucketed_node_alters(
        skewed, u, max_alters, use_pallas=use_pallas
    )
    wv, wm = skewed.node_alters_padded(u, max_alters)
    np.testing.assert_array_equal(np.asarray(gv), np.asarray(wv))
    np.testing.assert_array_equal(np.asarray(gm), np.asarray(wm))


def test_node_alters_vs_projection_oracle(skewed):
    proj = project_two_mode(skewed)
    q = jnp.arange(0, skewed.n_nodes, 7)
    max_alters = skewed.n_nodes
    pv, pm = skewed.node_alters(q, max_alters)  # dispatched (concrete)
    mv, mm = proj.node_alters(q, max_alters)
    for i in range(q.shape[0]):
        got = set(np.asarray(pv[i])[np.asarray(pm[i])].tolist())
        want = set(np.asarray(mv[i])[np.asarray(mm[i])].tolist())
        assert got == want, f"alters mismatch for node {int(q[i])}"


def test_node_alters_hub_empty_and_singleton(skewed):
    iso = skewed.n_nodes - 1
    # a member of a size-1 hyperedge only has alters from its other edges;
    # find one: hyperedge ids n_hyper-5.. are size-1
    u = jnp.asarray([0, iso], jnp.int32)  # hub + isolated
    gv, gm = dispatch.bucketed_node_alters(skewed, u, 300)
    wv, wm = skewed.node_alters_padded(u, 300)
    np.testing.assert_array_equal(np.asarray(gv), np.asarray(wv))
    assert not np.asarray(gm[1]).any()  # isolated node: no alters


def test_node_alters_all_sentinel_batch(skewed):
    iso = jnp.full((17,), skewed.n_nodes - 1, jnp.int32)
    gv, gm = dispatch.bucketed_node_alters(skewed, iso, 32)
    assert not np.asarray(gm).any()
    assert (np.asarray(gv) == SENTINEL).all()


def test_size_one_hyperedges_only():
    # layer where EVERY hyperedge has one member: projection is empty
    layer = two_mode_from_memberships(
        10, 6, np.arange(6), np.arange(6)
    )
    u = jnp.arange(10)
    ev = dispatch.bucketed_edge_value(layer, u, u[::-1])
    np.testing.assert_array_equal(np.asarray(ev), np.zeros(10))
    gv, gm = dispatch.bucketed_node_alters(layer, u, 4)
    assert not np.asarray(gm).any()


# ---------------------------------------------------------------------------
# segmented-union kernel
# ---------------------------------------------------------------------------


def test_segmented_union_kernel_vs_ref():
    rng = np.random.default_rng(5)
    for _ in range(10):
        B = int(rng.integers(1, 12))
        K = int(rng.integers(1, 260))
        flat = rng.integers(0, 40, (B, K)).astype(np.int32)
        flat[rng.random((B, K)) < 0.3] = SENTINEL
        max_out = int(rng.integers(1, K + 4))
        fj = jnp.asarray(flat)
        gv, gm = ops.segmented_union(fj, max_out, use_pallas=True)
        wv, wm = ref.segmented_union_ref(fj, max_out)
        np.testing.assert_array_equal(np.asarray(gv), np.asarray(wv))
        np.testing.assert_array_equal(np.asarray(gm), np.asarray(wm))


def test_pseudo_node_alters_widths(skewed):
    """Narrow per-bucket widths must not change results when they cover
    the queried rows (the dispatcher's core invariant)."""
    u = jnp.asarray([3, 4, 5], jnp.int32)
    deg = np.asarray(skewed.memb.degrees())[np.asarray(u)]
    wn = int(dispatch.node_max_hyperedge_size(skewed)[np.asarray(u)].max())
    gv, gm = ops.pseudo_node_alters(
        skewed, u, 128, width_m=int(deg.max()), width_n=wn, use_pallas=False
    )
    wv, wm = skewed.node_alters_padded(u, 128)
    np.testing.assert_array_equal(np.asarray(gv), np.asarray(wv))
    np.testing.assert_array_equal(np.asarray(gm), np.asarray(wm))


def test_node_max_hyperedge_size(skewed):
    per_node = dispatch.node_max_hyperedge_size(skewed)
    indptr = np.asarray(skewed.memb.indptr)
    indices = np.asarray(skewed.memb.indices)
    sizes = np.diff(np.asarray(skewed.members.indptr))
    for u in [0, 1, 7, skewed.n_nodes - 1]:
        hes = indices[indptr[u] : indptr[u + 1]]
        want = int(sizes[hes].max()) if hes.size else 0
        assert per_node[u] == want


def test_node_width_cache_evicts_oldest_not_everything():
    """Regression: overflowing the per-layer width cache used to clear it
    wholesale, so >64-layer workloads (TemporalNetwork.window over many
    years) recomputed every width table per query. Overflow must evict
    only the oldest-inserted entry and keep recent layers warm."""
    cap = dispatch._NODE_WIDTH_CACHE_MAX

    def tiny_layer(seed):
        rng = np.random.default_rng(seed)
        return two_mode_from_memberships(
            40, 6, rng.integers(0, 40, 60), rng.integers(0, 6, 60)
        )

    layers = [tiny_layer(s) for s in range(cap + 8)]
    dispatch._NODE_WIDTH_CACHE.clear()
    tables = [dispatch.node_max_hyperedge_size(l) for l in layers]
    assert len(dispatch._NODE_WIDTH_CACHE) == cap
    # the 8 oldest were evicted one at a time; everything newer stays
    for i, layer in enumerate(layers):
        key = (id(layer.memb.indices), id(None), id(None))
        assert (key in dispatch._NODE_WIDTH_CACHE) == (i >= 8)
    # warm entries return the cached array by identity (no recompute)
    for i in range(8, len(layers)):
        again = dispatch.node_max_hyperedge_size(layers[i])
        assert again is tables[i]
    # re-querying an evicted layer recomputes correctly and re-inserts
    re0 = dispatch.node_max_hyperedge_size(layers[0])
    np.testing.assert_array_equal(re0, tables[0])
    key0 = (id(layers[0].memb.indices), id(None), id(None))
    assert key0 in dispatch._NODE_WIDTH_CACHE


def test_node_width_cache_hit_promotes_hot_layer():
    """LRU, not plain FIFO: a layer that keeps getting hit must survive
    a full cap's worth of churn from other layers."""
    cap = dispatch._NODE_WIDTH_CACHE_MAX

    def tiny_layer(seed):
        rng = np.random.default_rng(seed)
        return two_mode_from_memberships(
            40, 6, rng.integers(0, 40, 60), rng.integers(0, 6, 60)
        )

    dispatch._NODE_WIDTH_CACHE.clear()
    hot = tiny_layer(1000)
    hot_table = dispatch.node_max_hyperedge_size(hot)
    churn = [tiny_layer(s) for s in range(cap - 1)]
    for layer in churn:  # interleave churn with hits on the hot layer
        dispatch.node_max_hyperedge_size(layer)
        assert dispatch.node_max_hyperedge_size(hot) is hot_table
    # cap-1 fresh inserts plus the hot layer fill the cache exactly; the
    # next insert evicts the LRU churn entry, never the just-hit layer
    dispatch.node_max_hyperedge_size(tiny_layer(2000))
    assert dispatch.node_max_hyperedge_size(hot) is hot_table
    dispatch._NODE_WIDTH_CACHE.clear()


# ---------------------------------------------------------------------------
# host ids -> host results (the serve executors' path)
# ---------------------------------------------------------------------------


def _parity_net(dirty: bool):
    """A skewed two-mode layer "tm" (hub node 0) and a one-mode layer "om"
    (hub node 0 with 150 neighbours) over 300 nodes; ``dirty`` routes
    inserts and deletes into live delta overlays, hub rows included."""
    n = 300
    tm = _skewed_layer()
    rng = np.random.default_rng(11)
    src = np.concatenate([rng.integers(0, n - 20, 700), np.zeros(150, int)])
    dst = np.concatenate([rng.integers(0, n - 20, 700),
                          np.arange(1, 151)])
    om = one_mode_from_edges(n, src, dst)
    if dirty:
        tm = add_edges(tm, [0, 0, 5, 7, 260], [41, 44, 0, 44, 44],
                       compact_ratio=None)
        tm = delete_edges(tm, [1, 2], [int(tm.memb.indices[0]), 0],
                          compact_ratio=None)
        om = add_edges(om, [0, 3, 4], [160, 9, 270], compact_ratio=None)
        om = delete_edges(om, [0], [1], compact_ratio=None)
        assert has_overlay(tm) and has_overlay(om)
    return create_network(n).with_layer("tm", tm).with_layer("om", om)


@pytest.fixture(scope="module", params=[False, True], ids=["clean", "dirty"])
def parity_net(request):
    return _parity_net(request.param)


_B, _MAX_ALTERS = 37, 64  # a batch that is no power of two; hub rows cap


def _query(net, kind, layer, u, v, nf, *, host):
    """One query of ``kind`` on ``layer`` -> a tuple of its outputs: the
    host form (host results) or the public form (jax results)."""
    if kind == "getedge":
        f = net.edge_value_host if host else net.edge_value
        return (f(layer, u, v, node_filter=nf),)
    if kind == "alters":
        f = net.node_alters_host if host else net.node_alters
        return tuple(f(u, _MAX_ALTERS, [layer], node_filter=nf))
    f = net.degree_host if host else net.degree
    return (f(u, [layer], node_filter=nf),)


def _counter(name):
    return obs.snapshot()["counters"].get(name, 0)


@pytest.mark.parametrize("filtered", [False, True], ids=["all", "filter"])
@pytest.mark.parametrize("layer", ["tm", "om"])
@pytest.mark.parametrize("kind", ["getedge", "alters", "degree"])
def test_host_ids_path_matches_padded_and_device_ids(
    parity_net, kind, layer, filtered
):
    net = parity_net
    rng = np.random.default_rng(12)
    u = rng.integers(0, net.n_nodes, _B).astype(np.int32)
    v = rng.integers(0, net.n_nodes, _B).astype(np.int32)
    u[:3] = [0, 0, net.n_nodes - 1]  # hub, hub, isolated
    v[:3] = [1, net.n_nodes - 1, 0]
    nf = rng.random(net.n_nodes) < 0.5 if filtered else None

    d0 = _counter("dispatch.device_ids")
    got = _query(net, kind, layer, u, v, nf, host=True)
    assert _counter("dispatch.device_ids") == d0
    dev = _query(net, kind, layer, jnp.asarray(u), jnp.asarray(v), nf,
                 host=False)
    if kind == "degree" and not filtered:  # the whole degree vector, read
        padded = (jnp.take(net.layer(layer).degrees(), u, mode="clip"),)
    else:  # a traced network takes the global-max padded paths
        padded = jax.jit(
            lambda n, a, b: _query(n, kind, layer, a, b, nf, host=False)
        )(net, jnp.asarray(u), jnp.asarray(v))
    for want in (dev, padded):
        assert len(got) == len(want)
        for g, w in zip(got, want):
            assert isinstance(g, np.ndarray)
            assert g.dtype == np.asarray(w).dtype
            np.testing.assert_array_equal(g, np.asarray(w))


def test_single_layer_alters_without_the_merge_equals_the_merge(parity_net):
    net = parity_net
    layer = net.layer("tm")
    u = np.arange(0, net.n_nodes, 9, dtype=np.int32)
    h0 = _counter("dispatch.host_ids")
    vals, mask = net.node_alters_host(u, _MAX_ALTERS, ["tm"])
    assert _counter("dispatch.host_ids") == h0 + 1
    merged = dispatch.union_rows(
        *layer.node_alters(jnp.asarray(u), _MAX_ALTERS), _MAX_ALTERS
    )
    public = net.node_alters(u, _MAX_ALTERS, ["tm"])
    for want in (merged, public):
        np.testing.assert_array_equal(vals, np.asarray(want[0]))
        np.testing.assert_array_equal(mask, np.asarray(want[1]))


def test_degree_sum_reads_every_layer_in_one_program(parity_net):
    net = parity_net
    u = np.array([0, 5, net.n_nodes - 1, -3, net.n_nodes + 7], np.int32)
    want = sum(
        np.take(np.asarray(l.degrees()), u, mode="clip") for l in net.layers
    )
    got = dispatch.degree_sum(net.layers, u)
    assert isinstance(got, jax.Array) and got.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got), want)
