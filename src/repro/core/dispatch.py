"""Degree-bucketed batched query dispatch for pseudo-projection hot paths.

Problem (NetworKit/SNAP's lesson, applied to the query engine): batched
two-mode queries pad every row to the *layer-global* maximum —
``max_memberships`` for ``edge_value`` and ``max_memberships ×
max_hyperedge_size`` for ``node_alters``. Real-world affiliation graphs
are heavily skewed, so a single hub node or giant hyperedge inflates every
query in every batch by orders of magnitude.

Mechanism: when a query batch is *concrete* (host-visible ids — the
serving path; anything inside a caller's ``jit`` trace falls back to the
global-max padded path), the dispatcher

  1. reads row degrees straight from the CSR ``indptr`` on the host,
  2. splits the batch into power-of-two padding buckets
     (``DEFAULT_BUCKET_WIDTHS`` then the layer max),
  3. pads each bucket's row count to a power of two (so each
     (rows, width) pair compiles exactly once),
  4. runs each bucket through a jit'd fixed-width kernel — the Pallas
     intersect / segmented-union kernels for wide buckets on TPU, the jnp
     ``sorted_isin`` / ``padded_unique`` paths for tiny buckets and CPU —
  5. scatters per-bucket results back into the original batch order.

For ``node_alters`` the second-hop width is also bucket-local: the max
hyperedge size *among the bucket's actual hyperedges* (cached per layer),
not the global ``max_hyperedge_size`` — this is what neutralizes giant
hyperedges for the 99% of queries that never touch them.

Bucketed results are bit-identical to the padded reference paths: every
row's data fits its bucket width, and both dedup paths emit the same
sorted-unique, smallest-first, ``max_alters``-capped rows.
"""

from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp

from .csr import CSR, SENTINEL, on_tpu as _on_tpu, sorted_isin
from .overlay import (
    eff_host_degree_table,
    eff_host_degrees,
    eff_row_gather,
    ov_buffers,
)

__all__ = [
    "DEFAULT_BUCKET_WIDTHS",
    "can_dispatch",
    "plan_buckets",
    "bucketed_edge_value",
    "bucketed_check_edge",
    "bucketed_node_alters",
    "bucketed_filtered_degree",
    "alters_bound",
    "pow2_ceil",
    "pow2_pad",
    "union_rows",
    "node_max_hyperedge_size",
]

# Bucket pad widths tried in order; the layer-global max closes the list.
DEFAULT_BUCKET_WIDTHS = (8, 32, 128)
# Below this membership width the Pallas intersect kernel would pad back up
# to a full 128-lane tile — tiny buckets stay on the jnp binary-search path.
PALLAS_MIN_WIDTH = 128
# All-pairs dedup is O(K^2); beyond this flat width the sort path wins.
UNION_PALLAS_MAX_FLAT = 2048


def can_dispatch(*arrays) -> bool:
    """True when every array is concrete (not inside a jit trace).

    Callers must pass the layer's own buffers (indptr/indices) along with
    the query ids: a layer flowing through jit as a pytree argument is
    traced even when the queries are host arrays.
    """
    return not any(isinstance(a, jax.core.Tracer) for a in arrays)


# ---------------------------------------------------------------------------
# Host-side planning
# ---------------------------------------------------------------------------


def _host_degrees(csr: CSR, rows: np.ndarray, ov=None) -> np.ndarray:
    """Effective row lengths read from indptr (mirrors the device clip).

    ``ov`` is the CSR's delta overlay (core/overlay.py): dirty rows take
    the delta's length — the post-mutation truth the bucket plan must pad
    for.
    """
    return eff_host_degrees(csr, ov, rows)


def _width_ladder(max_width: int, widths) -> list[int]:
    max_width = max(int(max_width), 1)
    return [w for w in widths if w < max_width] + [max_width]


def plan_buckets(
    deg: np.ndarray,
    max_width: int,
    widths=DEFAULT_BUCKET_WIDTHS,
) -> list[tuple[np.ndarray, int]]:
    """Assign each query the smallest bucket width covering its degree.

    Returns [(original_positions, pad_width)] for each non-empty bucket,
    ascending by width. Degree-0 rows land in the smallest bucket.
    """
    ladder = _width_ladder(max_width, widths)
    assign = np.searchsorted(np.asarray(ladder), deg, side="left")
    out = []
    for bi, w in enumerate(ladder):
        idx = np.nonzero(assign == bi)[0]
        if idx.size:
            out.append((idx, int(w)))
    return out


def pow2_ceil(n: int, floor: int = 8) -> int:
    """Smallest power of two >= ``n`` (and >= ``floor``): padding a
    data-dependent size to it bounds how many shapes compile."""
    p = floor
    while p < n:
        p <<= 1
    return p


def pow2_pad(ids) -> np.ndarray:
    """``ids`` padded to a power-of-two length with repeats of its first
    element: a batch then compiles once per size class, and the pad rows
    answer a real query whose copies the caller drops."""
    ids = np.asarray(ids)
    n = pow2_ceil(ids.size, floor=1)
    if n == ids.size:
        return ids
    return np.concatenate([ids, np.full(n - ids.size, ids[0], ids.dtype)])


def _scatter_back(out: jnp.ndarray, idx: np.ndarray, res: jnp.ndarray):
    """``out[idx] = res[:idx.size]`` as one program per (out, res) shape.

    ``res`` has the bucket's power-of-two row count and ``out`` one spare
    last row, which takes every pad row of ``res`` (callers slice it off):
    the scatter does not compile anew for every bucket size, and no index
    falls outside ``out``.
    """
    pos = np.full(res.shape[0], out.shape[0] - 1, np.int32)
    pos[: idx.size] = idx
    return out.at[jnp.asarray(pos)].set(res)


def _pad_rows(ids: np.ndarray, n: int) -> jnp.ndarray:
    out = np.zeros((n,), dtype=np.int32)
    out[: ids.size] = ids
    return jnp.asarray(out)


# Per-layer cache: node -> max hyperedge size over its memberships.
# Keyed by id() of the membership indices buffer; the buffer itself is
# pinned in the value so a recycled id can be detected by identity check.
# Bounded LRU (dicts preserve insertion order; a hit re-inserts as
# newest): overflow evicts the least-recently-used entry, so a working
# set of up to _NODE_WIDTH_CACHE_MAX layers stays warm under churn from
# other layers (e.g. TemporalNetwork.window sliding across many years)
# instead of being wiped wholesale as before. A strict cycle over more
# than the cap still misses every time — as under any eviction policy —
# but each miss costs one layer's width table, not all of them.
_NODE_WIDTH_CACHE: dict[tuple, tuple[tuple, np.ndarray]] = {}
_NODE_WIDTH_CACHE_MAX = 64


def node_max_hyperedge_size(layer) -> np.ndarray:
    """int32[n_nodes]: largest hyperedge each node belongs to (host, cached).

    This bounds the second-hop gather width for ``node_alters`` per query
    node, replacing the layer-global ``max_hyperedge_size``. int32 is
    exact: a hyperedge's size is bounded by nnz, which the builders cap
    below 2**31 (DtypePolicy widens only indptr, never sizes). At 10M+
    nodes the narrower table halves this cache's footprint vs int64.
    """
    memb_ov = getattr(layer, "memb_ov", None)
    members_ov = getattr(layer, "members_ov", None)
    pins = (
        layer.memb.indices,
        None if memb_ov is None else memb_ov.delta.indices,
        None if members_ov is None else members_ov.delta.indices,
    )
    key = tuple(id(p) for p in pins)
    hit = _NODE_WIDTH_CACHE.get(key)
    if hit is not None and all(a is b for a, b in zip(hit[0], pins)):
        # LRU: a hit re-promotes to newest (pop default guards a
        # concurrent hit on the same key having popped it first)
        _NODE_WIDTH_CACHE.pop(key, None)
        _NODE_WIDTH_CACHE[key] = hit
        return hit[1]
    indptr = np.asarray(layer.memb.indptr)
    indices = np.asarray(layer.memb.indices)
    # effective hyperedge sizes: a grown/shrunk hyperedge changes the
    # width bound of every node that contains it, dirty row or not
    he_sizes = eff_host_degree_table(layer.members, members_ov).astype(
        np.int32
    )
    out = np.zeros(layer.memb.n_rows, dtype=np.int32)
    if indices.size:
        per_memb = he_sizes[indices]
        lengths = np.diff(indptr)
        nonempty = lengths > 0
        starts = indptr[:-1][nonempty]
        out[nonempty] = np.maximum.reduceat(per_memb, starts)
    if memb_ov is not None:
        # dirty membership rows re-derive from the delta's row content
        dirty = np.asarray(memb_ov.dirty)
        dind = np.asarray(memb_ov.delta.indptr)
        dids = np.asarray(memb_ov.delta.indices)
        out[dirty] = 0
        if dids.size:
            dlen = np.diff(dind)
            dne = (dlen > 0) & dirty
            dstarts = dind[:-1][dne]
            out[dne] = np.maximum.reduceat(he_sizes[dids], dstarts)
    _NODE_WIDTH_CACHE.pop(key, None)  # recycled id: re-insert as newest
    while len(_NODE_WIDTH_CACHE) >= _NODE_WIDTH_CACHE_MAX:
        del _NODE_WIDTH_CACHE[next(iter(_NODE_WIDTH_CACHE))]
    _NODE_WIDTH_CACHE[key] = (pins, out)
    return out


# ---------------------------------------------------------------------------
# Fixed-width bucket kernels (jit-cached per (layer treedef, widths))
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit, static_argnames=("width", "use_pallas", "interpret")
)
def _edge_value_bucket(layer, u, v, *, width, use_pallas, interpret):
    a, am = layer.memberships(u, width)
    b, bm = layer.memberships(v, width)
    if use_pallas:
        from repro.kernels import ops as kops

        a = jnp.where(am, a, SENTINEL)
        b = jnp.where(bm, b, SENTINEL)
        return kops.intersect_count(a, b, interpret=interpret).astype(
            jnp.float32
        )
    hits = sorted_isin(a, am, b, bm)
    return jnp.sum(hits, axis=-1).astype(jnp.float32)


@functools.partial(
    jax.jit,
    static_argnames=(
        "width_m", "width_n", "max_alters", "use_pallas", "interpret"
    ),
)
def _node_alters_bucket(
    layer, u, node_filter=None, *,
    width_m, width_n, max_alters, use_pallas, interpret,
):
    from repro.kernels import ops as kops

    return kops.pseudo_node_alters(
        layer, u, max_alters,
        width_m=width_m, width_n=width_n, node_filter=node_filter,
        use_pallas=use_pallas, interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("width",))
def _one_mode_filtered_degree_bucket(layer, u, node_filter, *, width):
    vals, mask = eff_row_gather(layer.out, layer.out_ov, u, width)
    hit = mask & jnp.take(node_filter, vals, mode="clip")
    return jnp.sum(hit, axis=-1).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Dispatchers
# ---------------------------------------------------------------------------


def bucketed_edge_value(
    layer,
    u: jnp.ndarray,
    v: jnp.ndarray,
    *,
    node_filter=None,
    widths=DEFAULT_BUCKET_WIDTHS,
    use_pallas: bool | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Degree-bucketed GetEdgeValue over a concrete query batch -> f32[...].

    Buckets by max(deg(u), deg(v)) so both membership rows fit the bucket
    width. ``use_pallas=None`` auto-selects: the Pallas intersect kernel on
    TPU for buckets >= PALLAS_MIN_WIDTH, ``sorted_isin`` otherwise.

    ``node_filter`` (bool[n_nodes]) restricts the query to selected target
    nodes: pairs whose ``v`` fails the filter return 0 — and are dropped
    from the plan *before* any bucket runs, so a mostly-filtered batch does
    a fraction of the unfiltered work.
    """
    shape = jnp.shape(u)
    un = np.asarray(u, dtype=np.int64).reshape(-1)
    vn = np.asarray(v, dtype=np.int64).reshape(-1)
    B = un.size
    if B == 0:
        return jnp.zeros(shape, jnp.float32)
    if node_filter is not None:
        nf = np.asarray(node_filter, dtype=bool)
        keep = nf[np.clip(vn, 0, nf.size - 1)]
        out = jnp.zeros((B,), jnp.float32)
        if keep.any():
            sub = bucketed_edge_value(
                layer, un[keep], vn[keep],
                widths=widths, use_pallas=use_pallas, interpret=interpret,
            )
            out = out.at[jnp.asarray(np.nonzero(keep)[0])].set(sub)
        return out.reshape(shape)
    memb_ov = getattr(layer, "memb_ov", None)
    deg = np.maximum(
        _host_degrees(layer.memb, un, memb_ov),
        _host_degrees(layer.memb, vn, memb_ov),
    )
    out = jnp.zeros((B + 1,), jnp.float32)  # + the spare row of _scatter_back
    for idx, w in plan_buckets(deg, layer.max_memberships, widths):
        n = pow2_ceil(idx.size)
        pallas_here = (
            use_pallas
            if use_pallas is not None
            else (_on_tpu() and w >= PALLAS_MIN_WIDTH)
        )
        res = _edge_value_bucket(
            layer, _pad_rows(un[idx], n), _pad_rows(vn[idx], n),
            width=w, use_pallas=pallas_here, interpret=interpret,
        )
        out = _scatter_back(out, idx, res)
    return out[:B].reshape(shape)


def bucketed_check_edge(layer, u, v, **kw) -> jnp.ndarray:
    return bucketed_edge_value(layer, u, v, **kw) > 0


def bucketed_node_alters(
    layer,
    u: jnp.ndarray,
    max_alters: int,
    *,
    node_filter=None,
    widths=DEFAULT_BUCKET_WIDTHS,
    use_pallas: bool | None = None,
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Degree-bucketed GetNodeAlters -> (int32[..., max_alters], mask).

    First-hop width = membership-degree bucket; second-hop width = the max
    hyperedge size among the bucket's nodes, rounded up the same width
    ladder (compile-count bound). Output rows are sorted-unique and capped
    at ``max_alters`` — bit-identical to the padded reference path.

    ``node_filter`` (bool[n_nodes]) masks alters by attribute predicate
    *inside each bucket*, before the segmented-union dedup — a filtered
    query never widens beyond its bucket's pad width, and the cap applies
    to the filtered set (the post-filter oracle: take the unfiltered
    alters at full width, drop failing ids, then cap at ``max_alters``).
    """
    shape = jnp.shape(u)
    un = np.asarray(u, dtype=np.int64).reshape(-1)
    B = un.size
    if B == 0:
        return (
            jnp.full(shape + (max_alters,), SENTINEL, jnp.int32),
            jnp.zeros(shape + (max_alters,), bool),
        )
    nf = None if node_filter is None else jnp.asarray(
        np.asarray(node_filter, dtype=bool)
    )
    deg = _host_degrees(layer.memb, un, getattr(layer, "memb_ov", None))
    per_node_wn = node_max_hyperedge_size(layer)
    vals = jnp.full((B + 1, max_alters), SENTINEL, jnp.int32)
    for idx, wm in plan_buckets(deg, layer.max_memberships, widths):
        needed = int(per_node_wn[np.clip(un[idx], 0, per_node_wn.size - 1)].max())
        wn = next(
            w
            for w in _width_ladder(layer.max_hyperedge_size, widths)
            if w >= needed
        )
        n = pow2_ceil(idx.size)
        pallas_here = (
            use_pallas
            if use_pallas is not None
            else (_on_tpu() and wm * wn <= UNION_PALLAS_MAX_FLAT)
        )
        va, _ = _node_alters_bucket(
            layer, _pad_rows(un[idx], n), nf,
            width_m=wm, width_n=wn, max_alters=max_alters,
            use_pallas=pallas_here, interpret=interpret,
        )
        vals = _scatter_back(vals, idx, va)
    vals = vals[:B].reshape(shape + (max_alters,))
    return vals, vals != SENTINEL


def bucketed_filtered_degree(
    layer,
    u: jnp.ndarray,
    node_filter,
    *,
    widths=DEFAULT_BUCKET_WIDTHS,
    use_pallas: bool | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Degree-bucketed filtered-alter count -> int32[...].

    One-mode: neighbors passing the filter (gather at the bucket width +
    mask-sum). Two-mode: *distinct* co-members passing the filter — each
    bucket runs the filtered alters kernel at its exact flat width
    (wm × wn) so the count is uncapped and exact.
    """
    shape = jnp.shape(u)
    un = np.asarray(u, dtype=np.int64).reshape(-1)
    B = un.size
    if B == 0:
        return jnp.zeros(shape, jnp.int32)
    nf = jnp.asarray(np.asarray(node_filter, dtype=bool))
    out = jnp.zeros((B + 1,), jnp.int32)
    memb = getattr(layer, "memb", None)
    if memb is None:  # one-mode
        deg = _host_degrees(layer.out, un, layer.out_ov)
        for idx, w in plan_buckets(deg, max(int(deg.max()), 1), widths):
            n = pow2_ceil(idx.size)
            res = _one_mode_filtered_degree_bucket(
                layer, _pad_rows(un[idx], n), nf, width=w
            )
            out = _scatter_back(out, idx, res)
        return out[:B].reshape(shape)
    deg = _host_degrees(memb, un, getattr(layer, "memb_ov", None))
    per_node_wn = node_max_hyperedge_size(layer)
    for idx, wm in plan_buckets(deg, layer.max_memberships, widths):
        needed = int(per_node_wn[np.clip(un[idx], 0, per_node_wn.size - 1)].max())
        wn = next(
            w
            for w in _width_ladder(layer.max_hyperedge_size, widths)
            if w >= needed
        )
        n = pow2_ceil(idx.size)
        pallas_here = (
            use_pallas
            if use_pallas is not None
            else (_on_tpu() and wm * wn <= UNION_PALLAS_MAX_FLAT)
        )
        va, _ = _node_alters_bucket(
            layer, _pad_rows(un[idx], n), nf,
            width_m=wm, width_n=wn, max_alters=wm * wn,
            use_pallas=pallas_here, interpret=interpret,
        )
        counts = jnp.sum(va != SENTINEL, axis=-1).astype(jnp.int32)
        out = _scatter_back(out, idx, counts)
    return out[:B].reshape(shape)


def alters_bound(layers, u, n_nodes: int) -> int:
    """Host-side upper bound on distinct alters across ``layers`` for batch u.

    Two-mode layers contribute ≤ deg(u) × (max hyperedge size among u's
    hyperedges − 1); one-mode layers their out-degree. Falls back to
    ``n_nodes`` when anything is traced. Used to size exact alter queries
    (e.g. analysis.projected_degree) without a (B, n_nodes) blowup.
    """
    if not can_dispatch(u):
        return n_nodes
    un = np.asarray(u, dtype=np.int64).reshape(-1)
    if un.size == 0:
        return 1
    total = np.zeros(un.size, dtype=np.int64)
    for layer in layers:
        memb = getattr(layer, "memb", None)
        if memb is not None:
            csr, ov = memb, getattr(layer, "memb_ov", None)
            other = ov_buffers(getattr(layer, "members_ov", None))
        else:
            csr, ov = layer.out, layer.out_ov
            other = ()
        if not can_dispatch(csr.indptr, csr.indices, *ov_buffers(ov), *other):
            return n_nodes
        deg = _host_degrees(csr, un, ov)
        if memb is not None:
            wn = node_max_hyperedge_size(layer)
            wn_u = wn[np.clip(un, 0, wn.size - 1)]
            total += deg * np.maximum(wn_u - 1, 0)
        else:
            total += deg
    return int(np.clip(total.max(), 1, n_nodes))


def union_rows(
    vals: jnp.ndarray,
    valid: jnp.ndarray,
    max_out: int,
    *,
    use_pallas: bool | None = None,
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Sorted-unique rows capped at ``max_out`` (multilayer alters merge).

    jit-compatible either way; ``use_pallas=None`` picks the segmented-union
    kernel on TPU for rows narrow enough for all-pairs dedup, else the
    ``padded_unique`` sort path.
    """
    from repro.kernels import ops as kops

    flat = jnp.where(valid, vals, SENTINEL)
    if use_pallas is None:
        use_pallas = _on_tpu() and flat.shape[-1] <= UNION_PALLAS_MAX_FLAT
    return kops.segmented_union(
        flat, max_out, use_pallas=use_pallas, interpret=interpret
    )
