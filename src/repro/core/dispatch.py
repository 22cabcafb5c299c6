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
  5. launches every bucket back to back, brings all their results to
     the host in one fetch, and scatters them into the batch's order
     there.

The cores (``edge_value_host``, ``node_alters_host``,
``filtered_degree_host``) take host ids and return host arrays: the
serving path's form, where ids arrive from the wire and answers leave
on it. The ``bucketed_*`` wrappers take ids from anywhere and return a
jax array, converted once.

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

from . import obs
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
    "host_ids",
    "plan_buckets",
    "edge_value_host",
    "node_alters_host",
    "filtered_degree_host",
    "degree_sum",
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


# in the table from the start, so that ``/stats`` reads 0 until a call
for _name in ("dispatch.host_ids", "dispatch.device_ids"):
    obs.count(_name, 0)


def can_dispatch(*arrays) -> bool:
    """True when every array is concrete (not inside a jit trace).

    Callers must pass the layer's own buffers (indptr/indices) along with
    the query ids: a layer flowing through jit as a pytree argument is
    traced even when the queries are host arrays.
    """
    return not any(isinstance(a, jax.core.Tracer) for a in arrays)


def host_ids(*ids) -> list[np.ndarray]:
    """Each id array as a flat int64 host array.

    Counts the call as ``dispatch.host_ids``, or as
    ``dispatch.device_ids`` where any array had to come back from the
    device (in one fetch, with the others).
    """
    on_device = any(isinstance(a, jax.Array) for a in ids)
    obs.count("dispatch.device_ids" if on_device else "dispatch.host_ids")
    return [a.reshape(-1) for a in obs.fetch(list(ids), np.int64)]


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


def _launch(kernel: str | None, width: int, rows: int) -> obs.span:
    """Count one bucket program (and its Pallas ``kernel``, if it takes
    that path) and return the span that times its launch."""
    obs.count("dispatch.buckets")
    if kernel is not None:
        obs.count(f"kernels.{kernel}")
    return obs.span("threadle.dispatch.launch", width=width, rows=rows)


def _pad_rows(ids: np.ndarray, n: int) -> np.ndarray:
    """A bucket's ids padded to ``n`` rows, on the host: the jitted
    bucket program takes them as its argument."""
    out = np.zeros((n,), dtype=np.int32)
    out[: ids.size] = ids
    return out


def _fetch_buckets(launched: list) -> list[tuple[np.ndarray, np.ndarray]]:
    """[(positions, device result)] -> [(positions, its real rows)]: every
    bucket's result comes to the host in one fetch."""
    res = obs.fetch([r for _, r in launched])
    return [(idx, r[: idx.size]) for (idx, _), r in zip(launched, res)]


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


def _two_hop_plan(layer, un: np.ndarray, widths) -> list[tuple]:
    """[(positions, membership width, hyperedge width)] per bucket of a
    two-mode layer: the second hop pads to the widest hyperedge among the
    bucket's own nodes, rounded up the width ladder (compile-count
    bound)."""
    with obs.span("threadle.dispatch.plan"):
        deg = _host_degrees(layer.memb, un, getattr(layer, "memb_ov", None))
        per_node_wn = node_max_hyperedge_size(layer)
        ladder = _width_ladder(layer.max_hyperedge_size, widths)
        out = []
        for idx, wm in plan_buckets(deg, layer.max_memberships, widths):
            needed = int(
                per_node_wn[np.clip(un[idx], 0, per_node_wn.size - 1)].max()
            )
            out.append((idx, wm, next(w for w in ladder if w >= needed)))
        return out


def _union_pallas_here(use_pallas: bool | None, flat_width: int) -> bool:
    """The segmented-union kernel's auto rule: on a TPU, for rows narrow
    enough for its all-pairs dedup."""
    if use_pallas is not None:
        return use_pallas
    return _on_tpu() and flat_width <= UNION_PALLAS_MAX_FLAT


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


@functools.partial(
    jax.jit, static_argnames=("width_m", "width_n", "use_pallas", "interpret")
)
def _two_mode_filtered_degree_bucket(
    layer, u, node_filter, *, width_m, width_n, use_pallas, interpret,
):
    """Distinct co-members passing the filter: the filtered alters at the
    bucket's exact flat width (uncapped), counted on the device."""
    from repro.kernels import ops as kops

    vals, _ = kops.pseudo_node_alters(
        layer, u, width_m * width_n,
        width_m=width_m, width_n=width_n, node_filter=node_filter,
        use_pallas=use_pallas, interpret=interpret,
    )
    return jnp.sum(vals != SENTINEL, axis=-1).astype(jnp.int32)


def _degree_csr(layer):
    """(CSR, overlay) whose row lengths are ``layer.degrees()``."""
    if getattr(layer, "memb", None) is not None:
        return layer.memb, layer.memb_ov
    return layer.out, layer.out_ov


def _row_lengths(indptr, r):
    return jnp.take(indptr, r + 1, mode="clip") - jnp.take(
        indptr, r, mode="clip"
    )


@jax.jit
def _degree_sum(parts, u):
    total = jnp.zeros(jnp.shape(u), jnp.int32)
    for indptr, ov in parts:
        # eff_degrees: rows past the base read 0, dirty rows the delta's
        n_base = indptr.shape[0] - 1
        n = n_base if ov is None else ov[0].shape[0] - 1
        r = jnp.clip(u, 0, max(n - 1, 0))
        deg = jnp.where(r < n_base, _row_lengths(indptr, r), 0)
        if ov is not None:
            dptr, dirty = ov
            deg = jnp.where(
                jnp.take(dirty, r, mode="clip"), _row_lengths(dptr, r), deg
            )
        total = total + deg.astype(jnp.int32)
    return total


def degree_sum(layers, u) -> jnp.ndarray:
    """Summed per-layer degree (two-mode: membership count) -> int32[...].

    One program per (layer set, batch shape): each layer's ``indptr`` is
    read at ``u`` and ``u + 1`` only, overlay-aware, with the clip of
    ``jnp.take(layer.degrees(), u, mode="clip")``, so no layer's whole
    degree vector is computed. ``u`` may be host ids, a device array or
    a tracer.
    """
    parts = []
    for layer in layers:
        csr, ov = _degree_csr(layer)
        parts.append(
            (csr.indptr, None if ov is None else (ov.delta.indptr, ov.dirty))
        )
    return _degree_sum(tuple(parts), u)


# ---------------------------------------------------------------------------
# Dispatchers: host cores
# ---------------------------------------------------------------------------


def edge_value_host(
    layer,
    un: np.ndarray,
    vn: np.ndarray,
    *,
    node_filter=None,
    widths=DEFAULT_BUCKET_WIDTHS,
    use_pallas: bool | None = None,
    interpret: bool | None = None,
) -> np.ndarray:
    """Degree-bucketed GetEdgeValue over flat host ids -> float32[B] on the
    host.

    Buckets by max(deg(u), deg(v)) so both membership rows fit the bucket
    width. ``use_pallas=None`` auto-selects: the Pallas intersect kernel on
    TPU for buckets >= PALLAS_MIN_WIDTH, ``sorted_isin`` otherwise.

    ``node_filter`` (bool[n_nodes]) restricts the query to selected target
    nodes: pairs whose ``v`` fails the filter return 0 — and are dropped
    from the plan *before* any bucket runs, so a mostly-filtered batch does
    a fraction of the unfiltered work.
    """
    out = np.zeros(un.size, np.float32)
    if node_filter is not None:
        nf = obs.fetch(node_filter, bool)
        keep = nf[np.clip(vn, 0, nf.size - 1)]
        if keep.any():
            out[keep] = edge_value_host(
                layer, un[keep], vn[keep],
                widths=widths, use_pallas=use_pallas, interpret=interpret,
            )
        return out
    if un.size == 0:
        return out
    with obs.span("threadle.dispatch.plan"):
        memb_ov = getattr(layer, "memb_ov", None)
        deg = np.maximum(
            _host_degrees(layer.memb, un, memb_ov),
            _host_degrees(layer.memb, vn, memb_ov),
        )
        buckets = plan_buckets(deg, layer.max_memberships, widths)
    launched = []
    for idx, w in buckets:
        n = pow2_ceil(idx.size)
        pallas_here = (
            use_pallas
            if use_pallas is not None
            else (_on_tpu() and w >= PALLAS_MIN_WIDTH)
        )
        with _launch("intersect" if pallas_here else None, w, n):
            launched.append((idx, _edge_value_bucket(
                layer, _pad_rows(un[idx], n), _pad_rows(vn[idx], n),
                width=w, use_pallas=pallas_here, interpret=interpret,
            )))
    for idx, res in _fetch_buckets(launched):
        out[idx] = res
    return out


def node_alters_host(
    layer,
    un: np.ndarray,
    max_alters: int,
    *,
    node_filter=None,
    widths=DEFAULT_BUCKET_WIDTHS,
    use_pallas: bool | None = None,
    interpret: bool | None = None,
) -> np.ndarray:
    """Degree-bucketed GetNodeAlters over flat host ids -> int32[B,
    max_alters] on the host, SENTINEL-padded (the mask is ``!= SENTINEL``).

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
    out = np.full((un.size, max_alters), SENTINEL, np.int32)
    if un.size == 0:
        return out
    nf = None if node_filter is None else jnp.asarray(node_filter, bool)
    launched = []
    for idx, wm, wn in _two_hop_plan(layer, un, widths):
        n = pow2_ceil(idx.size)
        pallas_here = _union_pallas_here(use_pallas, wm * wn)
        with _launch("segmented_union" if pallas_here else None, wm * wn, n):
            va, _ = _node_alters_bucket(
                layer, _pad_rows(un[idx], n), nf,
                width_m=wm, width_n=wn, max_alters=max_alters,
                use_pallas=pallas_here, interpret=interpret,
            )
            launched.append((idx, va))
    for idx, va in _fetch_buckets(launched):
        out[idx] = va
    return out


def filtered_degree_host(
    layers,
    un: np.ndarray,
    node_filter,
    *,
    widths=DEFAULT_BUCKET_WIDTHS,
    use_pallas: bool | None = None,
    interpret: bool | None = None,
) -> np.ndarray:
    """Degree-bucketed filtered-alter counts over flat host ids, summed
    across ``layers`` -> int32[B] on the host.

    One-mode: neighbors passing the filter (gather at the bucket width +
    mask-sum). Two-mode: *distinct* co-members passing the filter — each
    bucket runs the filtered alters kernel at its exact flat width
    (wm × wn) so the count is uncapped and exact. Every layer's buckets
    launch before the one fetch.
    """
    out = np.zeros(un.size, np.int32)
    if un.size == 0:
        return out
    nf = jnp.asarray(node_filter, bool)
    launched = []
    for layer in layers:
        if getattr(layer, "memb", None) is None:  # one-mode
            with obs.span("threadle.dispatch.plan"):
                deg = _host_degrees(layer.out, un, layer.out_ov)
                buckets = plan_buckets(deg, max(int(deg.max()), 1), widths)
            for idx, w in buckets:
                n = pow2_ceil(idx.size)
                with _launch(None, w, n):
                    launched.append((idx, _one_mode_filtered_degree_bucket(
                        layer, _pad_rows(un[idx], n), nf, width=w
                    )))
            continue
        for idx, wm, wn in _two_hop_plan(layer, un, widths):
            n = pow2_ceil(idx.size)
            pallas_here = _union_pallas_here(use_pallas, wm * wn)
            with _launch(
                "segmented_union" if pallas_here else None, wm * wn, n
            ):
                launched.append((idx, _two_mode_filtered_degree_bucket(
                    layer, _pad_rows(un[idx], n), nf,
                    width_m=wm, width_n=wn,
                    use_pallas=pallas_here, interpret=interpret,
                )))
    for idx, res in _fetch_buckets(launched):
        out[idx] += res
    return out


# ---------------------------------------------------------------------------
# Dispatchers: ids from anywhere, a jax array back
# ---------------------------------------------------------------------------


def bucketed_edge_value(layer, u, v, **kw) -> jnp.ndarray:
    """:func:`edge_value_host` over ids of any kind -> f32[...] (u's shape)."""
    un, vn = host_ids(u, v)
    out = edge_value_host(layer, un, vn, **kw)
    return jnp.asarray(out.reshape(jnp.shape(u)))


def bucketed_check_edge(layer, u, v, **kw) -> jnp.ndarray:
    return bucketed_edge_value(layer, u, v, **kw) > 0


def bucketed_node_alters(
    layer, u, max_alters: int, **kw
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """:func:`node_alters_host` over ids of any kind -> (int32[...,
    max_alters], mask)."""
    (un,) = host_ids(u)
    vals = node_alters_host(layer, un, max_alters, **kw)
    vals = vals.reshape(jnp.shape(u) + (max_alters,))
    return jnp.asarray(vals), jnp.asarray(vals != SENTINEL)


def bucketed_filtered_degree(layer, u, node_filter, **kw) -> jnp.ndarray:
    """:func:`filtered_degree_host` of one layer over ids of any kind ->
    int32[...]."""
    (un,) = host_ids(u)
    out = filtered_degree_host((layer,), un, node_filter, **kw)
    return jnp.asarray(out.reshape(jnp.shape(u)))


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
    use_pallas = _union_pallas_here(use_pallas, flat.shape[-1])
    if use_pallas and can_dispatch(flat):
        obs.count("kernels.segmented_union")
    return kops.segmented_union(
        flat, max_out, use_pallas=use_pallas, interpret=interpret
    )
