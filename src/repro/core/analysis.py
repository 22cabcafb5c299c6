"""Proof-of-concept analytics (paper §3.1 'Analysis' area), multilayer-aware.

* degree centrality, density, attribute summaries — trivial reductions.
* BFS shortest paths across any subset of layers of mixed modes: dense
  frontier expansion. Two-mode layers advance node-frontier → hyperedge
  -frontier → node-frontier, i.e. one *pseudo-projected* hop costs two
  bipartite sparse ops and never touches the k(k−1)/2 projection —
  DESIGN.md §4.2's traversal form of the paper's idea.
* connected components: iterative label propagation (min-label) to fixpoint,
  also through hyperedges without projecting.

Frontier expansion uses per-edge source-row ids (csr_row_ids), built lazily
host-side and O(nnz) per BFS level — the data-parallel formulation.
"""

from __future__ import annotations

from typing import Sequence

import numpy as np
import jax
import jax.numpy as jnp

from .csr import CSR, csr_row_ids
from .layers import LayerTwoMode
from .network import Network

__all__ = [
    "degree_centrality",
    "projected_degree",
    "degree_distribution",
    "density",
    "attribute_summary",
    "bfs_distances",
    "shortest_path_length",
    "connected_components",
]

_INF = np.int32(2**31 - 1)


# ---------------------------------------------------------------------------
# Simple metrics
# ---------------------------------------------------------------------------


def degree_centrality(net: Network, layer_names: Sequence[str] | None = None):
    """Per-node degree summed over selected layers (two-mode: memberships)."""
    total = jnp.zeros((net.n_nodes,), dtype=jnp.int32)
    for layer in net._select(layer_names):
        total = total + layer.degrees().astype(jnp.int32)
    return total


def projected_degree(
    net: Network,
    u: jnp.ndarray,
    layer_names: Sequence[str] | None = None,
    max_alters: int | None = None,
    node_filter=None,
) -> jnp.ndarray:
    """Exact *projected* degree per query node -> int32[B].

    Counts distinct alters across the selected layers — for two-mode layers
    this is the degree in the never-materialized projection (≠ membership
    count). Concrete query batches run through the degree-bucketed
    dispatcher (core/dispatch.py), so hub queries don't inflate the batch.
    ``max_alters`` caps the per-node count; the default is exact — a tight
    host-side bound on the batch's largest possible alter set
    (dispatch.alters_bound), falling back to n_nodes under tracing.
    ``node_filter`` counts only alters passing an attribute predicate.
    """
    from . import dispatch

    if max_alters is None:
        max_alters = dispatch.alters_bound(
            net._select(layer_names), u, net.n_nodes
        )
    _, mask = net.node_alters(u, max_alters, layer_names,
                              node_filter=node_filter)
    return jnp.sum(mask, axis=-1).astype(jnp.int32)


def degree_distribution(
    net: Network,
    layer_names: Sequence[str] | None = None,
    node_filter=None,
) -> tuple[np.ndarray, np.ndarray]:
    """Degree histogram over all nodes -> (degrees int64[k], counts int64[k]).

    Degree is the summed per-layer degree (two-mode: membership count),
    matching ``Network.degree``'s unfiltered semantics. ``node_filter``
    restricts *which nodes are counted* (the population), not their
    degrees. Zero-count degrees are omitted.
    """
    from .nodeset import node_filter_mask

    total = np.asarray(degree_centrality(net, layer_names), dtype=np.int64)
    nf = node_filter_mask(node_filter, net.n_nodes)
    if nf is not None:
        total = total[np.asarray(nf, dtype=bool)]
    if total.size == 0:
        return np.zeros(0, np.int64), np.zeros(0, np.int64)
    counts = np.bincount(total)
    degs = np.nonzero(counts)[0]
    return degs.astype(np.int64), counts[degs].astype(np.int64)


def density(layer) -> float:
    n = layer.n_nodes
    if n < 2:
        return 0.0
    if isinstance(layer, LayerTwoMode):
        # bipartite density: memberships / (n_nodes * n_hyperedges)
        return float(layer.n_memberships) / (n * max(layer.n_hyperedges, 1))
    possible = n * (n - 1)
    if not layer.directed:
        possible //= 2
    return float(layer.n_edges) / possible


def attribute_summary(net: Network, name: str) -> dict:
    col = net.nodeset.attrs.column(name)
    vals = np.asarray(col.values)
    out = {
        "name": name,
        "kind": col.kind,
        "n_set": col.n_set,
        "coverage": col.n_set / max(net.n_nodes, 1),
    }
    if col.kind in ("int", "float") and vals.size:
        out.update(
            mean=float(vals.mean()), min=float(vals.min()),
            max=float(vals.max()), std=float(vals.std()),
        )
    return out


# ---------------------------------------------------------------------------
# Frontier expansion primitives
# ---------------------------------------------------------------------------


def _expand_csr(
    csr: CSR, row_ids: jnp.ndarray, frontier: jnp.ndarray, n_out: int
) -> jnp.ndarray:
    """bool[n_rows] frontier -> bool[n_out] reached via csr edges. O(nnz)."""
    if csr.nnz == 0:
        return jnp.zeros((n_out,), dtype=bool)
    active = jnp.take(frontier, row_ids)  # per-edge: source in frontier?
    out = jnp.zeros((n_out,), dtype=bool)
    return out.at[csr.indices].max(active)


class _LayerExpander:
    """Pre-extracts row-id arrays so expansion is pure jnp (jit-friendly)."""

    def __init__(self, layer):
        from .layers import compact_layer, has_overlay

        if has_overlay(layer):
            # expansion reads raw CSR buffers; fold the delta overlay
            # first (bit-identical by the compaction contract)
            layer = compact_layer(layer)
        self.layer = layer
        if isinstance(layer, LayerTwoMode):
            self.memb_rows = csr_row_ids(layer.memb)
            self.members_rows = csr_row_ids(layer.members)
        else:
            self.out_rows = csr_row_ids(layer.out)

    def expand(self, frontier: jnp.ndarray, n_nodes: int) -> jnp.ndarray:
        if isinstance(self.layer, LayerTwoMode):
            he = _expand_csr(
                self.layer.memb, self.memb_rows, frontier,
                self.layer.n_hyperedges,
            )
            return _expand_csr(
                self.layer.members, self.members_rows, he, n_nodes
            )
        return _expand_csr(self.layer.out, self.out_rows, frontier, n_nodes)


# ---------------------------------------------------------------------------
# BFS shortest paths
# ---------------------------------------------------------------------------


def bfs_distances(
    net: Network,
    source: int | jnp.ndarray,
    layer_names: Sequence[str] | None = None,
    max_steps: int | None = None,
) -> jnp.ndarray:
    """Unweighted multilayer BFS -> int32[n_nodes] distances (INF unreached).

    Pseudo-projected hops through two-mode layers count as ONE step (they
    are edges of the never-materialized projection).
    """
    n = net.n_nodes
    expanders = [_LayerExpander(l) for l in net._select(layer_names)]
    max_steps = n if max_steps is None else max_steps

    src = jnp.zeros((n,), dtype=bool).at[jnp.asarray(source)].set(True)

    def step(state):
        dist, frontier, d = state
        nxt = jnp.zeros((n,), dtype=bool)
        for e in expanders:
            nxt = nxt | e.expand(frontier, n)
        nxt = nxt & (dist == _INF)
        dist = jnp.where(nxt, d + 1, dist)
        return dist, nxt, d + 1

    def cond(state):
        _, frontier, d = state
        return jnp.any(frontier) & (d < max_steps)

    dist0 = jnp.where(src, 0, _INF).astype(jnp.int32)
    dist, _, _ = jax.lax.while_loop(cond, step, (dist0, src, jnp.int32(0)))
    return dist


def shortest_path_length(
    net: Network,
    source: int,
    target: int,
    layer_names: Sequence[str] | None = None,
) -> int:
    """Paper Listing 3 ``shortestpath`` — returns -1 if unreachable."""
    n = net.n_nodes
    expanders = [_LayerExpander(l) for l in net._select(layer_names)]
    src = jnp.zeros((n,), dtype=bool).at[source].set(True)
    visited = src

    def cond(state):
        visited, frontier, d, found = state
        return (~found) & jnp.any(frontier) & (d < n)

    def step(state):
        visited, frontier, d, _ = state
        nxt = jnp.zeros((n,), dtype=bool)
        for e in expanders:
            nxt = nxt | e.expand(frontier, n)
        nxt = nxt & ~visited
        visited = visited | nxt
        return visited, nxt, d + 1, nxt[target]

    _, _, d, found = jax.lax.while_loop(
        cond, step, (visited, src, jnp.int32(0), src[target])
    )
    return int(jnp.where(found, d, -1))


# ---------------------------------------------------------------------------
# Connected components
# ---------------------------------------------------------------------------


def connected_components(
    net: Network, layer_names: Sequence[str] | None = None, node_filter=None
) -> jnp.ndarray:
    """Min-label propagation -> int32[n_nodes] component labels.

    Delegates to ``traversal.components_batched``: each sweep propagates
    labels one hop through every layer (two-mode layers through hyperedge
    labels, never projecting) and then pointer-jumps (label doubling), so
    long path graphs converge in O(log diameter) sweeps instead of the
    O(diameter) one-hop loop this function used to run. Directed layers
    are treated as undirected (weak components); ``node_filter`` restricts
    to the induced selection (filtered-out nodes stay singletons).
    """
    from .traversal import components_batched

    return components_batched(net, layer_names, node_filter=node_filter)
