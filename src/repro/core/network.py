"""Multilayer mixed-mode Network container + mode-agnostic query API.

A Network references a Nodeset and holds named layers, each one-mode or
two-mode (paper §3.1). Because both layer classes implement the shared
query protocol, every multilayer operation below works across layers of
*different modes* without branching at the call site — the paper's central
API contract (Listing 3: ``getnodealters(net, v, layernames=Workplaces;
Communication)`` mixes a two-mode and a one-mode layer).

Layer membership of the container is static pytree metadata (names,
ordering) while the layer contents are pytree children — so a Network flows
through jit / pjit unchanged.
"""

from __future__ import annotations

from typing import Sequence

import numpy as np
import jax
import jax.numpy as jnp

from . import dispatch, obs
from .csr import SENTINEL
from .pytree import pytree_dataclass
from .layers import LayerOneMode, LayerTwoMode
from .nodeset import Nodeset, create_nodeset, node_filter_mask

Layer = LayerOneMode | LayerTwoMode


@pytree_dataclass(static=("layer_names",))
class Network:
    nodeset: Nodeset
    layers: tuple[Layer, ...]
    layer_names: tuple[str, ...]

    # -- container ----------------------------------------------------------

    @property
    def n_nodes(self) -> int:
        return self.nodeset.n_nodes

    def layer(self, name: str) -> Layer:
        try:
            return self.layers[self.layer_names.index(name)]
        except ValueError:
            raise KeyError(
                f"no layer {name!r}; have {self.layer_names}"
            ) from None

    def with_layer(self, name: str, layer: Layer) -> "Network":
        if layer.n_nodes != self.n_nodes:
            raise ValueError(
                f"layer has {layer.n_nodes} nodes, network has {self.n_nodes}"
            )
        if name in self.layer_names:
            i = self.layer_names.index(name)
            return Network(
                nodeset=self.nodeset,
                layers=self.layers[:i] + (layer,) + self.layers[i + 1 :],
                layer_names=self.layer_names,
            )
        return Network(
            nodeset=self.nodeset,
            layers=self.layers + (layer,),
            layer_names=self.layer_names + (name,),
        )

    def with_nodeset(self, nodeset: Nodeset) -> "Network":
        """Swap the nodeset (attribute mutations rebind functionally)."""
        if nodeset.n_nodes != self.n_nodes:
            raise ValueError(
                f"nodeset has {nodeset.n_nodes} nodes, network has "
                f"{self.n_nodes}"
            )
        return Network(
            nodeset=nodeset, layers=self.layers, layer_names=self.layer_names
        )

    def without_layer(self, name: str) -> "Network":
        i = self.layer_names.index(name)
        return Network(
            nodeset=self.nodeset,
            layers=self.layers[:i] + self.layers[i + 1 :],
            layer_names=self.layer_names[:i] + self.layer_names[i + 1 :],
        )

    def _select(self, layer_names: Sequence[str] | None) -> tuple[Layer, ...]:
        if layer_names is None:
            return self.layers
        return tuple(self.layer(n) for n in layer_names)

    # -- mode-agnostic multilayer queries (paper Listing 3) ------------------

    def check_edge(
        self, layer_name: str, u: jnp.ndarray, v: jnp.ndarray
    ) -> jnp.ndarray:
        u, v = _as_batch(u), _as_batch(v)
        return self.layer(layer_name).check_edge(u, v)

    def edge_value(
        self, layer_name: str, u: jnp.ndarray, v: jnp.ndarray,
        node_filter=None,
    ) -> jnp.ndarray:
        u, v = _as_batch(u), _as_batch(v)
        nf = node_filter_mask(node_filter, self.n_nodes)
        return self.layer(layer_name).edge_value(u, v, node_filter=nf)

    def check_edge_any(
        self, u: jnp.ndarray, v: jnp.ndarray,
        layer_names: Sequence[str] | None = None,
        node_filter=None,
    ) -> jnp.ndarray:
        """Edge existence across layers of any mode (OR-combined).

        ``node_filter`` (NodeSelection or bool[n_nodes]) restricts targets:
        the result is True only when ``v`` passes the filter — "is v, among
        the selected nodes, connected to u?". Filtered-out pairs skip the
        bucketed pseudo-projection work entirely.
        """
        u, v = _as_batch(u), _as_batch(v)
        nf = node_filter_mask(node_filter, self.n_nodes)
        out = jnp.zeros(u.shape, dtype=bool)
        for layer in self._select(layer_names):
            out = out | layer.check_edge(u, v, node_filter=nf)
        return out

    def node_alters(
        self,
        u: jnp.ndarray,
        max_alters: int,
        layer_names: Sequence[str] | None = None,
        node_filter=None,
    ) -> tuple[jnp.ndarray, jnp.ndarray]:
        """Union of alters across selected layers (mixed modes welcome).

        Returns (int32[B, max_alters] sorted padded, mask). Two-mode layers
        contribute pseudo-projected alters; concrete query batches run
        degree-bucketed per layer (core/dispatch.py) and the cross-layer
        merge goes through the segmented-union dispatch rule. A single
        two-mode layer's rows are already sorted-unique and capped, so
        they are the answer, with no merge.

        ``node_filter`` (NodeSelection or bool[n_nodes]) keeps only alters
        passing an attribute predicate — the paper's "alters of u in the
        Workplaces layer where income > X" — applied inside the per-bucket
        kernels, with the ``max_alters`` cap applying post-filter.
        """
        u = _as_batch(u)
        nf = node_filter_mask(node_filter, self.n_nodes)
        layers = self._select(layer_names)
        if len(layers) == 1 and layers[0].mode == 2:
            return layers[0].node_alters(u, max_alters, node_filter=nf)
        parts, masks = [], []
        for layer in layers:
            a, m = layer.node_alters(u, max_alters, node_filter=nf)
            parts.append(a)
            masks.append(m)
        vals = jnp.concatenate(parts, axis=-1)
        mask = jnp.concatenate(masks, axis=-1)
        return dispatch.union_rows(vals, mask, max_alters)

    def degree(
        self, u: jnp.ndarray, layer_names: Sequence[str] | None = None,
        node_filter=None,
    ) -> jnp.ndarray:
        """Summed per-layer degree (two-mode: membership count).

        With ``node_filter``, the semantics switch to *filtered alter
        counts*: per layer, the number of neighbors (one-mode) / distinct
        co-members (two-mode) passing the filter, summed across layers —
        the count matching the post-filter oracle over per-layer alters.
        Note an all-True filter therefore differs from the unfiltered
        degree on two-mode layers (distinct co-members ≠ memberships).
        Unfiltered, every layer is read in one program
        (``dispatch.degree_sum``).
        """
        u = _as_batch(u)
        nf = node_filter_mask(node_filter, self.n_nodes)
        if nf is None:
            return dispatch.degree_sum(self._select(layer_names), u)
        total = jnp.zeros(u.shape, dtype=jnp.int32)
        for layer in self._select(layer_names):
            total = total + layer.filtered_degree(u, nf)
        return total

    # -- the same queries answered on the host (the serve executors) ---------
    #
    # Host ids in, host arrays out: a two-mode layer answers through the
    # dispatcher's host core (its buckets launch back to back, then one
    # fetch); any other query runs as above and its result is fetched
    # once. Concrete networks only.

    def edge_value_host(
        self, layer_name: str, u, v, node_filter=None
    ) -> np.ndarray:
        """``edge_value`` -> float32[B] on the host."""
        layer = self.layer(layer_name)
        nf = node_filter_mask(node_filter, self.n_nodes)
        if layer.mode == 2:
            un, vn = dispatch.host_ids(u, v)
            return dispatch.edge_value_host(layer, un, vn, node_filter=nf)
        return obs.fetch(self.edge_value(layer_name, u, v, node_filter=nf))

    def node_alters_host(
        self, u, max_alters: int, layer_names: Sequence[str] | None = None,
        node_filter=None,
    ) -> tuple[np.ndarray, np.ndarray]:
        """``node_alters`` -> (int32[B, max_alters], mask) on the host."""
        layers = self._select(layer_names)
        nf = node_filter_mask(node_filter, self.n_nodes)
        if len(layers) == 1 and layers[0].mode == 2:
            (un,) = dispatch.host_ids(u)
            vals = dispatch.node_alters_host(
                layers[0], un, max_alters, node_filter=nf
            )
            return vals, vals != SENTINEL
        return obs.fetch(
            self.node_alters(u, max_alters, layer_names, node_filter=nf)
        )

    def degree_host(
        self, u, layer_names: Sequence[str] | None = None, node_filter=None,
    ) -> np.ndarray:
        """``degree`` -> int32[B] on the host."""
        layers = self._select(layer_names)
        nf = node_filter_mask(node_filter, self.n_nodes)
        (un,) = dispatch.host_ids(u)
        if nf is None:
            return obs.fetch(dispatch.degree_sum(layers, un.astype(np.int32)))
        return dispatch.filtered_degree_host(layers, un, nf)

    # -- batched traversal (core/traversal.py) -------------------------------

    def khop(
        self,
        sources: jnp.ndarray,
        k: int,
        *,
        max_frontier: int | None = None,
        max_alters_per_node: int | None = None,
        layer_names: Sequence[str] | None = None,
        node_filter=None,
    ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
        """Batched k-hop neighborhoods -> (nodes, mask, hop_of_slot).

        Frontier-based multi-source BFS through the degree-bucketed
        dispatch — see ``traversal.khop_neighborhood`` for the layout
        (slot 0 = source, then k sorted hop groups of ``max_frontier``)."""
        from .traversal import khop_neighborhood

        return khop_neighborhood(
            self, sources, k, max_frontier=max_frontier,
            max_alters_per_node=max_alters_per_node,
            layer_names=layer_names, node_filter=node_filter,
        )

    def ego_batch(
        self,
        egos: jnp.ndarray,
        max_alters: int,
        *,
        k: int = 1,
        max_alters_per_node: int | None = None,
        layer_names: Sequence[str] | None = None,
        node_filter=None,
    ) -> tuple[jnp.ndarray, jnp.ndarray]:
        """Batched k-hop ego networks -> (int32[B, max_alters], dedup mask).

        Sorted-unique alters within k hops of each ego (ego excluded);
        every alter appears once however many paths reach it."""
        from .traversal import ego_batch

        return ego_batch(
            self, egos, max_alters, k=k,
            max_alters_per_node=max_alters_per_node,
            layer_names=layer_names, node_filter=node_filter,
        )

    # -- serving (serve/graph_engine.py) --------------------------------------

    def serve_session(self, **kw) -> "object":
        """A resident query-serving session over this network.

        Returns a ``repro.serve.GraphServeEngine``: bounded request
        queues, same-kind micro-batching through the bucketed dispatch,
        and an LRU result cache invalidated on mutation — the threadleR
        deployment model (§3.1). Keyword args forward to the engine
        (``cache_size``, ``queue_limit``, ``max_heavy_per_round``, ...).
        """
        from repro.serve.graph_engine import GraphServeEngine

        return GraphServeEngine(self, **kw)

    # -- bookkeeping ----------------------------------------------------------

    def compacted(self) -> "Network":
        """Fold every layer's delta overlay into a rebuilt base CSR.

        Returns ``self`` unchanged when no layer carries an overlay, so
        callers can use object identity to detect whether compaction did
        anything. Query results are bit-identical before and after.
        """
        from .layers import compact_layer, has_overlay

        if not any(has_overlay(l) for l in self.layers):
            return self
        return Network(
            nodeset=self.nodeset,
            layers=tuple(
                compact_layer(l) if has_overlay(l) else l
                for l in self.layers
            ),
            layer_names=self.layer_names,
        )

    @property
    def nbytes(self) -> int:
        return self.nodeset.nbytes + sum(l.nbytes for l in self.layers)


def _as_batch(x):
    """Query ids as an int32 batch of at least one dimension. Device arrays
    and tracers stay jax arrays; host ids stay on the host, where the
    dispatcher plans from them without a fetch."""
    if isinstance(x, jax.Array):
        x = jnp.asarray(x, dtype=jnp.int32)
    else:
        x = np.asarray(x, dtype=np.int32)
    return x[None] if x.ndim == 0 else x


def create_network(nodeset: Nodeset | int) -> Network:
    if isinstance(nodeset, int):
        nodeset = create_nodeset(nodeset)
    return Network(nodeset=nodeset, layers=(), layer_names=())
