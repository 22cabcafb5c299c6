"""Batched multi-source traversal over the pseudo-projection (paper §5).

threadleR exists to run sampling- and traversal-based analyses over
population-scale multilayer networks; the engine side of that contract is
dispatching *thousands of sources per call*, not one ego at a time. This
module is the batched traversal workload layer over the degree-bucketed
query engine (core/dispatch.py):

* ``khop_neighborhood`` — frontier-based k-hop BFS for B sources at once.
  Each hop flattens every source's frontier, dedups it across the whole
  batch host-side (a hub reached from hundreds of sources is expanded
  ONCE), pushes the unique nodes through the bucketed ``node_alters``
  dispatch, scatters the alters back per source, and compacts the next
  frontier with the sort-free frontier kernel (kernels/frontier.py):
  first occurrence of every candidate not already visited.
* ``ego_batch`` — batched ego-network extraction: padded per-source
  neighborhoods (sorted-unique, ego excluded) + a dedup mask.
* ``random_walk_batch`` — a walk fleet: W walkers per source in ONE
  ``lax.scan``, honoring ``layer_weights`` (categorical layer choice per
  walker per step) and ``node_filter`` (moves into filtered-out nodes are
  rejected; the walker stays in place).
* ``components_batched`` — min-label propagation with pointer jumping
  (label doubling), converging in O(log diameter) sweeps instead of the
  O(diameter) one-hop sweeps; two-mode layers propagate through hyperedge
  labels without projecting, and ``node_filter`` restricts components to
  the induced selection (filtered-out nodes stay singletons).

Everything composes with PR 2's ``NodeSelection`` filters and works on
one-mode and two-mode (pseudo-projected) layers alike. Concrete source
batches use exact host-side alter bounds (dispatch.alters_bound); traced
callers must pass static caps (``max_alters_per_node``).
"""

from __future__ import annotations

import functools
from typing import Sequence

import numpy as np
import jax
import jax.numpy as jnp

from . import dispatch, obs
from .csr import SENTINEL, on_tpu as _on_tpu
from .nodeset import node_filter_mask

__all__ = [
    "khop_neighborhood",
    "khop_records",
    "ego_batch",
    "random_walk_batch",
    "components_batched",
]

# Default per-hop frontier cap when the caller does not pass one.
DEFAULT_MAX_FRONTIER = 4096
# Flat-width budget for one hop-expansion gather: frontiers are processed
# in slot chunks so the (B, slots * cap) candidate buffer stays bounded
# even when a hub pushes the per-node alter bound toward n_nodes.
MAX_CAND_FLAT = 65536

_INF = np.int32(2**31 - 1)


def _layer_buffers(layer):
    from .overlay import ov_buffers

    memb = getattr(layer, "memb", None)
    if memb is not None:
        return (memb.indptr, memb.indices,
                layer.members.indptr, layer.members.indices,
                *ov_buffers(getattr(layer, "memb_ov", None)),
                *ov_buffers(getattr(layer, "members_ov", None)))
    return (layer.out.indptr, layer.out.indices,
            *ov_buffers(layer.out_ov))


def _hop_cap(
    net, frontier: jnp.ndarray, layer_names, max_alters_per_node: int | None
) -> int:
    """Static per-node alter width for this hop's gathers.

    Concrete frontiers get the exact host-side bound over the frontier's
    distinct nodes (dispatch.alters_bound); traced callers must pass
    ``max_alters_per_node``.
    """
    if max_alters_per_node is not None:
        return max(int(max_alters_per_node), 1)
    layers = net._select(layer_names)
    flat = frontier.reshape(-1)
    buffers = [b for l in layers for b in _layer_buffers(l)]
    if not dispatch.can_dispatch(flat, *buffers):
        raise ValueError(
            "khop on traced sources needs an explicit max_alters_per_node "
            "(host-side alter bounds are unavailable under tracing)"
        )
    fn = np.asarray(flat, dtype=np.int64)
    real = fn[fn != SENTINEL]
    if real.size == 0:
        return 1
    return hop_width(dispatch.alters_bound(layers, real, net.n_nodes))


def hop_width(bound: int) -> int:
    """A hop's per-node alter width: the exact bound rounded up to a power
    of two, so hops compile once per width class (a wider gather only
    adds SENTINEL padding — results are unchanged)."""
    return dispatch.pow2_ceil(bound, floor=1)


def _frontier_alters(
    net,
    frontier: jnp.ndarray,  # int32[B, F], SENTINEL-padded
    layer_names,
    nf,
    cap: int,
) -> jnp.ndarray:
    """Alters of every frontier slot -> candidate row int32[B, F*cap].

    Concrete frontiers dedup across the whole batch first: the bucketed
    dispatch sees each distinct frontier node once, however many sources
    reached it this hop.
    """
    B, F = frontier.shape
    layers = net._select(layer_names)
    flat = frontier.reshape(-1)
    buffers = [b for l in layers for b in _layer_buffers(l)]
    if dispatch.can_dispatch(flat, nf, *buffers):
        fn = np.asarray(flat, dtype=np.int64)
        real = fn != SENTINEL
        un = np.unique(fn[real])
        if un.size == 0:
            return jnp.full((B, F), SENTINEL, jnp.int32)
        alters, _ = net.node_alters(
            jnp.asarray(dispatch.pow2_pad(un), jnp.int32), cap, layer_names,
            node_filter=nf,
        )
        pos = np.where(real, np.searchsorted(un, np.where(real, fn, un[0])), -1)
        return _gather_candidates(alters, jnp.asarray(pos, jnp.int32), B, F)
    real = flat != SENTINEL
    alters, amask = net.node_alters(
        jnp.where(real, flat, 0), cap, layer_names, node_filter=nf
    )
    cand = jnp.where(real[:, None] & amask, alters, SENTINEL)
    return cand.reshape(B, F * cap)


@functools.partial(jax.jit, static_argnames=("B", "F"))
def _gather_candidates(alters, pos, B, F):
    """Row ``pos[i]`` of ``alters`` per frontier slot (SENTINEL where
    ``pos`` is -1, a pad slot) -> int32[B, F * cap]."""
    cand = jnp.take(alters, jnp.maximum(pos, 0), axis=0)
    cand = jnp.where((pos >= 0)[:, None], cand, SENTINEL)
    return cand.reshape(B, F * alters.shape[1])


def khop_neighborhood(
    net,
    sources: jnp.ndarray,
    k: int,
    *,
    max_frontier: int | None = None,
    max_alters_per_node: int | None = None,
    layer_names: Sequence[str] | None = None,
    node_filter=None,
    use_pallas: bool | None = None,
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Batched k-hop neighborhoods -> (nodes, mask, hop_of_slot).

    ``nodes`` is int32[B, 1 + k*max_frontier]: slot 0 is the source, then
    k groups of ``max_frontier`` slots, group h holding the (sorted,
    SENTINEL-padded) nodes first reached at hop h. ``mask`` flags valid
    slots; ``hop_of_slot`` is int32[1 + k*max_frontier] giving each slot's
    hop index (identical for every source row).

    ``max_frontier`` caps each hop's per-source frontier (capped hops
    truncate to the ``max_frontier`` smallest new ids — same contract as
    ``max_alters``). ``node_filter`` (NodeSelection / bool[n_nodes])
    restricts expansion to selected alters; sources are always included.
    Mixed one-/two-mode layer selections traverse the pseudo-projection
    without materializing it.
    """
    if k < 0:
        raise ValueError(f"k must be >= 0, got {k}")
    src = jnp.asarray(sources, dtype=jnp.int32)
    if src.ndim == 0:
        src = src[None]
    if src.ndim != 1:
        raise ValueError(f"sources must be a vector, got shape {src.shape}")
    B = src.shape[0]
    nf = node_filter_mask(node_filter, net.n_nodes)
    if max_frontier is None:
        max_frontier = min(net.n_nodes, DEFAULT_MAX_FRONTIER)
    max_frontier = max(int(max_frontier), 1)

    hop_of_slot = np.concatenate(
        [np.zeros(1, np.int32)]
        + [np.full(max_frontier, h, np.int32) for h in range(1, k + 1)]
    )
    from repro.kernels import ops as kops

    visited = src[:, None]
    frontier = src[:, None]
    groups = [src[:, None]]
    masks = [jnp.ones((B, 1), bool)]
    done_at = k  # hops actually expanded (early exit on empty frontier)
    for h in range(1, k + 1):
        # concrete frontiers are sorted with SENTINEL pads at the end, so
        # slicing to the batch's max occupancy (power-of-two rounded for
        # compile-count stability) drops dead pad columns before the
        # expensive expansion — typical frontiers fill a fraction of
        # max_frontier
        if dispatch.can_dispatch(frontier) and frontier.shape[1] > 1:
            used = int(np.sum(obs.fetch(frontier) != SENTINEL, axis=1).max())
            fw = dispatch.pow2_ceil(used, floor=1)
            frontier = frontier[:, : min(fw, frontier.shape[1])]
        cap = _hop_cap(net, frontier, layer_names, max_alters_per_node)
        # slot-chunk the expansion so the (B, slots*cap) candidate buffer
        # stays under MAX_CAND_FLAT even when a hub inflates cap; chunk
        # frontiers merge through union_rows — bit-identical to one shot
        # (each chunk's compact keeps its smallest new ids; the union of
        # the per-chunk smallest IS the hop's smallest max_frontier ids)
        F = frontier.shape[1]
        step = max(1, min(F, MAX_CAND_FLAT // cap))
        # one visited sort per hop, shared by every chunk's compact
        visited_hop = jnp.sort(visited, axis=-1)
        parts, pmasks = [], []
        for lo in range(0, F, step):
            cand = _frontier_alters(
                net, frontier[:, lo : lo + step], layer_names, nf, cap
            )
            # same auto rule as union_rows: the all-pairs Pallas kernel
            # wins on TPU for rows narrow enough for O(K^2); CPU (and very
            # wide rows) take the frontier_ref sort path — bit-identical
            pallas_here = (
                use_pallas
                if use_pallas is not None
                else (
                    _on_tpu()
                    and cand.shape[-1] <= dispatch.UNION_PALLAS_MAX_FLAT
                )
            )
            if pallas_here and dispatch.can_dispatch(cand):
                obs.count("kernels.frontier")
            pv, pm = kops.frontier_compact(
                cand, visited_hop, max_frontier,
                use_pallas=pallas_here, interpret=interpret,
                visited_sorted=True,
            )
            parts.append(pv)
            pmasks.append(pm)
        if len(parts) == 1:
            frontier, fmask = parts[0], pmasks[0]
        else:
            frontier, fmask = dispatch.union_rows(
                jnp.concatenate(parts, axis=-1),
                jnp.concatenate(pmasks, axis=-1),
                max_frontier,
                use_pallas=use_pallas, interpret=interpret,
            )
        groups.append(frontier)
        masks.append(fmask)
        visited = jnp.concatenate([visited, frontier], axis=-1)
        if dispatch.can_dispatch(fmask) and not bool(jnp.any(fmask)):
            done_at = h
            break
    pad = (k - done_at) * max_frontier
    nodes = jnp.concatenate(groups, axis=-1)
    mask = jnp.concatenate(masks, axis=-1)
    if pad:
        nodes = jnp.pad(nodes, ((0, 0), (0, pad)), constant_values=SENTINEL)
        mask = jnp.pad(mask, ((0, 0), (0, pad)), constant_values=False)
    return nodes, mask, jnp.asarray(hop_of_slot)


def khop_records(
    sources, nodes, mask, hop_of_slot
) -> list[dict]:
    """``khop_neighborhood`` output -> one client-facing record per source:
    ``{"source", "count", "nodes", "hops"}`` with the source slot dropped.
    The single definition shared by the CLI path (api.khop) and the serve
    path (serve/graph_engine) — their records are asserted identical."""
    nodes = np.asarray(nodes)
    mask = np.asarray(mask)
    hops = np.asarray(hop_of_slot)
    out = []
    for i, s in enumerate(np.asarray(sources).reshape(-1)):
        keep = mask[i] & (hops > 0)  # drop the source slot
        out.append({
            "source": int(s),
            "count": int(keep.sum()),
            "nodes": nodes[i][keep].tolist(),
            "hops": hops[keep].tolist(),
        })
    return out


def ego_batch(
    net,
    egos: jnp.ndarray,
    max_alters: int,
    *,
    k: int = 1,
    max_alters_per_node: int | None = None,
    layer_names: Sequence[str] | None = None,
    node_filter=None,
    use_pallas: bool | None = None,
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Batched ego-network extraction -> (int32[B, max_alters], dedup mask).

    The k-hop alter set of each ego (ego excluded), sorted-unique and
    SENTINEL-padded — every alter appears exactly once however many paths
    reach it. ``k=1`` is the multilayer ``node_alters`` union; ``k>1``
    runs the frontier-based BFS with per-hop cap ``max_alters`` and merges
    the hop groups (``max_alters_per_node`` bounds each node's gather
    width, as in ``khop_neighborhood``).
    """
    egos = jnp.asarray(egos, dtype=jnp.int32)
    if egos.ndim == 0:
        egos = egos[None]
    nf = node_filter_mask(node_filter, net.n_nodes)
    if k == 1:
        return net.node_alters(egos, max_alters, layer_names, node_filter=nf)
    nodes, mask, _ = khop_neighborhood(
        net, egos, k, max_frontier=max_alters,
        max_alters_per_node=max_alters_per_node, layer_names=layer_names,
        node_filter=nf, use_pallas=use_pallas, interpret=interpret,
    )
    return dispatch.union_rows(
        nodes[:, 1:], mask[:, 1:], max_alters,
        use_pallas=use_pallas, interpret=interpret,
    )


def random_walk_batch(
    net,
    start_nodes: jnp.ndarray,
    n_steps: int,
    key: jax.Array,
    *,
    walkers_per_start: int = 1,
    layer_names: Sequence[str] | None = None,
    layer_weights: Sequence[float] | None = None,
    node_filter=None,
) -> jnp.ndarray:
    """Walk fleet -> int32[B * walkers_per_start, n_steps + 1].

    W walkers per start node advance together in ONE ``lax.scan`` —
    walker w of start b is row ``b * walkers_per_start + w``. Layer
    choice per walker per step honors ``layer_weights`` (normalized
    categorical, as in ``random_walk``); ``node_filter`` rejects moves
    into filtered-out nodes (the walker stays put that step, mirroring
    the dangling-node rule). Start nodes are emitted as-is even when
    they fail the filter.
    """
    from .walks import _layer_logits

    layers = net._select(layer_names)
    logits = _layer_logits(len(layers), layer_weights)
    nf = node_filter_mask(node_filter, net.n_nodes)
    nfj = None if nf is None else jnp.asarray(nf)

    start = jnp.asarray(start_nodes, dtype=jnp.int32)
    if start.ndim == 0:
        start = start[None]
    if walkers_per_start < 1:
        raise ValueError(
            f"walkers_per_start must be >= 1, got {walkers_per_start}"
        )
    start = jnp.repeat(start, walkers_per_start)

    step_fns = [
        lambda u, kk, layer=layer: layer.sample_neighbor(u, kk)[0]
        for layer in layers
    ]

    def one_step(carry, _):
        u, kk = carry
        kk, k_layer, k_step = jax.random.split(kk, 3)
        if len(layers) == 1:
            v = step_fns[0](u, k_step)
        else:
            # logits precomputed outside the scan body (hoisted log);
            # walkers choose layers independently, so evaluate each
            # layer's step and select — len(layers) is small and static,
            # a per-walker lax.switch would serialize the batch
            choice = jax.random.categorical(k_layer, logits, shape=u.shape)
            keys = jax.random.split(k_step, len(layers))
            candidates = jnp.stack(
                [fn(u, kx) for fn, kx in zip(step_fns, keys)], axis=0
            )
            v = jnp.take_along_axis(candidates, choice[None, :], axis=0)[0]
        if nfj is not None:
            v = jnp.where(jnp.take(nfj, v, mode="clip"), v, u)
        return (v, kk), v

    (_, _), path = jax.lax.scan(one_step, (start, key), None, length=n_steps)
    return jnp.concatenate([start[None], path], axis=0).T


def components_batched(
    net,
    layer_names: Sequence[str] | None = None,
    node_filter=None,
    max_sweeps: int | None = None,
) -> jnp.ndarray:
    """Connected components -> int32[n_nodes] labels (min node id wins).

    Min-label propagation with pointer jumping: each sweep propagates
    labels one hop through every selected layer (two-mode layers through
    hyperedge labels — never projecting), then short-circuits chains with
    ``labels = min(labels, labels[labels])``. Label doubling converges in
    O(log diameter) sweeps vs the one-hop sweep's O(diameter).

    ``node_filter`` computes components of the induced subnetwork:
    filtered-out nodes keep their own label (singletons) and never carry
    labels between selected nodes. Directed layers are treated as
    undirected (weak components).
    """
    from .layers import LayerTwoMode
    from .overlay import eff_edge_stream, eff_nnz

    n = net.n_nodes
    layers = net._select(layer_names)
    nf = node_filter_mask(node_filter, n)
    nfj = None if nf is None else jnp.asarray(nf)
    # per-layer effective (row, col) edge streams: base CSR order for
    # overlay-free layers, clean-base + dirty-delta entries otherwise —
    # min-label scatters are order-independent, so both are bit-identical
    # to sweeping the rebuilt layer
    prep = []
    for layer in layers:
        if isinstance(layer, LayerTwoMode):
            if eff_nnz(layer.memb, layer.memb_ov):
                mrows, mcols = eff_edge_stream(layer.memb, layer.memb_ov)
                hrows, hcols = eff_edge_stream(
                    layer.members, layer.members_ov
                )
                prep.append((layer.n_hyperedges, mrows, mcols, hrows, hcols))
        elif eff_nnz(layer.out, layer.out_ov):
            rows, cols = eff_edge_stream(layer.out, layer.out_ov)
            prep.append((None, rows, cols, None, None))

    def sweep(labels):
        for n_he, rows, cols, hrows, hcols in prep:
            if n_he is None:
                src_lab = jnp.take(labels, rows)
                dst_lab = jnp.take(labels, cols)
                if nfj is not None:
                    live = (
                        jnp.take(nfj, rows)
                        & jnp.take(nfj, cols, mode="clip")
                    )
                    src_lab = jnp.where(live, src_lab, _INF)
                    dst_lab = jnp.where(live, dst_lab, _INF)
                labels = labels.at[cols].min(src_lab)
                labels = labels.at[rows].min(dst_lab)
            else:
                mem_lab = jnp.take(labels, hcols)
                if nfj is not None:
                    mem_lab = jnp.where(
                        jnp.take(nfj, hcols, mode="clip"), mem_lab, _INF
                    )
                he = jnp.full((n_he,), _INF, dtype=jnp.int32)
                he = he.at[hrows].min(mem_lab)
                node_min = jnp.take(he, cols)
                if nfj is not None:
                    node_min = jnp.where(
                        jnp.take(nfj, rows, mode="clip"), node_min, _INF
                    )
                labels = labels.at[rows].min(node_min)
        # pointer jumping: a label is itself a same-component node id, so
        # relabeling through it never leaves the component
        labels = jnp.minimum(labels, jnp.take(labels, labels))
        return labels

    limit = n if max_sweeps is None else max_sweeps

    def cond(state):
        labels, prev, it = state
        return jnp.any(labels != prev) & (it < limit)

    def body(state):
        labels, _, it = state
        return sweep(labels), labels, it + 1

    labels0 = jnp.arange(n, dtype=jnp.int32)
    if not prep:
        return labels0
    labels, _, _ = jax.lax.while_loop(
        cond, body, (sweep(labels0), labels0, jnp.int32(0))
    )
    return labels
