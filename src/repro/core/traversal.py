"""Batched multi-source traversal over the pseudo-projection (paper §5).

threadleR exists to run sampling- and traversal-based analyses over
population-scale multilayer networks; the engine side of that contract is
dispatching *thousands of sources per call*, not one ego at a time. This
module is the batched traversal workload layer over the degree-bucketed
query engine (core/dispatch.py):

* ``khop_neighborhood`` — frontier-based k-hop BFS for B sources at once.
  Over one-mode layers with concrete sources, a hop is one program whose
  work follows the hop's real candidates, the sum of the frontier's
  degrees (the one-pass hop below; ``khop_host`` is its host form).
  Otherwise (a two-mode layer in the selection, or traced sources) each
  hop flattens every source's frontier, dedups it across the whole batch
  host-side, pushes the unique nodes through the bucketed
  ``node_alters`` dispatch, scatters the alters back per source, and
  compacts the next frontier with the frontier kernel
  (kernels/frontier.py): first occurrence of every candidate not
  already visited.
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
    "khop_host",
    "khop_records",
    "ego_batch",
    "random_walk_batch",
    "components_batched",
]

# Default per-hop frontier cap when the caller does not pass one.
DEFAULT_MAX_FRONTIER = 4096
# Flat-width budget for one hop-expansion gather of the padded loop:
# frontiers are processed in slot chunks so the (B, slots * cap) candidate
# buffer stays bounded even when a hub pushes the per-node alter bound
# toward n_nodes.
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

    Concrete sources over one-mode layers take the one-pass hop
    (``_khop_one_pass``); a selection holding a two-mode layer, and
    traced callers (``max_alters_per_node`` given), take the padded
    frontier loop.
    """
    src, k, max_frontier, nf, layers = _khop_args(
        net, sources, k, max_frontier, node_filter, layer_names
    )
    if _one_pass_applies(net, layers, src, nf, max_alters_per_node):
        return tuple(
            jnp.asarray(a)
            for a in _khop_one_pass(net, layers, src, k, max_frontier, nf)
        )
    return _khop_padded(
        net, src, k, max_frontier, max_alters_per_node, layer_names, nf,
        use_pallas, interpret,
    )


def khop_host(
    net,
    sources,
    k: int,
    *,
    max_frontier: int | None = None,
    layer_names: Sequence[str] | None = None,
    node_filter=None,
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """``khop_neighborhood`` over concrete sources -> (nodes, mask,
    hop_of_slot) on the host: the serving path's form. The one-pass hop
    already holds every group on the host; the padded loop's result is
    fetched once."""
    src, k, max_frontier, nf, layers = _khop_args(
        net, sources, k, max_frontier, node_filter, layer_names
    )
    if _one_pass_applies(net, layers, src, nf, None):
        return _khop_one_pass(net, layers, src, k, max_frontier, nf)
    return tuple(obs.fetch(list(_khop_padded(
        net, src, k, max_frontier, None, layer_names, nf, None, None,
    ))))


def _khop_args(net, sources, k, max_frontier, node_filter, layer_names):
    if k < 0:
        raise ValueError(f"k must be >= 0, got {k}")
    src = sources if isinstance(sources, (np.ndarray, jax.Array)) \
        else np.asarray(sources, np.int32)
    if src.ndim == 0:
        src = src[None]
    if src.ndim != 1:
        raise ValueError(f"sources must be a vector, got shape {src.shape}")
    nf = node_filter_mask(node_filter, net.n_nodes)
    if max_frontier is None:
        max_frontier = min(net.n_nodes, DEFAULT_MAX_FRONTIER)
    return src, k, max(int(max_frontier), 1), nf, net._select(layer_names)


def _hop_of_slot(k: int, max_frontier: int) -> np.ndarray:
    return np.concatenate(
        [np.zeros(1, np.int32)]
        + [np.full(max_frontier, h, np.int32) for h in range(1, k + 1)]
    )


# ---------------------------------------------------------------------------
# One-pass hop: one-mode layers, concrete sources
# ---------------------------------------------------------------------------
#
# A hop's real candidates are the neighbours of every row's frontier
# nodes, each node's sorted row read only as far as the answer can lie
# (see ``_one_pass_rows``): T, the sum of those lengths, which the host
# reads from indptr. The hop is one jitted program (``_hop_expand``) that
# walks the T candidates in chunks of HOP_CHUNK slots on the device. Each
# frontier node's run of neighbour ids is a segment of slots; the host
# plans the live segments' slot offsets, source starts and rows as a
# table, and the segment-copy kernel (kernels/segment_copy.py) copies each
# run into its slots. The chunk's candidates are then sorted, as keys
# (row, id, flag), with the rows' visited ids (flag 0) and the hop's
# smallest ids so far (flag 1), so that first occurrences not yet visited
# can be counted per row and the ``max_frontier`` smallest kept. One
# chunk size means one compiled program for every hop size (the TPU's
# sorts and running sums compile for seconds per shape), a fixed peak of
# device memory, and fewer than HOP_CHUNK padded slots per hop.

HOP_CHUNK = 1 << 18
# rows of a hop program are padded to at least this many (and at most to
# one chunk's worth of frontier slots), so that groups of up to this many
# sources share one compiled program
HOP_ROW_FLOOR = 32
# a key packs (row, id, flag) into 31 bits
_KEY_BITS = 31

for _name in ("traversal.hops", "traversal.cand", "traversal.slots",
              "traversal.launches", "traversal.copies"):
    obs.count(_name, 0)


def _one_pass_applies(net, layers, src, nf, max_alters_per_node) -> bool:
    if max_alters_per_node is not None or not layers:
        return False
    if any(layer.mode != 1 for layer in layers):
        return False
    if _most_rows(_id_bits(net)) < 1:
        return False
    buffers = [b for layer in layers for b in _layer_buffers(layer)]
    return dispatch.can_dispatch(src, nf, *buffers)


def _id_bits(net) -> int:
    return max(int(net.n_nodes - 1).bit_length(), 1)


def _degree_parts(layers) -> list[tuple]:
    """(csr, dirty, take_dirty) per stored row source of the layers: a
    layer's base CSR, and its overlay's delta for the dirty rows."""
    parts = []
    for layer in layers:
        ov = layer.out_ov
        if ov is None:
            parts.append((layer.out, None, False))
        else:
            parts.append((layer.out, ov.dirty, False))
            parts.append((ov.delta, ov.dirty, True))
    return parts


def _part_runs(indptr, n_rows, dirty, take_dirty, rows):
    """(start, length) of each of ``rows``' runs in one part on the host
    (length 0 where the row is the other part's), clipped as
    ``overlay.eff_host_degrees`` clips."""
    r = np.clip(rows, 0, max(n_rows - 1, 0))
    start = indptr[r]
    deg = indptr[r + 1] - start
    if dirty is not None:
        d = dirty[np.clip(rows, 0, dirty.shape[0] - 1)]
        deg = np.where(d if take_dirty else ~d, deg, 0)
    return start, deg


def _prefix(x: jnp.ndarray, width: int = 128) -> jnp.ndarray:
    """Inclusive running sum of a 1-D int32 vector whose every running sum
    (over any stretch) lies within +-2**24, as products with a triangular
    matrix of ones: exact in float32, where the TPU takes seconds to
    compile ``cumsum`` of a long vector and runs it slower."""
    n = x.shape[0]
    if n <= width or n >= 1 << 24:  # short, or too long for the bound
        return jnp.cumsum(x)
    m = -(-n // width) * width
    blocks = jnp.pad(x, (0, m - n)).reshape(-1, width).astype(jnp.float32)
    tri = jnp.triu(jnp.ones((width, width), jnp.float32))
    inner = jnp.dot(blocks, tri, precision=jax.lax.Precision.HIGHEST)
    inner = inner.astype(jnp.int32)
    last = inner[:, -1]
    return (inner + (_prefix(last) - last)[:, None]).reshape(-1)[:n]


def _shifted(x: jnp.ndarray, first) -> jnp.ndarray:
    """``x`` moved one place on, ``first`` in front."""
    return jnp.concatenate([jnp.full(1, first, x.dtype), x[:-1]])


@functools.partial(
    jax.jit, static_argnames=("chunk", "id_bits", "interpret")
)
def _hop_expand(layers, nf, src, groups, hop, ends, runs, *, chunk,
                id_bits, interpret=None):
    """Hop ``hop`` of the rows ``src`` int32[R] (SENTINEL rows are
    padding). ``groups`` int32[k, R * mf] holds each earlier hop's kept
    ids as sorted keys ``row << (id_bits + 1) | id << 1`` (SENTINEL
    pads); ``ends`` int32[parts * R * mf] is the running sum of the live
    segments' lengths, part-major, then the total repeated, and ``runs``
    int32[2, parts * R * mf] their source starts and tags (row * parts +
    part), as the host planned them. Returns (groups with group ``hop``
    filled, that group)."""
    from repro.kernels import ops as kops

    R = src.shape[0]
    k, width = groups.shape
    mf = width // R
    shift = id_bits + 1
    src_keys = jnp.where(
        src != SENTINEL, (jnp.arange(R, dtype=jnp.int32) << shift)
        | (src << 1), SENTINEL,
    )
    sources = tuple(csr.indices for csr, _, _ in _degree_parts(layers))
    total = ends[-1]

    # the source and the earlier hops' ids are visited (flag 0)
    g = jnp.arange(k, dtype=jnp.int32)[:, None]
    visited = jnp.concatenate([
        src_keys, jnp.where(g < hop - 1, groups, SENTINEL).reshape(-1),
    ])

    def chunk_of(lo, best):
        cand, row = kops.segment_copy(
            sources, ends, runs[0], runs[1], lo, chunk=chunk,
            interpret=interpret,
        )
        ok = cand != SENTINEL
        if nf is not None:
            ok = ok & jnp.take(nf, cand, mode="clip")
        cand_keys = jnp.where(ok, (row << shift) | (cand << 1) | 1, SENTINEL)

        # candidates (flag 1) after the visited ids (flag 0); the first
        # of a (row, id) that is a candidate is new to the row; then the
        # mf smallest new ids per row, as sorted keys
        keys = jax.lax.sort(
            jnp.concatenate([cand_keys, visited, best | 1]),
            is_stable=False,
        )
        new = ((keys >> 1) != _shifted(keys >> 1, -1)) \
            & ((keys & 1) == 1) & (keys != SENTINEL)
        before = _prefix(new.astype(jnp.int32)) - new
        # the count of new keys before each row's first key, spread over
        # the row by a running sum of its changes at the rows' starts
        row_at = jnp.searchsorted(
            keys, jnp.arange(R + 1, dtype=jnp.int32) << shift
        )
        at_start = before[jnp.minimum(row_at, keys.shape[0] - 1)]
        row_start = _prefix(jnp.zeros(keys.shape[0], jnp.int32).at[
            row_at[:R]
        ].add(at_start[:R] - _shifted(at_start[:R], 0), mode="drop"))
        kept = jnp.where(new & (before - row_start < mf), keys ^ 1, SENTINEL)
        return jax.lax.sort(kept, is_stable=False)[:width]

    _, best = jax.lax.while_loop(
        lambda state: state[0] < total,
        lambda state: (state[0] + chunk, chunk_of(*state)),
        (jnp.int32(0), jnp.full(width, SENTINEL, jnp.int32)),
    )
    return jax.lax.dynamic_update_index_in_dim(groups, best, hop - 1, 0), best


@functools.lru_cache(maxsize=32)
def _empty_groups(k: int, width: int) -> jnp.ndarray:
    return jnp.full((k, width), SENTINEL, jnp.int32)


def _khop_one_pass(net, layers, src, k, max_frontier, nf):
    """The one-pass k-hop over one-mode ``layers`` -> host (nodes, mask,
    hop_of_slot). Repeated sources are expanded once; each hop is one
    launch and one fetch, which the next hop's plan needs."""
    src = np.asarray(obs.fetch(src), np.int64).reshape(-1)
    uniq, inv = np.unique(src, return_inverse=True)
    # in blocks of as many rows as a key holds
    most = _most_rows(_id_bits(net))
    nodes = np.concatenate([
        _one_pass_rows(net, layers, uniq[lo : lo + most], k, max_frontier,
                       nf)
        for lo in range(0, max(uniq.size, 1), most)
    ])[inv]
    return nodes, nodes != SENTINEL, _hop_of_slot(k, max_frontier)


def _most_rows(id_bits: int) -> int:
    """Rows whose keys (row, id, flag) fit ``_KEY_BITS`` below SENTINEL
    (0 where not even one row's do)."""
    spare = _KEY_BITS - 1 - (id_bits + 1)
    return 1 << spare if spare >= 0 else 0


def _one_pass_rows(net, layers, uniq, k, mf, nf) -> np.ndarray:
    """The one-pass k-hop of distinct sources ``uniq`` -> host int32[len,
    1 + k * mf] (the ``nodes`` layout)."""
    from repro.kernels.segment_copy import BLOCK

    id_bits = _id_bits(net)
    parts = _degree_parts(layers)
    # plan inputs, as ``overlay.eff_host_degrees`` reads them
    host_parts = [
        (np.asarray(csr.indptr), csr.n_rows,
         None if dirty is None else np.asarray(dirty), take)
        for csr, dirty, take in parts
    ]
    # small layers take a smaller chunk, of whole segment-copy blocks
    nnz = sum(int(csr.indices.shape[0]) for csr, _, _ in parts)
    chunk = dispatch.pow2_ceil(min(nnz, HOP_CHUNK), floor=BLOCK)
    R = min(dispatch.pow2_ceil(
        uniq.size, floor=max(min(HOP_ROW_FLOOR, chunk // mf), 1)
    ), _most_rows(id_bits))
    # a source outside the node range expands to nothing, as a row read
    # past indptr's end is empty
    rows = np.full(R, SENTINEL, np.int32)
    rows[: uniq.size] = np.where(
        (uniq >= 0) & (uniq < net.n_nodes), uniq, SENTINEL
    )
    nodes = np.full((uniq.size, 1 + k * mf), SENTINEL, np.int32)
    nodes[:, 0] = uniq
    groups = _empty_groups(k, R * mf) if k else None
    nf_dev = None if nf is None else jnp.asarray(nf, bool)
    shift = id_bits + 1
    n_parts = len(parts)
    front = np.full(R * mf, SENTINEL, np.int32)
    front[:R] = rows
    front_row = np.arange(R * mf) % R
    # a row's visited ids: its source, then each hop's kept ids
    seen = np.ones(R, np.int64)
    for h in range(1, k + 1):
        with obs.span("threadle.dispatch.plan"):
            live = front != SENTINEL
            # rows are sorted: an id among a row's mf smallest new ones
            # lies within the first mf + seen of each row it is in, so an
            # unfiltered hop reads no further
            read = mf + seen[front_row] if nf is None else np.inf
            part_runs = [_part_runs(*part, front) for part in host_parts]
            lens = np.concatenate([
                np.where(live, np.minimum(deg, read), 0).astype(np.int64)
                for _, deg in part_runs
            ])
            seg = np.flatnonzero(lens)
            lens = lens[seg]
            total = int(lens.sum())
        if total == 0:
            break
        if total >= 2**31 - 2 * chunk:
            raise ValueError(f"hop {h} has {total} candidates; the one-pass "
                             f"hop takes fewer than 2**31")
        # the live segments, part-major: where their slots end (then the
        # total repeated), their source starts and their tags
        ends = np.full(R * mf * n_parts, total, np.int32)
        ends[: seg.size] = np.cumsum(lens)
        runs = np.zeros((2, R * mf * n_parts), np.int32)
        runs[0, : seg.size] = np.concatenate([st for st, _ in part_runs])[seg]
        runs[1, : seg.size] = (
            front_row[seg % (R * mf)] * n_parts + seg // (R * mf)
        )
        slots = -(-total // chunk) * chunk
        with obs.span("threadle.traversal.hop", hop=h, rows=R, slots=slots):
            groups, best = _hop_expand(
                tuple(layers), nf_dev, rows, groups, h, ends, runs=runs,
                chunk=chunk, id_bits=id_bits,
            )
            keys = obs.fetch(best, np.int32)
        obs.count("traversal.hops")
        obs.count("traversal.cand", total)
        obs.count("traversal.slots", slots)
        obs.count("traversal.launches")
        # one window copy per segment-copy block a segment's slots touch
        last = ends[: seg.size].astype(np.int64) - 1
        obs.count("traversal.copies", int(
            (last // BLOCK - (last + 1 - lens) // BLOCK + 1).sum()
        ))
        keys = keys[keys != SENTINEL]
        row = keys >> shift
        front = np.full(R * mf, SENTINEL, np.int32)
        front[: keys.size] = (keys >> 1) & ((1 << id_bits) - 1)
        front_row[: keys.size] = row
        seen += np.bincount(row, minlength=R)
        rank = np.arange(keys.size) - np.searchsorted(row, row)
        nodes[row, 1 + (h - 1) * mf + rank] = front[: keys.size]
    return nodes


# ---------------------------------------------------------------------------
# Padded frontier loop: two-mode layers and traced callers
# ---------------------------------------------------------------------------


def _khop_padded(
    net, src, k, max_frontier, max_alters_per_node, layer_names, nf,
    use_pallas, interpret,
):
    src = jnp.asarray(src, jnp.int32)
    B = src.shape[0]
    hop_of_slot = _hop_of_slot(k, max_frontier)
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
