"""Device-sharded graph queries — removing the paper's single-machine limit.

The paper (§6) lists "single-machine architecture" as Threadle's main
limitation. This module shards a two-mode layer's node→membership CSR by
node range across the mesh's data axis and runs pseudo-projection queries
with an owner-computes pattern under ``shard_map``:

* each device holds the membership rows of its node range (balanced
  contiguous partition, re-indexed to local ids);
* a query batch (u[], v[]) is broadcast; every device answers the subset
  it owns for ``u`` via its local rows plus a *replicated* hyperedge→
  member index for the second hop (hyperedge directory ≪ membership data
  in the paper's regime: 10k hyperedges vs 400M memberships);
* results combine with a masked ``psum`` — one small collective per batch.

This is the engine-side analogue of the framework's DP sharding: storage
scales with devices, query latency stays one collective deep. Walk
batches route the same way (sample locally, psum-select by owner).

Two generations live here:

* ``ShardedTwoMode`` + ``make_sharded_edge_value`` / ``make_sharded_
  walk_step`` — the original shard_map kernels for ONE two-mode layer
  (kept as-is; the 8-device tests pin them).
* ``ShardedNetwork`` / ``shard_network`` — the full sharded query +
  traversal engine: every layer's CSR row-sliced by contiguous node
  ranges (global column ids, so no re-indexing on the query path),
  owner-routed ``edge_value`` / ``node_alters`` / ``degree`` point
  queries through the per-shard degree-bucketed dispatch, and khop /
  components with per-shard frontier expansion + a cross-shard
  frontier exchange between hops. Every result is bit-identical to
  the single-device path: per-row point queries run the same bucketed
  kernels on identical rows, the khop hop-union argument is the same
  one that justifies slot-chunking in ``traversal.khop_neighborhood``
  (the union of per-shard smallest new ids IS the hop's smallest
  ``max_frontier`` new ids), and components converge to the unique
  min-label fixed point regardless of sweep partitioning.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from typing import Sequence

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from . import dispatch
from .csr import CSR, SENTINEL, sorted_isin
from .layers import LayerOneMode, LayerTwoMode
from .network import Network, _as_batch
from .nodeset import node_filter_mask
from .overlay import (
    DeltaOverlay, eff_edge_stream, eff_host_degree_table, eff_nnz,
)
from .pytree import pytree_dataclass


@pytree_dataclass(static=("n_nodes", "n_shards", "rows_per_shard", "max_memberships"))
class ShardedTwoMode:
    """Node-range-sharded memberships + replicated member directory.

    memb_indptr  : int32[n_shards, rows_per_shard + 1] (local offsets)
    memb_indices : int32[n_shards, max_local_nnz] (hyperedge ids, padded)
    members      : replicated hyperedge->node CSR arrays
    """

    memb_indptr: jnp.ndarray
    memb_indices: jnp.ndarray
    members_indptr: jnp.ndarray
    members_indices: jnp.ndarray
    n_nodes: int
    n_shards: int
    rows_per_shard: int
    max_memberships: int


def shard_two_mode(layer: LayerTwoMode, n_shards: int) -> ShardedTwoMode:
    """Partition a LayerTwoMode by contiguous node ranges (host-side)."""
    n = layer.n_nodes
    rows = -(-n // n_shards)  # ceil
    indptr = np.asarray(layer.memb.indptr)
    indices = np.asarray(layer.memb.indices)

    local_ptrs, local_idx = [], []
    max_nnz = 0
    for s in range(n_shards):
        lo, hi = s * rows, min((s + 1) * rows, n)
        base = indptr[lo]
        ptr = indptr[lo : hi + 1] - base
        ptr = np.pad(ptr, (0, rows + 1 - len(ptr)), mode="edge")
        idx = indices[indptr[lo] : indptr[hi]]
        max_nnz = max(max_nnz, len(idx))
        local_ptrs.append(ptr)
        local_idx.append(idx)
    pad_idx = np.full((n_shards, max(max_nnz, 1)), SENTINEL, dtype=np.int32)
    for s, idx in enumerate(local_idx):
        pad_idx[s, : len(idx)] = idx

    return ShardedTwoMode(
        memb_indptr=jnp.asarray(np.stack(local_ptrs).astype(np.int32)),
        memb_indices=jnp.asarray(pad_idx),
        members_indptr=layer.members.indptr,
        members_indices=layer.members.indices,
        n_nodes=n,
        n_shards=n_shards,
        rows_per_shard=rows,
        max_memberships=layer.max_memberships,
    )


def _local_rows(indptr, indices, local_u, valid, k):
    """Gather up to k membership slots for local row ids (padded)."""
    start = jnp.take(indptr, jnp.clip(local_u, 0, indptr.shape[0] - 1))
    length = jnp.take(indptr, jnp.clip(local_u + 1, 0, indptr.shape[0] - 1)) - start
    offs = jnp.arange(k, dtype=jnp.int32)
    gather_at = start[:, None] + offs[None, :]
    ok = (offs[None, :] < length[:, None]) & valid[:, None]
    vals = jnp.take(indices, jnp.where(ok, gather_at, 0), mode="clip")
    return jnp.where(ok, vals, SENTINEL)


def make_sharded_edge_value(graph: ShardedTwoMode, mesh: Mesh, axis: str = "data"):
    """Build a jit'd batched pseudo-projection edge_value over the mesh.

    Returns fn(u int32[B], v int32[B]) -> f32[B]. Each device resolves the
    membership rows of nodes IT owns, for both endpoints; partial rows
    combine with a single psum (rows are disjoint across owners).
    """
    K = max(graph.max_memberships, 1)
    rows = graph.rows_per_shard

    def kernel(memb_indptr, memb_indices, u, v):
        # block-local shapes: memb_indptr (1, rows+1), memb_indices (1, nnz)
        memb_indptr = memb_indptr[0]
        memb_indices = memb_indices[0]
        shard_id = jax.lax.axis_index(axis)
        lo = shard_id * rows

        def owned_rows(nodes):
            local = nodes - lo
            mine = (local >= 0) & (local < rows)
            r = _local_rows(memb_indptr, memb_indices, local, mine, K)
            # psum assembles full rows: non-owners contribute SENTINEL→0
            contrib = jnp.where(r == SENTINEL, 0, r + 1)
            full = jax.lax.psum(contrib, axis)
            return jnp.where(full == 0, SENTINEL, full - 1)

        a = owned_rows(u)  # (B, K) hyperedge ids, SENTINEL-padded
        b = owned_rows(v)
        eq = (a[:, :, None] == b[:, None, :]) & (a != SENTINEL)[:, :, None]
        return jnp.sum(eq, axis=(1, 2)).astype(jnp.float32)

    fn = jax.shard_map(
        kernel,
        mesh=mesh,
        in_specs=(P(axis), P(axis), P(), P()),
        out_specs=P(),
        check_vma=False,
    )

    @jax.jit
    def edge_value(u, v):
        return fn(
            graph.memb_indptr, graph.memb_indices,
            u.astype(jnp.int32), v.astype(jnp.int32),
        )

    return edge_value


def make_sharded_walk_step(graph: ShardedTwoMode, mesh: Mesh, axis: str = "data"):
    """Owner-routed pseudo-projected walk step over the sharded graph.

    fn(u int32[B], key) -> int32[B]: the owner of each walker samples a
    hyperedge from its local membership row; the member hop uses the
    replicated directory; one psum routes results back.
    """
    rows = graph.rows_per_shard

    def kernel(memb_indptr, memb_indices, h_indptr, h_indices, u, seed):
        memb_indptr = memb_indptr[0]
        memb_indices = memb_indices[0]
        shard_id = jax.lax.axis_index(axis)
        lo = shard_id * rows
        local = u - lo
        mine = (local >= 0) & (local < rows)
        lc = jnp.clip(local, 0, rows - 1)
        start = jnp.take(memb_indptr, lc)
        length = jnp.take(memb_indptr, lc + 1) - start
        key = jax.random.fold_in(jax.random.PRNGKey(0), seed[0])
        key = jax.random.fold_in(key, shard_id)
        k1, k2 = jax.random.split(key)
        r1 = jax.random.randint(k1, u.shape, 0, jnp.maximum(length, 1))
        he = jnp.take(memb_indices, start + r1, mode="clip")
        # second hop through the replicated hyperedge directory
        hs = jnp.take(h_indptr, jnp.clip(he, 0, h_indptr.shape[0] - 2))
        hl = jnp.take(h_indptr, jnp.clip(he + 1, 0, h_indptr.shape[0] - 1)) - hs
        r2 = jax.random.randint(k2, u.shape, 0, jnp.maximum(hl, 1))
        nxt = jnp.take(h_indices, hs + r2, mode="clip")
        ok = mine & (length > 0) & (hl > 0)
        contrib = jnp.where(ok, nxt + 1, 0)
        combined = jax.lax.psum(contrib, axis)
        return jnp.where(combined == 0, u, combined - 1).astype(jnp.int32)

    fn = jax.shard_map(
        kernel,
        mesh=mesh,
        in_specs=(P(axis), P(axis), P(), P(), P(), P()),
        out_specs=P(),
        check_vma=False,
    )

    @jax.jit
    def walk_step(u, seed):
        return fn(
            graph.memb_indptr, graph.memb_indices,
            graph.members_indptr, graph.members_indices,
            u.astype(jnp.int32), jnp.asarray([seed], jnp.int32),
        )

    return walk_step


# ---------------------------------------------------------------------------
# ShardedNetwork: the full sharded query + traversal engine
# ---------------------------------------------------------------------------
#
# Layout: each shard s owns the contiguous node range [bounds[s],
# bounds[s+1]) and holds, per layer, a ROW-SLICED CSR — the indptr is
# clamped so rows outside the range are empty, the indices keep their
# GLOBAL column ids (no re-indexing), and the full row space is
# preserved. An owned row is therefore byte-identical to the same row
# in the unsharded layer, so the degree-bucketed dispatch runs on a
# shard completely unchanged and per-row results are bit-identical by
# construction. Two-mode layers replicate the hyperedge->member
# directory (directory << membership data in the paper's regime) and
# recompute the LOCAL max_memberships, which shrinks per-shard pad
# widths without changing results.
#
# Cross-shard exchange is host-mediated: per-shard partial results are
# pulled to host and combined there (scatter-back for point queries,
# sorted union for khop frontiers, elementwise min for component
# labels). With multiple local devices each shard's arrays are placed
# on its own device, so per-shard dispatches overlap across a thread
# pool; with one device the same code path still wins on hub-skewed
# graphs because each shard's hop expansion pays its OWN alter bound
# rather than the global hub bound (see sharded khop below).

_POOL: ThreadPoolExecutor | None = None


def _shard_pool() -> ThreadPoolExecutor:
    # one process-wide pool shared by every ShardedNetwork (engines
    # rebuild sharded views on mutation; per-instance pools would leak
    # a thread set per rebuild)
    global _POOL
    if _POOL is None:
        _POOL = ThreadPoolExecutor(
            max_workers=min(16, (os.cpu_count() or 4)),
            thread_name_prefix="shard-query",
        )
    return _POOL


def _smap(fn, items: list):
    """Map over per-shard work items, threaded when there are several.

    jax releases the GIL during device execution, so per-shard
    dispatches overlap; host-side planning interleaves.
    """
    if len(items) <= 1:
        return [fn(x) for x in items]
    return list(_shard_pool().map(fn, items))


def _slice_csr_rows(csr: CSR, lo: int, hi: int) -> CSR:
    """Row-range restriction: rows outside [lo, hi) become empty.

    new_indptr[i] = clip(indptr[i], indptr[lo], indptr[hi]) - indptr[lo]
    keeps the full row space (n_rows unchanged) while the indices /
    values arrays shrink to the owned rows' nnz. Owned rows are
    byte-identical to the source CSR's.
    """
    indptr = np.asarray(csr.indptr)
    base, top = int(indptr[lo]), int(indptr[hi])
    new_ptr = (np.clip(indptr.astype(np.int64), base, top) - base).astype(
        indptr.dtype
    )
    return CSR(
        indptr=jnp.asarray(new_ptr),
        indices=csr.indices[base:top],
        values=None if csr.values is None else csr.values[base:top],
        n_rows=csr.n_rows,
        n_cols=csr.n_cols,
    )


def _slice_overlay(
    ov: DeltaOverlay | None, base_slice: CSR, lo: int, hi: int,
) -> DeltaOverlay | None:
    """Row-range restriction of a delta overlay.

    The delta CSR slices exactly like a base CSR (full row space kept,
    owned rows byte-identical). The dirty mask stays whole — a dirty
    row outside [lo, hi) selects an EMPTY delta row over an equally
    empty sliced-base row, so non-owned rows still resolve empty.
    ``base_shadowed`` is recomputed against the sliced base so the
    shard's effective-nnz accounting covers owned rows only.
    """
    if ov is None:
        return None
    delta = _slice_csr_rows(ov.delta, lo, hi)
    bdeg = np.diff(np.asarray(base_slice.indptr).astype(np.int64))
    dirty_np = np.asarray(ov.dirty)[: base_slice.n_rows]
    return DeltaOverlay(
        delta=delta,
        dirty=ov.dirty,
        base_shadowed=int(bdeg[dirty_np].sum()),
    )


def _slice_layer(layer, lo: int, hi: int):
    """One shard's view of a layer: owned rows only, global column ids."""
    if isinstance(layer, LayerTwoMode):
        memb = _slice_csr_rows(layer.memb, lo, hi)
        deg = eff_host_degree_table(layer.memb, layer.memb_ov)[lo:hi]
        mm = int(deg.max()) if deg.size else 0
        return LayerTwoMode(
            memb=memb,
            members=layer.members,  # replicated hyperedge directory
            memb_ov=_slice_overlay(layer.memb_ov, memb, lo, hi),
            members_ov=layer.members_ov,
            max_memberships=max(mm, 1),
            max_hyperedge_size=layer.max_hyperedge_size,
        )
    out = _slice_csr_rows(layer.out, lo, hi)
    in_ = None if layer.in_ is None else _slice_csr_rows(layer.in_, lo, hi)
    return LayerOneMode(
        out=out,
        in_=in_,
        out_ov=_slice_overlay(layer.out_ov, out, lo, hi),
        in_ov=(
            None if layer.in_ov is None
            else _slice_overlay(layer.in_ov, in_, lo, hi)
        ),
        directed=layer.directed,
        valued=layer.valued,
        allow_self=layer.allow_self,
        store_inbound=layer.store_inbound,
    )


def _owned(ids: np.ndarray, idx: np.ndarray) -> jnp.ndarray:
    """A shard's sub-batch ``ids[idx]`` padded to a power-of-two length;
    callers keep the first ``idx.size`` rows of each answer."""
    return jnp.asarray(dispatch.pow2_pad(ids[idx]), jnp.int32)


@jax.jit
def _member_gather(layer, ids):
    return layer.memberships(ids)


@jax.jit
def _shared_count(a, am, b, bm):
    """Shared hyperedges per row of two gathered membership batches."""
    return jnp.sum(sorted_isin(a, am, b, bm), axis=-1).astype(jnp.float32)


class ShardedNetwork:
    """Per-shard row-sliced layer views + the owner-routing query engine.

    Implements the Network query protocol (``edge_value`` /
    ``check_edge_any`` / ``node_alters`` / ``degree`` / ``khop`` /
    ``components``) with results bit-identical to ``source``'s
    single-device paths, so the serve engine's executors and
    ``api.runquery`` take either interchangeably. Traced inputs fall
    back to ``source`` (owner routing needs concrete ids). ``source``
    stays resident for walk fleets (batch-coupled RNG cannot shard
    bit-identically) and layer/nodeset metadata.
    """

    def __init__(self, source: Network, shards: tuple, bounds: np.ndarray):
        self.source = source
        self.shards = tuple(shards)
        self.bounds = np.asarray(bounds, dtype=np.int64)
        self.n_shards = len(self.shards)

    # -- container parity ----------------------------------------------------

    @property
    def n_nodes(self) -> int:
        return self.source.n_nodes

    @property
    def nodeset(self):
        return self.source.nodeset

    @property
    def layer_names(self) -> tuple[str, ...]:
        return self.source.layer_names

    def layer(self, name: str):
        return self.source.layer(name)

    def _select(self, layer_names):
        return self.source._select(layer_names)

    @property
    def nbytes(self) -> int:
        return sum(
            sum(l.nbytes for l in sh.layers) for sh in self.shards
        ) + self.source.nodeset.nbytes

    def shard_of(self, ids: np.ndarray) -> np.ndarray:
        """Owning shard per node id (contiguous-range partition)."""
        own = np.searchsorted(self.bounds, ids, side="right") - 1
        return np.clip(own, 0, self.n_shards - 1)

    def _partition(self, ids: np.ndarray) -> list[tuple[int, np.ndarray]]:
        """[(shard, positions-into-ids)] for the shards that own any."""
        own = self.shard_of(ids)
        return [
            (s, np.nonzero(own == s)[0])
            for s in range(self.n_shards)
            if (own == s).any()
        ]

    # -- owner-routed point queries ------------------------------------------

    def edge_value(self, layer_name: str, u, v, node_filter=None):
        """Batched edge value, routed to owning shards.

        One-mode rows live wholly on owner(u), so pairs route there and
        run the shard's bucketed kernel on identical rows. Two-mode
        pairs may STRADDLE shards: each endpoint's membership row is
        gathered from its owner and the shared-hyperedge count is
        computed at the coordinator by sorted intersection — the same
        integer every single-device path produces.
        """
        u, v = _as_batch(u), _as_batch(v)
        nf = node_filter_mask(node_filter, self.n_nodes)
        layer = self.source.layer(layer_name)
        if not dispatch.can_dispatch(u, v, nf):
            return self.source.edge_value(
                layer_name, u, v, node_filter=nf
            )
        un = np.asarray(u, np.int64)
        vn = np.asarray(v, np.int64)
        if isinstance(layer, LayerTwoMode):
            a, am = self._member_rows(layer_name, un)
            b, bm = self._member_rows(layer_name, vn)
            val = _shared_count(
                jnp.asarray(a), jnp.asarray(am),
                jnp.asarray(b), jnp.asarray(bm),
            )
            if nf is not None:
                val = jnp.where(
                    jnp.take(jnp.asarray(nf), v, mode="clip"), val, 0.0
                )
            return val
        out = np.zeros(un.shape[0], np.float32)

        def run(part):
            s, idx = part
            vals = self.shards[s].layer(layer_name).edge_value(
                _owned(un, idx), _owned(vn, idx), node_filter=nf,
            )
            return idx, np.asarray(vals)[: idx.size]

        for idx, vals in _smap(run, self._partition(un)):
            out[idx] = vals
        return jnp.asarray(out)

    def _member_rows(self, layer_name: str, ids: np.ndarray):
        """Gather membership rows from owners, padded to a common width."""
        parts = []

        def run(part):
            s, idx = part
            lay = self.shards[s].layer(layer_name)
            a, m = _member_gather(lay, _owned(ids, idx))
            return idx, np.asarray(a)[: idx.size], np.asarray(m)[: idx.size]

        parts = _smap(run, self._partition(ids))
        K = max([p[1].shape[1] for p in parts] or [1])
        A = np.full((ids.shape[0], K), int(SENTINEL), np.int32)
        M = np.zeros((ids.shape[0], K), bool)
        for idx, a, m in parts:
            A[idx, : a.shape[1]] = a
            M[idx, : m.shape[1]] = m
        return A, M

    def check_edge_any(self, u, v, layer_names=None, node_filter=None):
        """OR across selected layers (Network.check_edge_any parity)."""
        u, v = _as_batch(u), _as_batch(v)
        nf = node_filter_mask(node_filter, self.n_nodes)
        if not dispatch.can_dispatch(u, v, nf):
            return self.source.check_edge_any(
                u, v, layer_names, node_filter=nf
            )
        names = (
            self.layer_names if layer_names is None else tuple(layer_names)
        )
        un = np.asarray(u, np.int64)
        vn = np.asarray(v, np.int64)
        out = np.zeros(un.shape[0], bool)
        for name in names:
            layer = self.source.layer(name)
            if isinstance(layer, LayerTwoMode):
                out |= np.asarray(
                    self.edge_value(name, u, v, node_filter=nf)
                ) > 0
                continue

            def run(part, name=name):
                s, idx = part
                hit = self.shards[s].layer(name).check_edge(
                    _owned(un, idx), _owned(vn, idx), node_filter=nf,
                )
                return idx, np.asarray(hit)[: idx.size]

            for idx, hit in _smap(run, self._partition(un)):
                out[idx] |= hit
        return jnp.asarray(out)

    def node_alters(self, u, max_alters: int, layer_names=None,
                    node_filter=None):
        """Owner-routed multilayer alters union -> (vals, mask).

        Rows are row-independent, so each shard answers the queried
        nodes it owns through its own bucketed dispatch and results
        scatter back — per-row bit-identical to the unsharded call.
        """
        u = _as_batch(u)
        nf = node_filter_mask(node_filter, self.n_nodes)
        if not dispatch.can_dispatch(u, nf):
            return self.source.node_alters(
                u, max_alters, layer_names, node_filter=nf
            )
        un = np.asarray(u, np.int64)
        vals = np.full((un.shape[0], max_alters), int(SENTINEL), np.int32)
        mask = np.zeros((un.shape[0], max_alters), bool)

        def run(part):
            s, idx = part
            a, m = self.shards[s].node_alters(
                _owned(un, idx), max_alters, layer_names, node_filter=nf,
            )
            return idx, np.asarray(a)[: idx.size], np.asarray(m)[: idx.size]

        for idx, a, m in _smap(run, self._partition(un)):
            vals[idx] = a
            mask[idx] = m
        return jnp.asarray(vals), jnp.asarray(mask)

    def degree(self, u, layer_names=None, node_filter=None):
        """Owner-routed summed per-layer degree (Network.degree parity)."""
        u = _as_batch(u)
        nf = node_filter_mask(node_filter, self.n_nodes)
        if not dispatch.can_dispatch(u, nf):
            return self.source.degree(u, layer_names, node_filter=nf)
        un = np.asarray(u, np.int64)
        out = np.zeros(un.shape[0], np.int32)

        def run(part):
            s, idx = part
            d = self.shards[s].degree(
                _owned(un, idx), layer_names, node_filter=nf,
            )
            return idx, np.asarray(d)[: idx.size]

        for idx, d in _smap(run, self._partition(un)):
            out[idx] = d
        return jnp.asarray(out)

    # -- sharded traversal ---------------------------------------------------

    def khop(self, sources, k: int, *, max_frontier: int | None = None,
             max_alters_per_node: int | None = None, layer_names=None,
             node_filter=None, use_pallas: bool | None = None,
             interpret: bool | None = None):
        return sharded_khop(
            self, sources, k, max_frontier=max_frontier,
            max_alters_per_node=max_alters_per_node,
            layer_names=layer_names, node_filter=node_filter,
            use_pallas=use_pallas, interpret=interpret,
        )

    def components(self, layer_names=None, node_filter=None,
                   max_sweeps: int | None = None):
        return sharded_components(
            self, layer_names=layer_names, node_filter=node_filter,
            max_sweeps=max_sweeps,
        )


def shard_network(
    net: Network, n_shards: int, devices: Sequence | None = None,
) -> ShardedNetwork:
    """Partition every layer of ``net`` by contiguous node ranges.

    ``devices=None`` places shard s on ``jax.local_devices()[s % D]``
    when more than one local device exists (the 8-device CPU mesh the
    distributed tests force), and skips placement on a single device.
    Pass an explicit device list to pin, or ``devices=()`` to disable.
    """
    n_shards = int(n_shards)
    if n_shards < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    n = net.n_nodes
    n_shards = min(n_shards, max(n, 1))
    bounds = np.array(
        [(n * s) // n_shards for s in range(n_shards + 1)], np.int64
    )
    if devices is None:
        devs = jax.local_devices()
        devices = devs if len(devs) > 1 else ()
    shards = []
    for s in range(n_shards):
        lo, hi = int(bounds[s]), int(bounds[s + 1])
        sub = Network(
            nodeset=net.nodeset,
            layers=tuple(_slice_layer(l, lo, hi) for l in net.layers),
            layer_names=net.layer_names,
        )
        if len(devices):
            sub = jax.device_put(sub, devices[s % len(devices)])
        shards.append(sub)
    return ShardedNetwork(net, tuple(shards), bounds)


def _base_csrs(layer) -> tuple:
    if isinstance(layer, LayerTwoMode):
        return (layer.memb, layer.members)
    return (layer.out, layer.in_)


def reshard_deltas(
    snet: ShardedNetwork, new_net: Network,
) -> ShardedNetwork | None:
    """Cheap re-shard when only delta overlays changed.

    Overlay-only mutation keeps every base CSR object-identical, so the
    shards' row-sliced bases are still valid — only the O(delta)
    overlay slices need recomputing. Returns ``None`` when anything
    other than overlays changed (compaction, nodeset growth, layer set
    changes), signalling the caller to fall back to ``shard_network``.
    """
    old = snet.source
    if new_net is old:
        return snet
    if (
        new_net.nodeset is not old.nodeset
        or new_net.layer_names != old.layer_names
        or len(new_net.layers) != len(old.layers)
    ):
        return None
    for nl, ol in zip(new_net.layers, old.layers):
        if type(nl) is not type(ol):
            return None
        if any(a is not b for a, b in zip(_base_csrs(nl), _base_csrs(ol))):
            return None

    shards = []
    for s in range(snet.n_shards):
        lo, hi = int(snet.bounds[s]), int(snet.bounds[s + 1])
        old_sub = snet.shards[s]
        layers = []
        for nl, ol, osl in zip(new_net.layers, old.layers, old_sub.layers):
            if nl is ol:
                layers.append(osl)  # untouched layer: shard view reused
            elif isinstance(nl, LayerTwoMode):
                deg = eff_host_degree_table(nl.memb, nl.memb_ov)[lo:hi]
                mm = int(deg.max()) if deg.size else 0
                layers.append(LayerTwoMode(
                    memb=osl.memb,
                    members=nl.members,
                    memb_ov=_slice_overlay(nl.memb_ov, osl.memb, lo, hi),
                    members_ov=nl.members_ov,
                    max_memberships=max(mm, 1),
                    max_hyperedge_size=nl.max_hyperedge_size,
                ))
            else:
                layers.append(LayerOneMode(
                    out=osl.out,
                    in_=osl.in_,
                    out_ov=_slice_overlay(nl.out_ov, osl.out, lo, hi),
                    in_ov=(
                        None if nl.in_ov is None
                        else _slice_overlay(nl.in_ov, osl.in_, lo, hi)
                    ),
                    directed=nl.directed,
                    valued=nl.valued,
                    allow_self=nl.allow_self,
                    store_inbound=nl.store_inbound,
                ))
        shards.append(Network(
            nodeset=new_net.nodeset,
            layers=tuple(layers),
            layer_names=new_net.layer_names,
        ))
    return ShardedNetwork(new_net, tuple(shards), snet.bounds)


def sharded_khop(
    snet: ShardedNetwork,
    sources,
    k: int,
    *,
    max_frontier: int | None = None,
    max_alters_per_node: int | None = None,
    layer_names=None,
    node_filter=None,
    use_pallas: bool | None = None,
    interpret: bool | None = None,
):
    """Per-shard frontier expansion with a cross-shard hop exchange.

    Mirrors ``traversal.khop_neighborhood`` hop for hop. Frontier rows
    are sorted with SENTINEL pads, and shard ranges are contiguous, so
    each row's shard-s nodes form one contiguous segment (found by two
    vectorized rank counts — the "shard map" lookup). Per hop, each
    shard compacts its owned frontier segment, expands it through its
    OWN bucketed dispatch under its OWN exact alter bound, and compacts
    candidates against the hop's shared visited set; the per-shard
    partial frontiers then merge through ``union_rows`` — the halo/
    frontier exchange.

    Bit-identity: a per-shard compact keeps its partial's smallest new
    ids, and the union of per-shard smallest ids IS the hop's smallest
    ``max_frontier`` new ids — the same argument that justifies slot-
    chunking inside the single-device loop, with shard segments as the
    chunks. Beyond device parallelism this is an algorithmic win on
    hub-skewed graphs: the hop cost is B·Σ_s F_s·cap_s (each shard pays
    its local alter bound) instead of B·F·cap_global (every slot paying
    the hub's bound).
    """
    from repro.kernels import ops as kops
    from .csr import on_tpu as _on_tpu
    from .traversal import (
        DEFAULT_MAX_FRONTIER, MAX_CAND_FLAT, _frontier_alters, hop_width,
    )

    if k < 0:
        raise ValueError(f"k must be >= 0, got {k}")
    src = jnp.asarray(sources, dtype=jnp.int32)
    if src.ndim == 0:
        src = src[None]
    if src.ndim != 1:
        raise ValueError(f"sources must be a vector, got shape {src.shape}")
    if not dispatch.can_dispatch(src):
        # owner routing needs concrete ids; traced callers take the
        # single-device path (same results by the bit-identity contract)
        return snet.source.khop(
            src, k, max_frontier=max_frontier,
            max_alters_per_node=max_alters_per_node,
            layer_names=layer_names, node_filter=node_filter,
        )
    B = src.shape[0]
    n = snet.n_nodes
    nf = node_filter_mask(node_filter, n)
    if max_frontier is None:
        max_frontier = min(n, DEFAULT_MAX_FRONTIER)
    max_frontier = max(int(max_frontier), 1)

    hop_of_slot = np.concatenate(
        [np.zeros(1, np.int32)]
        + [np.full(max_frontier, h, np.int32) for h in range(1, k + 1)]
    )

    visited = src[:, None]
    frontier = src[:, None]
    groups = [src[:, None]]
    masks = [jnp.ones((B, 1), bool)]
    done_at = k
    rows_b = np.arange(B)[:, None]
    for h in range(1, k + 1):
        f_np = np.asarray(frontier)
        F = f_np.shape[1]
        visited_hop = jnp.sort(visited, axis=-1)

        # carve each row's owned segment per shard: rows are sorted with
        # SENTINEL (> any node id) padding, so entries in [lo, hi) sit at
        # positions [rank(lo), rank(hi)) — two counts per row, no sort
        tasks = []
        for s in range(snet.n_shards):
            lo, hi = int(snet.bounds[s]), int(snet.bounds[s + 1])
            left = (f_np < lo).sum(axis=1)
            right = (f_np < hi).sum(axis=1)
            widths = right - left
            fs_w = int(widths.max())
            if fs_w == 0:
                continue
            Fs = dispatch.pow2_ceil(fs_w, floor=1)  # compile-count stability
            cols = left[:, None] + np.arange(Fs)[None, :]
            valid = np.arange(Fs)[None, :] < widths[:, None]
            seg = np.where(
                valid, f_np[rows_b, np.minimum(cols, F - 1)], int(SENTINEL)
            ).astype(np.int32)
            tasks.append((s, seg))

        def expand(task):
            s, seg = task
            shard = snet.shards[s]
            if max_alters_per_node is not None:
                cap = max(int(max_alters_per_node), 1)
            else:
                real = np.unique(seg[seg != int(SENTINEL)].astype(np.int64))
                cap = hop_width(dispatch.alters_bound(
                    shard._select(layer_names), real, n
                ))
            Fs = seg.shape[1]
            step = max(1, min(Fs, MAX_CAND_FLAT // cap))
            seg_j = jnp.asarray(seg)
            parts, pmasks = [], []
            for lo2 in range(0, Fs, step):
                cand = _frontier_alters(
                    shard, seg_j[:, lo2 : lo2 + step], layer_names, nf, cap
                )
                pallas_here = (
                    use_pallas
                    if use_pallas is not None
                    else (
                        _on_tpu()
                        and cand.shape[-1] <= dispatch.UNION_PALLAS_MAX_FLAT
                    )
                )
                pv, pm = kops.frontier_compact(
                    cand, visited_hop, max_frontier,
                    use_pallas=pallas_here, interpret=interpret,
                    visited_sorted=True,
                )
                parts.append(pv)
                pmasks.append(pm)
            if len(parts) > 1:
                pv, pm = dispatch.union_rows(
                    jnp.concatenate(parts, axis=-1),
                    jnp.concatenate(pmasks, axis=-1),
                    max_frontier,
                    use_pallas=use_pallas, interpret=interpret,
                )
            else:
                pv, pm = parts[0], pmasks[0]
            # host pull = the frontier exchange (shards may sit on
            # different devices; the union below runs at the coordinator)
            return np.asarray(pv), np.asarray(pm)

        partials = _smap(expand, tasks)
        if not partials:
            frontier = jnp.full((B, max_frontier), SENTINEL, jnp.int32)
            fmask = jnp.zeros((B, max_frontier), bool)
        elif len(partials) == 1:
            frontier = jnp.asarray(partials[0][0])
            fmask = jnp.asarray(partials[0][1])
        else:
            frontier, fmask = dispatch.union_rows(
                jnp.asarray(np.concatenate([p[0] for p in partials], axis=1)),
                jnp.asarray(np.concatenate([p[1] for p in partials], axis=1)),
                max_frontier,
                use_pallas=use_pallas, interpret=interpret,
            )
        groups.append(frontier)
        masks.append(fmask)
        visited = jnp.concatenate([visited, frontier], axis=-1)
        if not bool(jnp.any(fmask)):
            done_at = h
            break
    pad = (k - done_at) * max_frontier
    nodes = jnp.concatenate(groups, axis=-1)
    mask = jnp.concatenate(masks, axis=-1)
    if pad:
        nodes = jnp.pad(nodes, ((0, 0), (0, pad)), constant_values=SENTINEL)
        mask = jnp.pad(mask, ((0, 0), (0, pad)), constant_values=False)
    return nodes, mask, jnp.asarray(hop_of_slot)


def sharded_components(
    snet: ShardedNetwork,
    layer_names=None,
    node_filter=None,
    max_sweeps: int | None = None,
):
    """Connected components over the sharded views -> int32[n] labels.

    Each round runs one min-label sweep PER SHARD over its owned rows
    (two-mode sweeps go through the replicated hyperedge directory),
    min-combines the per-shard proposals at the coordinator, applies
    one pointer-jumping pass, and repeats to the fixed point. The
    converged labeling (min node id per component; filtered-out nodes
    keep their own id) is the unique fixed point of min-label
    propagation, so it is bit-identical to ``components_batched``
    regardless of how sweeps were partitioned or ordered.
    """
    from .traversal import _INF

    n = snet.n_nodes
    nf = node_filter_mask(node_filter, n)
    nfj = None if nf is None else jnp.asarray(nf)

    shard_prep = []
    for shard in snet.shards:
        prep = []
        for layer in shard._select(layer_names):
            if isinstance(layer, LayerTwoMode):
                if eff_nnz(layer.memb, layer.memb_ov):
                    mrows, mcols = eff_edge_stream(layer.memb, layer.memb_ov)
                    hrows, hcols = eff_edge_stream(
                        layer.members, layer.members_ov
                    )
                    prep.append(
                        (layer.n_hyperedges, mrows, mcols, hrows, hcols)
                    )
            elif eff_nnz(layer.out, layer.out_ov):
                rows, cols = eff_edge_stream(layer.out, layer.out_ov)
                prep.append((None, rows, cols, None, None))
        if prep:
            shard_prep.append(prep)

    labels = jnp.arange(n, dtype=jnp.int32)
    if not shard_prep:
        return labels

    def sweep(prep, labels):
        # one shard's propagation pass — the traversal.components_batched
        # sweep body over this shard's effective edge streams
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
        return labels

    limit = n if max_sweeps is None else int(max_sweeps)
    lab_np = np.asarray(labels)
    for _ in range(max(limit, 1)):
        cur = jnp.asarray(lab_np)
        parts = _smap(lambda p: np.asarray(sweep(p, cur)), shard_prep)
        new_np = lab_np
        for p in parts:  # coordinator min-combine (host exchange)
            new_np = np.minimum(new_np, p)
        jumped = jnp.asarray(new_np)
        jumped = jnp.minimum(jumped, jnp.take(jumped, jumped))
        new_np = np.asarray(jumped)
        if np.array_equal(new_np, lab_np):
            break
        lab_np = new_np
    return jnp.asarray(lab_np)
