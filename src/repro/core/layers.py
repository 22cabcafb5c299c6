"""Network layers: one-mode (unipartite) and two-mode (hyperedge) storage.

This is the paper's central design, adapted to dense arrays:

* ``LayerOneMode`` — per-node edge lists as CSR; configurable directionality,
  valuation, self-ties; inbound storage can be disabled (halves memory, for
  random-walker workloads — paper §3.2).
* ``LayerTwoMode`` — a set of hyperedges with a **dual index** (paper §3.3):
  node→memberships CSR and hyperedge→members CSR. Queries go through the
  *same interface* as one-mode layers (pseudo-projection): edge existence is
  "share ≥1 hyperedge", edge value is "count of shared hyperedges", alters
  are "union of co-members" — the projection is never materialized.

Both classes implement the ``check_edge / edge_value / node_alters /
sample_neighbor / degrees`` protocol (the paper's shared interface), so
multilayer operations never branch on mode at the call site.

All query methods are batched (arrays of node ids); scalar usage is just a
size-1 batch.
"""

from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp

from . import dispatch
from .pytree import pytree_dataclass, replace
from .csr import (
    CSR,
    SENTINEL,
    DtypePolicy,
    csr_empty,
    csr_from_coo_chunks,
    csr_transpose,
    sorted_isin,
)
from .overlay import (
    DeltaOverlay,
    eff_contains,
    eff_coo,
    eff_degrees,
    eff_host_degree_table,
    eff_max_degree,
    eff_n_rows,
    eff_nnz,
    eff_row_gather,
    eff_row_sample,
    eff_value_at,
    ov_buffers,
    overlay_ratio,
    overlay_update,
)

__all__ = [
    "LayerOneMode",
    "LayerTwoMode",
    "add_edges",
    "delete_edges",
    "compact_layer",
    "has_overlay",
    "layer_overlay_ratio",
    "one_mode_from_edges",
    "one_mode_from_edge_chunks",
    "two_mode_from_memberships",
    "two_mode_from_membership_chunks",
    "DEFAULT_COMPACT_RATIO",
]

# Compaction policy: fold the overlay into the base CSR once the delta
# grows past this fraction of the base nnz (and always on snapshot).
DEFAULT_COMPACT_RATIO = 0.25


def _ov_nbytes(ov: DeltaOverlay | None) -> int:
    if ov is None:
        return 0
    return ov.delta.nbytes + int(ov.dirty.nbytes)


# ---------------------------------------------------------------------------
# One-mode layers
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("max_alters",))
def _row_alters(csr, ov, u, node_filter, *, max_alters):
    """Padded neighbor rows, filtered -> (int32[B, max_alters], mask);
    one compiled program per batch shape."""
    vals, mask = eff_row_gather(csr, ov, u, max_alters)
    if node_filter is not None:
        mask = mask & jnp.take(jnp.asarray(node_filter), vals, mode="clip")
        vals = jnp.where(mask, vals, SENTINEL)
    return vals, mask


@pytree_dataclass(static=("directed", "valued", "allow_self", "store_inbound"))
class LayerOneMode:
    """Unipartite layer: CSR out-edges (+ optional CSR in-edges).

    Symmetric layers store each undirected edge in both rows (so ``out`` is
    its own transpose and ``in_`` is None). Directed layers keep a separate
    inbound CSR unless ``store_inbound=False`` (paper's memory switch).
    """

    out: CSR
    in_: CSR | None
    directed: bool
    valued: bool
    allow_self: bool
    store_inbound: bool
    out_ov: DeltaOverlay | None = None
    in_ov: DeltaOverlay | None = None

    # -- shared query interface (pseudo-projection-compatible) -------------

    @property
    def mode(self) -> int:
        return 1

    @property
    def n_nodes(self) -> int:
        return self.out.n_rows

    @property
    def n_edges(self) -> int:
        """Logical edge count (undirected edges counted once)."""
        nnz = eff_nnz(self.out, self.out_ov)
        return nnz if self.directed else nnz // 2

    def check_edge(
        self, u: jnp.ndarray, v: jnp.ndarray, node_filter=None
    ) -> jnp.ndarray:
        hit = eff_contains(self.out, self.out_ov, u, v)
        if node_filter is not None:
            hit = hit & jnp.take(jnp.asarray(node_filter), v, mode="clip")
        return hit

    def edge_value(
        self, u: jnp.ndarray, v: jnp.ndarray, node_filter=None
    ) -> jnp.ndarray:
        val = eff_value_at(self.out, self.out_ov, u, v)
        if node_filter is not None:
            val = jnp.where(
                jnp.take(jnp.asarray(node_filter), v, mode="clip"), val, 0.0
            )
        return val

    def node_alters(
        self, u: jnp.ndarray, max_alters: int, inbound: bool = False,
        node_filter=None,
    ) -> tuple[jnp.ndarray, jnp.ndarray]:
        """Padded outbound (or inbound) neighbor lists -> (int32[B,K], mask).

        ``node_filter`` (bool[n_nodes]) drops neighbors failing an
        attribute predicate (mask holes; ids replaced by SENTINEL).
        """
        csr, ov = self._in_pair() if inbound else (self.out, self.out_ov)
        return _row_alters(csr, ov, u, node_filter, max_alters=max_alters)

    def filtered_degree(self, u: jnp.ndarray, node_filter) -> jnp.ndarray:
        """Count of out-neighbors passing ``node_filter`` -> int32[B].

        Concrete batches run degree-bucketed (core/dispatch.py); traced
        batches use an O(nnz) per-node filtered-degree precompute (the
        overlay's dirty rows take the delta's precompute instead).
        """
        if dispatch.can_dispatch(
            u, node_filter, self.out.indptr, self.out.indices,
            *ov_buffers(self.out_ov),
        ):
            return dispatch.bucketed_filtered_degree(self, u, node_filter)
        nf = jnp.asarray(node_filter)

        def per_node_counts(csr):
            rows = jnp.searchsorted(
                csr.indptr,
                jnp.arange(csr.nnz, dtype=jnp.int32),
                side="right",
            ) - 1
            contrib = jnp.take(nf, csr.indices, mode="clip").astype(jnp.int32)
            return jnp.zeros((csr.n_rows,), jnp.int32).at[rows].add(contrib)

        per_node = per_node_counts(self.out)
        if self.out_ov is not None:
            per_node = jnp.where(
                self.out_ov.dirty, per_node_counts(self.out_ov.delta), per_node
            )
        return jnp.take(per_node, u, mode="clip")

    def sample_neighbor(
        self, u: jnp.ndarray, key: jax.Array
    ) -> tuple[jnp.ndarray, jnp.ndarray]:
        """Uniform random out-neighbor per query node (random walk step)."""
        return eff_row_sample(self.out, self.out_ov, u, key)

    def degrees(self) -> jnp.ndarray:
        return eff_degrees(self.out, self.out_ov)

    def max_degree(self) -> int:
        return eff_max_degree(self.out, self.out_ov)

    # -- misc ---------------------------------------------------------------

    def _in_pair(self) -> tuple[CSR, DeltaOverlay | None]:
        if not self.directed:
            return self.out, self.out_ov
        if self.in_ is None:
            raise ValueError(
                "inbound edges not stored (store_inbound=False); "
                "re-import the layer with inbound storage enabled"
            )
        return self.in_, self.in_ov

    def _in_csr(self) -> CSR:
        return self._in_pair()[0]

    @property
    def nbytes(self) -> int:
        n = self.out.nbytes + _ov_nbytes(self.out_ov)
        if self.in_ is not None:
            n += self.in_.nbytes + _ov_nbytes(self.in_ov)
        return n

    def drop_inbound(self) -> "LayerOneMode":
        """Paper §3.2: disable inbound storage, ~halving directed-layer memory."""
        return replace(self, in_=None, in_ov=None, store_inbound=False)


def one_mode_from_edges(
    n_nodes: int,
    src: np.ndarray,
    dst: np.ndarray,
    values: np.ndarray | None = None,
    directed: bool = False,
    allow_self: bool = False,
    store_inbound: bool = True,
    sum_duplicates: bool = False,
    policy: DtypePolicy | None = None,
) -> LayerOneMode:
    """Build a one-mode layer from an edge list (host-side)."""
    src = np.asarray(src)
    dst = np.asarray(dst)
    if src.shape != dst.shape:
        raise ValueError("src/dst length mismatch")
    if values is not None:
        values = np.asarray(values, dtype=np.float32)
    return one_mode_from_edge_chunks(
        n_nodes,
        [(src, dst, values)],
        directed=directed,
        allow_self=allow_self,
        store_inbound=store_inbound,
        sum_duplicates=sum_duplicates,
        valued=values is not None,
        policy=policy,
    )


def one_mode_from_edge_chunks(
    n_nodes: int,
    chunks,
    directed: bool = False,
    allow_self: bool = False,
    store_inbound: bool = True,
    sum_duplicates: bool = False,
    valued: bool = False,
    policy: DtypePolicy | None = None,
) -> LayerOneMode:
    """Streaming one-mode build from ``(src, dst[, values])`` chunk tuples.

    ``chunks`` may be an iterable of chunk tuples, or a zero-arg callable
    returning a fresh iterator (e.g. a file re-parse). Self-tie filtering
    and undirected mirroring happen per chunk, so peak host memory tracks
    the CSR under construction, not the raw edge list.

    Duplicate (u, v) pairs dedup to the FIRST arrival. For undirected
    builds from a re-iterable source (callable / list / tuple) the source
    is walked twice — every forward edge, then every mirror — so the
    arrival order (and thus which duplicate's value wins) is exactly the
    single-chunk order, independent of chunking. A one-shot iterator
    can't be rewound, so there the mirror of chunk k arrives before
    chunk k+1's forward edges — same edges, but a value conflict between
    a chunk-k (v, u) and a chunk-k+1 (u, v) resolves to chunk k's value.
    """

    def norm(ch):
        src, dst = np.asarray(ch[0]), np.asarray(ch[1])
        vals = ch[2] if len(ch) > 2 else None
        if vals is not None:
            vals = np.asarray(vals, dtype=np.float32)
        if not allow_self:
            keep = src != dst
            src, dst = src[keep], dst[keep]
            if vals is not None:
                vals = vals[keep]
        if valued and vals is None:
            vals = np.ones(src.shape, np.float32)
        return src, dst, vals

    factory = (
        chunks if callable(chunks)
        else (lambda: iter(chunks)) if isinstance(chunks, (list, tuple))
        else None
    )

    def gen():
        if directed:
            for ch in (factory() if factory else chunks):
                yield norm(ch)
        elif factory is not None:
            # two passes: all forward edges, then all mirrors — the
            # legacy concatenation order, so dedup picks the same
            # winners regardless of chunk boundaries
            for ch in factory():
                yield norm(ch)
            for ch in factory():
                src, dst, vals = norm(ch)
                yield (dst, src, vals)
        else:
            for ch in chunks:
                src, dst, vals = norm(ch)
                yield (src, dst, vals)
                yield (dst, src, vals)

    out = csr_from_coo_chunks(
        gen(), n_nodes, n_nodes,
        dedup=not sum_duplicates, sum_duplicates=sum_duplicates,
        valued=valued, policy=policy,
    )
    in_ = None
    if directed and store_inbound:
        in_ = csr_transpose(out, policy=policy)
    return LayerOneMode(
        out=out,
        in_=in_,
        directed=directed,
        valued=valued,
        allow_self=allow_self,
        store_inbound=store_inbound,
    )


# ---------------------------------------------------------------------------
# Two-mode layers (pseudo-projection)
# ---------------------------------------------------------------------------


@pytree_dataclass(static=("max_memberships", "max_hyperedge_size"))
class LayerTwoMode:
    """Bipartite/affiliation layer stored as hyperedge memberships.

    Dual index (paper §3.3):
      memb    : CSR node -> hyperedge ids   (N rows, H cols)
      members : CSR hyperedge -> node ids   (H rows, N cols)

    ``max_memberships`` / ``max_hyperedge_size`` are construction-time row
    maxima — the static padding bounds used by batched queries.
    """

    memb: CSR
    members: CSR
    max_memberships: int
    max_hyperedge_size: int
    memb_ov: DeltaOverlay | None = None
    members_ov: DeltaOverlay | None = None

    @property
    def mode(self) -> int:
        return 2

    @property
    def n_nodes(self) -> int:
        return self.memb.n_rows

    @property
    def n_hyperedges(self) -> int:
        return eff_n_rows(self.members, self.members_ov)

    @property
    def n_memberships(self) -> int:
        return eff_nnz(self.memb, self.memb_ov)

    @property
    def nbytes(self) -> int:
        return (
            self.memb.nbytes + self.members.nbytes
            + _ov_nbytes(self.memb_ov) + _ov_nbytes(self.members_ov)
        )

    # -- pseudo-projection queries (paper Listing 1, batched) ---------------

    def memberships(
        self, u: jnp.ndarray, max_len: int | None = None
    ) -> tuple[jnp.ndarray, jnp.ndarray]:
        k = self.max_memberships if max_len is None else max_len
        return eff_row_gather(self.memb, self.memb_ov, u, max(k, 1))

    def member_rows(
        self, he: jnp.ndarray, max_len: int | None = None
    ) -> tuple[jnp.ndarray, jnp.ndarray]:
        """Padded member lists per hyperedge id (overlay-merged gather)."""
        k = self.max_hyperedge_size if max_len is None else max_len
        return eff_row_gather(self.members, self.members_ov, he, max(k, 1))

    def check_edge(
        self, u: jnp.ndarray, v: jnp.ndarray, node_filter=None
    ) -> jnp.ndarray:
        """Pseudo-projected edge existence: do u and v share a hyperedge?"""
        return self.edge_value(u, v, node_filter=node_filter) > 0

    def edge_value(
        self, u: jnp.ndarray, v: jnp.ndarray, node_filter=None
    ) -> jnp.ndarray:
        """Pseudo-projected edge value: number of shared hyperedges (f32[B]).

        Concrete query batches go through the degree-bucketed dispatcher
        (core/dispatch.py); traced batches (inside a caller's jit) fall
        back to the global-max padded path below. Results are identical.

        ``node_filter`` restricts targets: pairs whose ``v`` fails the
        filter return 0 (and skip the bucketed work entirely).
        """
        if dispatch.can_dispatch(
            u, v, node_filter, self.memb.indptr, self.memb.indices,
            *ov_buffers(self.memb_ov), *ov_buffers(self.members_ov),
        ):
            return dispatch.bucketed_edge_value(
                self, u, v, node_filter=node_filter
            )
        return self.edge_value_padded(u, v, node_filter=node_filter)

    def edge_value_padded(
        self, u: jnp.ndarray, v: jnp.ndarray, node_filter=None
    ) -> jnp.ndarray:
        """Global-max-padded reference path (jit-compatible baseline)."""
        a, am = self.memberships(u)
        b, bm = self.memberships(v)
        hits = sorted_isin(a, am, b, bm)
        val = jnp.sum(hits, axis=-1).astype(jnp.float32)
        if node_filter is not None:
            val = jnp.where(
                jnp.take(jnp.asarray(node_filter), v, mode="clip"), val, 0.0
            )
        return val

    def node_alters(
        self, u: jnp.ndarray, max_alters: int, inbound: bool = False,
        node_filter=None,
    ) -> tuple[jnp.ndarray, jnp.ndarray]:
        """Pseudo-projected alters: union of co-members across u's hyperedges.

        Returns (int32[B, max_alters] sorted padded, mask). Concrete query
        batches run degree-bucketed (per-bucket two-hop gather widths +
        segmented-union dedup); traced batches use the global-max padded
        gather-cube + sort below. Results are identical.

        ``node_filter`` (bool[n_nodes]) keeps only alters passing an
        attribute predicate; the ``max_alters`` cap applies post-filter.
        """
        if dispatch.can_dispatch(
            u, node_filter, self.memb.indptr, self.memb.indices,
            self.members.indptr, self.members.indices,
            *ov_buffers(self.memb_ov), *ov_buffers(self.members_ov),
        ):
            return dispatch.bucketed_node_alters(
                self, u, max_alters, node_filter=node_filter
            )
        return self.node_alters_padded(u, max_alters, node_filter=node_filter)

    def node_alters_padded(
        self, u: jnp.ndarray, max_alters: int, node_filter=None
    ) -> tuple[jnp.ndarray, jnp.ndarray]:
        """Global-max-padded reference path: the union is computed over
        max_memberships × max_hyperedge_size gathered slots then deduped
        by sort — capped at ``max_alters`` outputs. Delegates to the one
        shared gather/union implementation (kernels/ops.py) so the
        bucketed-vs-padded parity contract has a single source of truth."""
        from repro.kernels import ops as kops

        nf = None if node_filter is None else jnp.asarray(node_filter)
        return kops.pseudo_node_alters(
            self, u, max_alters, node_filter=nf, use_pallas=False
        )

    def filtered_degree(self, u: jnp.ndarray, node_filter) -> jnp.ndarray:
        """Distinct co-members passing ``node_filter`` -> int32[B].

        This is the degree of u in the never-materialized projection
        restricted to the selection (≠ the unfiltered ``degrees()``, which
        counts memberships). Concrete batches run bucketed at exact
        per-bucket flat widths; traced batches count the padded path's
        mask at the layer-global flat width.
        """
        if dispatch.can_dispatch(
            u, node_filter, self.memb.indptr, self.memb.indices,
            self.members.indptr, self.members.indices,
            *ov_buffers(self.memb_ov), *ov_buffers(self.members_ov),
        ):
            return dispatch.bucketed_filtered_degree(self, u, node_filter)
        bound = max(self.max_memberships * self.max_hyperedge_size, 1)
        _, mask = self.node_alters_padded(u, bound, node_filter=node_filter)
        return jnp.sum(mask, axis=-1).astype(jnp.int32)

    def sample_neighbor(
        self, u: jnp.ndarray, key: jax.Array
    ) -> tuple[jnp.ndarray, jnp.ndarray]:
        """Pseudo-projected walk step without computing alters (DESIGN §4.3).

        Sample hyperedge h uniformly from u's memberships, then a member v of
        h uniformly. This draws from the projected neighborhood with weight
        ∝ Σ_{shared h} 1/k_h (Newman-style 1/size weighting) in O(1) — the
        projection is never formed. Self-draws (v == u) are resampled once,
        then kept as 'stay' if unlucky (documented bias ~1/k_h).
        """
        k1, k2, k3 = jax.random.split(key, 3)
        he, he_valid = eff_row_sample(self.memb, self.memb_ov, u, k1)
        v, m_valid = eff_row_sample(
            self.members, self.members_ov, jnp.where(he_valid, he, 0), k2
        )
        # one resample round for self-draws
        v2, _ = eff_row_sample(
            self.members, self.members_ov, jnp.where(he_valid, he, 0), k3
        )
        v = jnp.where(v == u, v2, v)
        valid = he_valid & m_valid
        return jnp.where(valid, v, u.astype(jnp.int32)), valid

    def degrees(self) -> jnp.ndarray:
        """Membership counts per node (bipartite degree, not projected)."""
        return eff_degrees(self.memb, self.memb_ov)

    def max_degree(self) -> int:
        return eff_max_degree(self.memb, self.memb_ov)

    def hyperedge_sizes(self) -> jnp.ndarray:
        return eff_degrees(self.members, self.members_ov)

    def equivalent_projected_edges(self) -> int:
        """Σ_h k_h(k_h−1)/2 — paper Eq. (1): size of the never-built projection.

        Computed from host-side indptr in int64 and summed into a Python
        int: a single >65k-member hyperedge already pushes k(k−1)/2 past
        int32, and paper-scale sums (8e12 at 20M nodes) would overflow
        any device-side int32 accumulation (jax x64 is disabled).
        """
        k = eff_host_degree_table(self.members, self.members_ov)
        return int(np.sum(k * (k - 1) // 2, dtype=np.int64))


def two_mode_from_memberships(
    n_nodes: int,
    n_hyperedges: int,
    node_ids: np.ndarray,
    hyperedge_ids: np.ndarray,
    policy: DtypePolicy | None = None,
) -> LayerTwoMode:
    """Build a two-mode layer from (node, hyperedge) membership pairs."""
    return two_mode_from_membership_chunks(
        n_nodes, n_hyperedges,
        [(np.asarray(node_ids), np.asarray(hyperedge_ids))],
        policy=policy,
    )


def two_mode_from_membership_chunks(
    n_nodes: int,
    n_hyperedges: int,
    chunks,
    policy: DtypePolicy | None = None,
) -> LayerTwoMode:
    """Streaming two-mode build from (node_ids, hyperedge_ids) chunk tuples.

    Both directions of the dual index come out DtypePolicy-narrowed; the
    transpose runs as a single counting-sort pass over the finished memb
    CSR, so peak memory never holds a third copy of the membership list.
    """
    memb = csr_from_coo_chunks(
        ((np.asarray(n), np.asarray(h)) for n, h in chunks),
        n_nodes, n_hyperedges, policy=policy,
    )
    members = csr_transpose(memb, policy=policy)
    return LayerTwoMode(
        memb=memb,
        members=members,
        max_memberships=max(memb.max_degree(), 1),
        max_hyperedge_size=max(members.max_degree(), 1),
    )


# ---------------------------------------------------------------------------
# Batched edge insert / delete (the WAL's incremental mutation ops)
# ---------------------------------------------------------------------------


def _csr_coo(
    csr: CSR, ov: DeltaOverlay | None = None
) -> tuple[np.ndarray, np.ndarray, np.ndarray | None]:
    """Expand a CSR (+ optional overlay) to host COO (rows, cols, values)."""
    return eff_coo(csr, ov)


def _one_mode_logical_edges(
    layer: LayerOneMode,
) -> tuple[np.ndarray, np.ndarray, np.ndarray | None]:
    """The layer's effective logical edge list (undirected edges once)."""
    rows, cols, vals = eff_coo(layer.out, layer.out_ov)
    if not layer.directed:
        keep = rows <= cols  # each undirected edge stored in both rows
        rows, cols = rows[keep], cols[keep]
        vals = None if vals is None else vals[keep]
    return rows, cols, vals


def has_overlay(layer) -> bool:
    """True when the layer carries uncompacted delta state."""
    if isinstance(layer, LayerTwoMode):
        return layer.memb_ov is not None or layer.members_ov is not None
    return layer.out_ov is not None or layer.in_ov is not None


def layer_overlay_ratio(layer) -> float:
    """Largest delta-to-base nnz ratio across the layer's overlays."""
    if isinstance(layer, LayerTwoMode):
        return max(
            overlay_ratio(layer.memb, layer.memb_ov),
            overlay_ratio(layer.members, layer.members_ov),
        )
    r = overlay_ratio(layer.out, layer.out_ov)
    if layer.in_ is not None:
        r = max(r, overlay_ratio(layer.in_, layer.in_ov))
    return r


def compact_layer(layer):
    """Fold the delta overlay into a fresh base CSR (bit-identical).

    The effective edge set goes back through the standard builders, so
    the result is exactly the layer a from-scratch construction of the
    same edges would produce — the overlay-vs-rebuild identity contract.
    """
    if not has_overlay(layer):
        return layer
    if isinstance(layer, LayerTwoMode):
        rows, cols, _ = eff_coo(layer.memb, layer.memb_ov)
        return two_mode_from_memberships(
            layer.n_nodes, layer.n_hyperedges, rows, cols
        )
    rows, cols, vals = _one_mode_logical_edges(layer)
    return one_mode_from_edges(
        layer.n_nodes,
        rows,
        cols,
        values=vals,
        directed=layer.directed,
        allow_self=layer.allow_self,
        store_inbound=layer.store_inbound,
    )


def _maybe_compact(layer, compact_ratio):
    if compact_ratio is not None and layer_overlay_ratio(layer) > compact_ratio:
        return compact_layer(layer)
    return layer


def add_edges(layer, src, dst, values=None, *,
              compact_ratio=DEFAULT_COMPACT_RATIO):
    """Batched edge insert -> new layer (functional; overlay fast path).

    One-mode layers take (src, dst[, values]) edge triples — an edge
    that already exists takes the NEW value when ``values`` is given,
    and KEEPS its stored value when ``values=None`` (new edges default
    to 1.0). Two-mode layers take (node, hyperedge) membership pairs;
    the hyperedge space grows if a new id exceeds it.

    The batch lands in the layer's delta overlay: only the touched rows
    are re-resolved, so cost is O(batch + touched-row content), not
    O(nnz). Queries merge the overlay at query time, bit-identical to a
    full rebuild; once the delta outgrows ``compact_ratio`` × base nnz
    the overlay is folded back into the base (``compact_ratio=0``
    forces an immediate rebuild, ``None`` never auto-compacts).
    """
    src = np.atleast_1d(np.asarray(src, dtype=np.int64))
    dst = np.atleast_1d(np.asarray(dst, dtype=np.int64))
    if src.shape != dst.shape:
        raise ValueError("src/dst length mismatch")
    if isinstance(layer, LayerTwoMode):
        if values is not None:
            raise ValueError("two-mode memberships carry no edge values")
        if src.size == 0:
            return layer
        n_hyper = max(layer.n_hyperedges, int(dst.max()) + 1)
        memb_ov = overlay_update(
            layer.memb, layer.memb_ov, src, dst, None, n_cols=n_hyper,
        )
        members_ov = overlay_update(
            layer.members, layer.members_ov, dst, src, None, n_rows=n_hyper,
        )
        new = replace(
            layer,
            memb_ov=memb_ov,
            members_ov=members_ov,
            max_memberships=max(eff_max_degree(layer.memb, memb_ov), 1),
            max_hyperedge_size=max(
                eff_max_degree(layer.members, members_ov), 1
            ),
        )
        return _maybe_compact(new, compact_ratio)
    if layer.valued:
        # values given: the batch goes FIRST, so the first-occurrence
        # dedup upserts the NEW value. values=None: existing content
        # goes first — an existing edge KEEPS its stored value and only
        # genuinely new edges get the 1.0 default.
        new_first = values is not None
        vals = (
            np.ones(src.shape, np.float32) if values is None
            else np.broadcast_to(
                np.asarray(values, dtype=np.float32), src.shape
            )
        )
    else:
        if values is not None:
            raise ValueError(
                "layer is unvalued; re-import it valued to carry values"
            )
        new_first = True
        vals = None
    if not layer.allow_self:
        keep = src != dst
        src, dst = src[keep], dst[keep]
        if vals is not None:
            vals = vals[keep]
    if src.size == 0:
        return layer
    if layer.directed:
        bs, bd, bv = src, dst, vals
    else:
        # canonicalize to (min, max) then mirror: both stored rows of an
        # undirected edge resolve to the same winning value, whichever
        # orientation the batch used
        lo, hi = np.minimum(src, dst), np.maximum(src, dst)
        bs, bd = np.concatenate([lo, hi]), np.concatenate([hi, lo])
        bv = None if vals is None else np.concatenate([vals, vals])
    out_ov = overlay_update(
        layer.out, layer.out_ov, bs, bd, bv,
        valued=layer.valued, new_first=new_first,
    )
    in_ov = layer.in_ov
    if layer.directed and layer.in_ is not None:
        in_ov = overlay_update(
            layer.in_, layer.in_ov, dst, src, vals,
            valued=layer.valued, new_first=new_first,
        )
    new = replace(layer, out_ov=out_ov, in_ov=in_ov)
    return _maybe_compact(new, compact_ratio)


def delete_edges(layer, src, dst, *, compact_ratio=DEFAULT_COMPACT_RATIO):
    """Batched edge delete -> new layer (missing pairs are ignored).

    One-mode undirected layers treat (u, v) and (v, u) as the same edge;
    two-mode layers delete (node, hyperedge) membership pairs. Deletes
    are tombstones in the delta overlay: the touched rows re-resolve
    without the named pairs, same compaction policy as ``add_edges``.
    """
    src = np.atleast_1d(np.asarray(src, dtype=np.int64))
    dst = np.atleast_1d(np.asarray(dst, dtype=np.int64))
    if src.shape != dst.shape:
        raise ValueError("src/dst length mismatch")
    if isinstance(layer, LayerTwoMode):
        ok = (
            (src >= 0) & (src < layer.n_nodes)
            & (dst >= 0) & (dst < layer.n_hyperedges)
        )
        src, dst = src[ok], dst[ok]
        if src.size == 0:
            return layer
        memb_ov = overlay_update(
            layer.memb, layer.memb_ov, src, dst, None, delete=True,
        )
        members_ov = overlay_update(
            layer.members, layer.members_ov, dst, src, None, delete=True,
        )
        new = replace(
            layer,
            memb_ov=memb_ov,
            members_ov=members_ov,
            max_memberships=max(eff_max_degree(layer.memb, memb_ov), 1),
            max_hyperedge_size=max(
                eff_max_degree(layer.members, members_ov), 1
            ),
        )
        return _maybe_compact(new, compact_ratio)
    n = layer.n_nodes
    ok = (src >= 0) & (src < n) & (dst >= 0) & (dst < n)
    src, dst = src[ok], dst[ok]
    if src.size == 0:
        return layer
    if layer.directed:
        bs, bd = src, dst
    else:
        bs, bd = np.concatenate([src, dst]), np.concatenate([dst, src])
    out_ov = overlay_update(
        layer.out, layer.out_ov, bs, bd, None,
        delete=True, valued=layer.valued,
    )
    in_ov = layer.in_ov
    if layer.directed and layer.in_ is not None:
        in_ov = overlay_update(
            layer.in_, layer.in_ov, dst, src, None,
            delete=True, valued=layer.valued,
        )
    new = replace(layer, out_ov=out_ov, in_ov=in_ov)
    return _maybe_compact(new, compact_ratio)


def two_mode_empty(n_nodes: int, n_hyperedges: int) -> LayerTwoMode:
    return LayerTwoMode(
        memb=csr_empty(n_nodes, n_hyperedges),
        members=csr_empty(n_hyperedges, n_nodes),
        max_memberships=1,
        max_hyperedge_size=1,
    )
