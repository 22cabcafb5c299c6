"""kron22: a Graph500 Kronecker graph, generated from the seed.

``generate`` is the Graph500 specification's Kronecker generator
(section 3, v3.0): ``edge_factor * 2**scale`` edges, each placed by
``scale`` independent choices of a quadrant of the adjacency matrix with
probabilities A, B, C, D, then every vertex label permuted at random.
Self-loops and repeated edges stay in the raw list, as the generator
makes them; the graph is undirected, so the reference and the program's
builder drop both. The specification also shuffles the edge list, which
changes no edge of the graph, and is left out. Edges are drawn in chunks,
each from its own stream of the seed, on threads (numpy's draws and
arithmetic release the interpreter lock).

The plain reference is ``CsrRaw``: the raw list's undirected adjacency as
a numpy CSR, built by sorting the raw list, so that a k-hop request reads
its frontier's rows as slices. It answers by ``graphref``'s definitions
and imports nothing of the program.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from graphref import ID_BYTES, ROW_BYTES, OneModeRaw, PlainNetwork

CHUNK = 1 << 22  # edges drawn per task


def _edges(scale: int, p: dict, lo: int, hi: int, seed: int):
    """Edges lo..hi of the Kronecker draw -> (i, j) before relabelling."""
    rng = np.random.default_rng([seed % 2**63, 1, lo // CHUNK])
    m = hi - lo
    ab = np.float32(p["A"] + p["B"])
    c_norm = np.float32(p["C"] / (1.0 - ab))
    a_norm = np.float32(p["A"] / ab)
    i = np.zeros(m, np.int32)
    j = np.zeros(m, np.int32)
    for level in range(scale):
        ii = rng.random(m, dtype=np.float32) > ab
        jj = rng.random(m, dtype=np.float32) > np.where(ii, c_norm, a_norm)
        i |= ii.astype(np.int32) << level
        j |= jj.astype(np.int32) << level
    return i, j


def kronecker(scale: int, edge_factor: int, p: dict, seed: int):
    """The raw edge list (src, dst), int32, labels permuted."""
    n, m = 1 << scale, edge_factor << scale
    src = np.empty(m, np.int32)
    dst = np.empty(m, np.int32)
    perm = np.random.default_rng([seed % 2**63, 0]).permutation(n).astype(
        np.int32)

    def chunk(lo: int) -> None:
        hi = min(lo + CHUNK, m)
        i, j = _edges(scale, p, lo, hi, seed)
        src[lo:hi] = perm[i]
        dst[lo:hi] = perm[j]

    with ThreadPoolExecutor(max_workers=min(16, os.cpu_count() or 1)) as pool:
        list(pool.map(chunk, range(0, m, CHUNK)))
    return src, dst


class CsrRaw(OneModeRaw):
    """The undirected adjacency of a raw edge list as a numpy CSR: each row
    sorted, without self-loops or repeats. ``src`` and ``dst`` stay as the
    generator made them (the program's network is built from them)."""

    def __init__(self, n_nodes: int, src, dst):
        super().__init__(n_nodes, src, dst)
        keep = self.src != self.dst
        a = np.concatenate([self.src[keep], self.dst[keep]]).astype(np.int64)
        b = np.concatenate([self.dst[keep], self.src[keep]]).astype(np.int64)
        key = np.sort(a * self.n_nodes + b)
        del a, b
        fresh = np.ones(key.size, bool)
        fresh[1:] = key[1:] != key[:-1]
        key = key[fresh]
        self.indices = (key % self.n_nodes).astype(np.int32)
        self.indptr = np.zeros(self.n_nodes + 1, np.int64)
        np.cumsum(np.bincount(key // self.n_nodes, minlength=self.n_nodes),
                  out=self.indptr[1:])

    def neighbours_of(self, ids) -> dict:
        return {int(u): self.alters(int(u)) for u in np.unique(ids)}

    def prefetch_alters(self, ids) -> None:
        pass

    def alters(self, u: int) -> np.ndarray:
        return self.indices[self.indptr[u]:self.indptr[u + 1]]

    def alters_bytes(self, u: int) -> int:
        return ROW_BYTES + ID_BYTES * int(self.indptr[u + 1] - self.indptr[u])

    def edge_value(self, u: int, v: int) -> float:
        row = self.alters(u)
        at = np.searchsorted(row, v)
        return float(at < row.size and row[at] == v)


def _one_pass_khop() -> None:
    """Refuse a program without the one-pass k-hop (``khop_host`` of
    ``repro.core.traversal``): its padded hop loop answers no request of
    this cell within the frontend's 30 s, and its run would not end.

    The symbol ``repro.core.traversal.khop_host`` is part of this cell's
    contract: a program that renames or folds it away fails the cell.
    This stands in for a deadline on the harness's warm-up, which it has
    not got; once it has one, this probe goes."""
    from repro.core import traversal

    if not hasattr(traversal, "khop_host"):
        raise RuntimeError("kron22 needs the one-pass k-hop "
                           "(repro.core.traversal.khop_host)")


def generate(cfg: dict, seed: int) -> PlainNetwork:
    _one_pass_khop()
    scale = int(cfg["scale"])
    src, dst = kronecker(scale, int(cfg["edge_factor"]), cfg["initiator"],
                         seed)
    return PlainNetwork(1 << scale, {cfg["layer"]: CsrRaw(1 << scale, src,
                                                          dst)})
