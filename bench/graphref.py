"""Plain reference: query answers computed from the generator's raw lists.

The reference imports nothing of the program. It holds the membership
pairs and edge lists that a configuration's generator made from the seed,
and answers each query by its definition:

* a two-mode layer is a list of (node, group) memberships; a repeated pair
  is one membership;
* a one-mode layer is a list of undirected edges; self-loops and repeats
  are dropped;
* ``getedge`` is the number of groups two nodes share (two-mode) or 1.0
  when they are adjacent (one-mode);
* ``alters`` is the sorted union of co-members (or neighbours) of a node,
  the node itself excluded, cut to the ``max_alters`` smallest ids;
* ``degree`` sums memberships (two-mode) and neighbours (one-mode) over
  the selected layers;
* ``khop`` is breadth first: each hop keeps the ``max_frontier`` smallest
  ids reached that were not reached before.

Rows are looked up in batches: one vectorised pass over a raw list finds
every row that a set of requests needs, so answering thousands of
requests costs a few passes, not one per request.

It also counts the bytes an index-based reader of the same data would
read to answer each request: 8 bytes for each row located (a pair of
32-bit offsets) and 4 bytes for each id read. ``hbm_roofline`` divides
that by the chip's bandwidth.
"""

from __future__ import annotations

import numpy as np

ROW_BYTES = 8
ID_BYTES = 4

_EMPTY = np.zeros(0, np.int64)


def _group_rows(keys: np.ndarray, vals: np.ndarray) -> dict:
    """(key, val) pairs -> {key: sorted unique vals}."""
    if keys.size == 0:
        return {}
    order = np.lexsort((vals, keys))
    keys, vals = keys[order], vals[order]
    fresh = np.ones(keys.size, bool)
    fresh[1:] = (keys[1:] != keys[:-1]) | (vals[1:] != vals[:-1])
    keys, vals = keys[fresh], vals[fresh]
    cut = np.flatnonzero(np.diff(keys)) + 1
    return {int(k[0]): v for k, v in zip(np.split(keys, cut),
                                          np.split(vals, cut))}


def _isin(arr: np.ndarray, wanted: np.ndarray) -> np.ndarray:
    kind = "table" if wanted.size and int(wanted.max()) < (1 << 27) else None
    return np.isin(arr, wanted, kind=kind)


class TwoModeRaw:
    """Raw memberships of a two-mode layer."""

    mode = 2

    def __init__(self, n_nodes: int, n_groups: int, nodes, groups):
        self.n_nodes, self.n_groups = int(n_nodes), int(n_groups)
        self.nodes = np.asarray(nodes)
        self.groups = np.asarray(groups)
        self._of_node: dict = {}
        self._of_group: dict = {}

    def _fetch(self, cache: dict, keys, vals, ids) -> None:
        ids = np.unique(np.asarray(ids, np.int64))
        missing = np.array([i for i in ids.tolist() if i not in cache],
                           np.int64)
        if missing.size == 0:
            return
        sel = _isin(keys, missing)
        found = _group_rows(keys[sel].astype(np.int64),
                            vals[sel].astype(np.int64))
        for i in missing.tolist():
            cache[i] = found.get(i, _EMPTY)

    def groups_of(self, ids) -> dict:
        self._fetch(self._of_node, self.nodes, self.groups, ids)
        return self._of_node

    def members_of(self, ids) -> dict:
        self._fetch(self._of_group, self.groups, self.nodes, ids)
        return self._of_group

    def prefetch_alters(self, ids) -> None:
        rows = self.groups_of(ids)
        need = [rows[int(u)] for u in np.unique(np.asarray(ids, np.int64))]
        self.members_of(np.concatenate(need + [_EMPTY]))

    def alters(self, u: int) -> np.ndarray:
        gs = self.groups_of([u])[u]
        mem = self.members_of(gs)
        out = np.unique(np.concatenate([mem[int(g)] for g in gs] + [_EMPTY]))
        return out[out != u]

    def alters_bytes(self, u: int) -> int:
        gs = self.groups_of([u])[u]
        mem = self.members_of(gs)
        return (ROW_BYTES + ID_BYTES * gs.size
                + sum(ROW_BYTES + ID_BYTES * mem[int(g)].size for g in gs))

    def degree(self, u: int) -> int:
        return int(self.groups_of([u])[u].size)

    def edge_value(self, u: int, v: int) -> float:
        rows = self.groups_of([u, v])
        return float(np.intersect1d(rows[u], rows[v]).size)

    def edge_bytes(self, u: int, v: int) -> int:
        rows = self.groups_of([u, v])
        return 2 * ROW_BYTES + ID_BYTES * (rows[u].size + rows[v].size)


class OneModeRaw:
    """Raw undirected edge list of a one-mode layer. A configuration with
    a directed layer brings a raw class of its own that answers by that
    layer's definition."""

    mode = 1

    def __init__(self, n_nodes: int, src, dst, directed: bool = False):
        if directed:
            raise ValueError("OneModeRaw answers undirected layers only")
        self.directed = False
        self.n_nodes = int(n_nodes)
        self.src = np.asarray(src)
        self.dst = np.asarray(dst)
        self._adj: dict = {}

    def neighbours_of(self, ids) -> dict:
        ids = np.unique(np.asarray(ids, np.int64))
        missing = np.array([i for i in ids.tolist() if i not in self._adj],
                           np.int64)
        if missing.size:
            a = _isin(self.src, missing)
            b = _isin(self.dst, missing)
            keys = np.concatenate([self.src[a], self.dst[b]]).astype(np.int64)
            vals = np.concatenate([self.dst[a], self.src[b]]).astype(np.int64)
            loop = keys == vals
            found = _group_rows(keys[~loop], vals[~loop])
            for i in missing.tolist():
                self._adj[i] = found.get(i, _EMPTY)
        return self._adj

    def prefetch_alters(self, ids) -> None:
        self.neighbours_of(ids)

    def alters(self, u: int) -> np.ndarray:
        return self.neighbours_of([u])[u]

    def alters_bytes(self, u: int) -> int:
        return ROW_BYTES + ID_BYTES * self.alters(u).size

    def degree(self, u: int) -> int:
        return int(self.alters(u).size)

    def edge_value(self, u: int, v: int) -> float:
        return float(v in set(self.alters(u).tolist()))

    def edge_bytes(self, u: int, v: int) -> int:
        return ROW_BYTES + ID_BYTES * self.alters(u).size


def linked(layer: OneModeRaw | TwoModeRaw) -> np.ndarray:
    """bool[n_nodes]: the node has a neighbour or a membership."""
    if layer.mode == 2:
        return np.bincount(layer.nodes, minlength=layer.n_nodes) > 0
    keep = layer.src != layer.dst
    return (np.bincount(layer.src[keep], minlength=layer.n_nodes)
            + np.bincount(layer.dst[keep], minlength=layer.n_nodes)) > 0


class PlainNetwork:
    """The reference network: named raw layers in serving order, and the
    hub nodes the generator planted (keys the traffic favours)."""

    def __init__(self, n_nodes: int, layers: dict, hubs=()):
        self.n_nodes = int(n_nodes)
        self.layers = dict(layers)
        self.hubs = np.asarray(hubs, np.int64)
        self._nonisolated = None

    def nonisolated(self) -> np.ndarray:
        """Nodes with a neighbour or a membership in some layer."""
        if self._nonisolated is None:
            mask = np.zeros(self.n_nodes, bool)
            for layer in self.layers.values():
                mask |= linked(layer)
            self._nonisolated = np.flatnonzero(mask)
        return self._nonisolated

    def select(self, names) -> list:
        if names is None:
            return list(self.layers.values())
        if isinstance(names, str):
            names = [names]
        return [self.layers[n] for n in names]

    # -- whole-request answers -------------------------------------------

    def _prefetch(self, requests: list[dict]) -> None:
        nodes: dict = {}
        alters: dict = {}
        for r in requests:
            kind = r["kind"]
            if kind == "getedge":
                nodes.setdefault(r["layer"], []).extend([r["u"], r["v"]])
            elif kind == "alters":
                for name in self._names(r.get("layers")):
                    alters.setdefault(name, []).append(r["u"])
            elif kind == "degree":
                for name in self._names(r.get("layers")):
                    nodes.setdefault(name, []).extend(_ids(r["u"]))
        from concurrent.futures import ThreadPoolExecutor

        def fetch(name: str) -> None:
            layer = self.layers[name]
            if name in nodes:
                (layer.groups_of if layer.mode == 2
                 else layer.neighbours_of)(nodes[name])
            if name in alters:
                layer.prefetch_alters(alters[name])

        # one thread per layer: the passes over raw lists are numpy work
        # that releases the interpreter lock
        with ThreadPoolExecutor(max_workers=max(len(self.layers), 1)) as pool:
            list(pool.map(fetch, set(nodes) | set(alters)))

    def _names(self, names) -> list:
        if names is None:
            return list(self.layers)
        return [names] if isinstance(names, str) else list(names)

    def answers(self, requests: list[dict]) -> list:
        """Reference answer of every request, in the wire's form."""
        self._prefetch(requests)
        khop = [i for i, r in enumerate(requests) if r["kind"] == "khop"]
        out: list = [None] * len(requests)
        for i, r in enumerate(requests):
            kind = r["kind"]
            if kind == "getedge":
                out[i] = self.layers[r["layer"]].edge_value(r["u"], r["v"])
            elif kind == "alters":
                out[i] = self.alters(r["u"], r.get("layers"),
                                     r.get("max_alters", 4096)).tolist()
            elif kind == "degree":
                degs = [sum(layer.degree(u)
                            for layer in self.select(r.get("layers")))
                        for u in _ids(r["u"])]
                out[i] = degs[0] if len(degs) == 1 else degs
        for i, rec in zip(khop, self.khop_many([requests[i] for i in khop])):
            out[i] = rec
        return out

    def alters(self, u: int, names, max_alters: int) -> np.ndarray:
        parts = [layer.alters(u) for layer in self.select(names)]
        out = np.unique(np.concatenate(parts + [_EMPTY]))
        return out[out != u][:max_alters]

    def khop_many(self, requests: list[dict]) -> list:
        """Breadth-first k-hop records of many requests, hop by hop, with
        the rows each hop needs fetched for all requests at once."""
        states = []
        for r in requests:
            src = _ids(r["sources"])
            states.append([{"source": s, "visited": {s}, "frontier": [s],
                            "nodes": [], "hops": []} for s in src])
        depth = max((r["k"] for r in requests), default=0)
        for h in range(1, depth + 1):
            need: dict = {}
            for r, st in zip(requests, states):
                if h > r["k"]:
                    continue
                for name in self._names(r.get("layers")):
                    need.setdefault(name, []).extend(
                        f for s in st for f in s["frontier"])
            for name, ids in need.items():
                self.layers[name].prefetch_alters(ids)
            for r, st in zip(requests, states):
                if h > r["k"]:
                    continue
                mf = r.get("max_frontier") or min(self.n_nodes, 4096)
                for s in st:
                    if not s["frontier"]:
                        continue
                    cand = np.unique(np.concatenate(
                        [layer.alters(f) for f in s["frontier"]
                         for layer in self.select(r.get("layers"))]
                        + [_EMPTY]))
                    seen = np.fromiter(s["visited"], np.int64)
                    new = cand[~np.isin(cand, seen)][:mf].tolist()
                    s["visited"].update(new)
                    s["nodes"] += new
                    s["hops"] += [h] * len(new)
                    s["frontier"] = new
        return [[{"source": s["source"], "count": len(s["nodes"]),
                  "nodes": s["nodes"], "hops": s["hops"]} for s in st]
                for st in states]

    # -- bytes an index-based reader reads ---------------------------------

    def request_bytes(self, r: dict, answer=None) -> int:
        """Bytes read to answer ``r`` (rows located and ids read), given
        its reference ``answer`` for khop (whose frontiers it expands)."""
        kind = r["kind"]
        if kind == "getedge":
            return self.layers[r["layer"]].edge_bytes(r["u"], r["v"])
        if kind == "alters":
            return sum(layer.alters_bytes(r["u"])
                       for layer in self.select(r.get("layers")))
        if kind == "degree":
            return (ROW_BYTES * len(_ids(r["u"]))
                    * len(self.select(r.get("layers"))))
        if kind == "khop":
            total = 0
            for rec in answer:
                hops = np.asarray(rec["hops"])
                nodes = np.asarray(rec["nodes"])
                frontiers = [[rec["source"]]] + [
                    nodes[hops == h].tolist() for h in range(1, r["k"])]
                for front in frontiers:
                    for f in front:
                        total += sum(layer.alters_bytes(int(f))
                                     for layer in self.select(r.get("layers")))
            return total
        raise ValueError(f"no byte count for request kind {kind!r}")


def _ids(x) -> list[int]:
    return [int(i) for i in np.atleast_1d(x).tolist()]
