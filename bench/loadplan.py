"""The one traffic generator: a mix file's parameters -> request streams.

A traffic mix (``bench/traffic/<mix>.json``) is data only:

    {"loop": "open", "rate_per_s": 400, "connections": 64,
     "warmup_s": 10, "hub_share": 0.125,
     "mix": [{"kind": "getedge", "weight": 0.4,
              "layers": ["Workplaces"], "comember_share": 0.5}, ...]}

or ``"loop": "closed"`` with ``"sessions"`` in place of the rate. Kinds
and their parameters:

* ``getedge`` — ``layers`` to draw the layer from, ``comember_share`` of
  partners drawn among the first node's co-members (neighbours on a
  one-mode layer), the rest drawn as keys;
* ``alters`` — ``layers`` (one drawn per request), ``max_alters``;
* ``degree`` — ``nodes_min`` .. ``nodes_max`` nodes, over ``layers``
  (null: all layers);
* ``khop`` — ``k``, ``max_frontier``, ``layers`` (null: all layers).

Keys are drawn uniformly over ``keys`` (``"nodes"``: every node;
``"nonisolated"``: nodes with a neighbour or membership, Graph500's
rule for search roots), with ``hub_share`` of draws from the
configuration's hubs where it has any.

Every seed gets the same work in another order: the number of requests,
the count of each kind, of each layer, of hub draws and of co-member
partners are fixed by the mix; the seed draws the ids, the order and, in
an open loop, the arrival times (a Poisson process conditioned on its
count: sorted uniform offsets). The warm-up stream and the measured
stream are drawn from separate streams of the seed, so warm-up never
fills the result cache with the measured requests.
"""

from __future__ import annotations

import json

import numpy as np

WARMUP_STREAM, WINDOW_STREAM = 1, 2


def _shares(n: int, weights: list[float]) -> list[int]:
    """Split ``n`` by ``weights`` into whole counts that sum to ``n``."""
    w = np.asarray(weights, float)
    raw = n * w / w.sum()
    out = np.floor(raw).astype(int)
    for i in np.argsort(-(raw - out))[: n - out.sum()]:
        out[i] += 1
    return out.tolist()


class Keys:
    """Key draws for one stream: uniform over a population, a fixed share
    of them from the hubs."""

    def __init__(self, data, mix: dict, rng: np.random.Generator):
        self.rng = rng
        keys = mix.get("keys", "nodes")
        if keys == "nodes":
            self.population = None
            self.n = data.n_nodes
        elif keys == "nonisolated":
            self.population = data.nonisolated()
            self.n = self.population.size
        else:
            raise ValueError(f"unknown key population {keys!r}")
        self.hubs = np.asarray(getattr(data, "hubs", ()), np.int64)
        self.hub_share = float(mix.get("hub_share", 0.0)) if self.hubs.size \
            else 0.0

    def draw(self, n: int) -> np.ndarray:
        n_hub = int(round(n * self.hub_share))
        idx = self.rng.integers(0, self.n, n - n_hub)
        base = idx if self.population is None else self.population[idx]
        out = np.concatenate([base, self.rng.choice(self.hubs, n_hub)]) \
            if n_hub else base
        return self.rng.permutation(out.astype(np.int64))


def _layer_draws(spec: dict, n: int, rng) -> list:
    layers = spec.get("layers")
    if not layers:
        return [None] * n
    counts = _shares(n, [1.0] * len(layers))
    out = [name for name, c in zip(layers, counts) for _ in range(c)]
    return [out[i] for i in rng.permutation(n)]


def _partners(data, layer: str, us: np.ndarray, share: float, keys: Keys,
              rng) -> np.ndarray:
    """Partners of ``us`` on ``layer``: ``share`` of them co-members."""
    n = us.size
    co = np.zeros(n, bool)
    co[: int(round(n * share))] = True
    co = rng.permutation(co)
    out = keys.draw(n)
    plain = data.layers[layer]
    if plain.mode == 2:
        rows = plain.groups_of(us[co])
        picks = {}
        for u in us[co].tolist():
            g = rows[u]
            picks[u] = int(g[rng.integers(g.size)]) if g.size else None
        members = plain.members_of([g for g in picks.values()
                                    if g is not None])
        for i in np.flatnonzero(co):
            g = picks[int(us[i])]
            if g is not None:
                m = members[g]
                out[i] = int(m[rng.integers(m.size)])
    else:
        adj = plain.neighbours_of(us[co])
        for i in np.flatnonzero(co):
            nb = adj[int(us[i])]
            if nb.size:
                out[i] = int(nb[rng.integers(nb.size)])
    return out


def draw_requests(data, mix: dict, n: int, rng) -> list[dict]:
    """``n`` requests of the mix, in a seeded order."""
    keys = Keys(data, mix, rng)
    specs = mix["mix"]
    counts = _shares(n, [s["weight"] for s in specs])
    reqs: list[dict] = []
    for spec, count in zip(specs, counts):
        kind = spec["kind"]
        layers = _layer_draws(spec, count, rng)
        if kind == "getedge":
            us = keys.draw(count)
            vs = np.empty(count, np.int64)
            names = np.asarray(layers, object)
            for name in dict.fromkeys(layers):
                sel = np.flatnonzero(names == name)
                vs[sel] = _partners(data, name, us[sel],
                                    spec.get("comember_share", 0.0), keys,
                                    rng)
            reqs += [{"kind": kind, "layer": ly, "u": int(u), "v": int(v)}
                     for ly, u, v in zip(layers, us, vs)]
        elif kind == "alters":
            us = keys.draw(count)
            reqs += [{"kind": kind, "u": int(u), "layers": [ly],
                      "max_alters": int(spec["max_alters"])}
                     for ly, u in zip(layers, us)]
        elif kind == "degree":
            lo, hi = int(spec["nodes_min"]), int(spec["nodes_max"])
            sizes = np.resize(np.arange(lo, hi + 1), count)
            ids = keys.draw(int(sizes.sum()))
            cut = np.cumsum(sizes)[:-1]
            for part in np.split(ids, cut):
                r = {"kind": kind, "u": [int(x) for x in part]}
                if spec.get("layers"):
                    r["layers"] = list(spec["layers"])
                reqs.append(r)
        elif kind == "khop":
            roots = keys.draw(count)
            for root in roots:
                r = {"kind": kind, "sources": int(root), "k": int(spec["k"]),
                     "max_frontier": int(spec["max_frontier"])}
                if spec.get("layers"):
                    r["layers"] = list(spec["layers"])
                reqs.append(r)
        else:
            raise ValueError(f"unknown request kind {kind!r} in the mix")
    return [reqs[i] for i in rng.permutation(len(reqs))]


def phase(data, mix: dict, seed: int, stream: int, duration_s: float) -> dict:
    """One phase of the mix (warm-up or measured) for the load generator:
    the requests as wire JSON, and arrival offsets in an open loop.

    An open-loop warm-up sends its first half at the mix's rate and its
    second half at ``warmup_peak`` times it, so that the larger batches a
    burst of arrivals makes in the window are compiled beforehand."""
    rng = np.random.default_rng([seed % 2**63, stream])
    if mix["loop"] == "open":
        rate = float(mix["rate_per_s"])
        half = duration_s / 2.0
        peak = float(mix.get("warmup_peak", 1.0)) \
            if stream == WARMUP_STREAM else 1.0
        n1 = max(int(round(rate * half)), 1)
        n2 = max(int(round(rate * peak * half)), 1)
        offsets = np.concatenate([
            np.sort(rng.uniform(0.0, half, n1)),
            np.sort(rng.uniform(half, duration_s, n2))]).tolist()
        n = len(offsets)
        extra = {"offsets": offsets, "connections": int(mix["connections"])}
    elif mix["loop"] == "closed":
        n = int(mix["max_requests"])
        extra = {"sessions": int(mix["sessions"]), "duration_s": duration_s}
    else:
        raise ValueError(f"unknown loop {mix['loop']!r}")
    reqs = draw_requests(data, mix, n, rng)
    return {"loop": mix["loop"], "requests": reqs,
            "wire": [json.dumps(r) for r in reqs], **extra}
