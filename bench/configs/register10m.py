"""register10m: the population register, generated from the seed.

``generate`` makes the raw lists (memberships and contact edges) with
numpy from the seed, as the plain reference the run is checked against;
``netbuild.py`` builds the program's network from them.

The recipe is that of ``benchmarks/table1_scale.py``: per two-mode layer
every node holds ``per_node`` memberships in groups of ``nodes_per_group``
nodes on average; the hubs hold a few hundred extra memberships in one
layer; ``Contacts`` has mean degree ``mean_degree``. It is drawn as a
configuration model, so that every seed gets the same sizes in another
order: the group sizes and the contact degrees are one fixed draw from
``shape_seed`` (Poisson-like, as uniform draws make them), the hubs hold
``min_memberships`` + 0, 2, 4, ... extra memberships, and the seed picks
the hubs and matches membership slots to group slots (and contact stubs
to stubs) at random. Repeated (node, group) pairs, self-loops and
repeated contacts are swapped away, so the number of stored entries, the
largest row and the largest group are the same for every seed: the
program's static bounds and array shapes, and with them its compiled
programs, do not change with the seed.
"""

from __future__ import annotations

import numpy as np

from graphref import OneModeRaw, PlainNetwork, TwoModeRaw


def _bad_pairs(a: np.ndarray, b: np.ndarray, width: int) -> np.ndarray:
    """Positions of repeated (a, b) pairs, all but the first of each,
    ordered by pair."""
    key = a.astype(np.int64) * width + b
    order = np.argsort(key)
    s = key[order]
    return order[1:][s[1:] == s[:-1]]


def _memberships(nodes: np.ndarray, sizes: np.ndarray, rng) -> np.ndarray:
    """Group of each membership slot: the group slots (``sizes``) matched
    at random to the node slots, then swapped until no node holds a group
    twice."""
    groups = np.repeat(np.arange(sizes.size, dtype=np.int32), sizes)
    groups = groups[rng.permutation(groups.size)]
    while True:
        bad = _bad_pairs(nodes, groups, sizes.size)
        if bad.size == 0:
            return groups
        other = rng.integers(0, groups.size, bad.size)
        for i, j in zip(bad.tolist(), other.tolist()):
            groups[i], groups[j] = groups[j], groups[i]


def _contacts(degrees: np.ndarray, rng) -> tuple[np.ndarray, np.ndarray]:
    """Undirected edges with these degrees: stubs matched at random, then
    swapped until there is no self-loop and no repeated edge."""
    stubs = np.repeat(np.arange(degrees.size, dtype=np.int32), degrees)
    stubs = stubs[rng.permutation(stubs.size)]
    src, dst = stubs[0::2].copy(), stubs[1::2].copy()
    n = degrees.size
    while True:
        lo, hi = np.minimum(src, dst), np.maximum(src, dst)
        bad = np.union1d(np.flatnonzero(lo == hi), _bad_pairs(lo, hi, n))
        if bad.size == 0:
            return src, dst
        other = rng.integers(0, src.size, bad.size)
        for i, j in zip(bad.tolist(), other.tolist()):
            dst[i], dst[j] = dst[j], dst[i]


def _two_mode(n, spec, i, hubs, extra, hub_layer, shape, seed):
    rng = np.random.default_rng([seed % 2**63, 10 + i])
    per_node = int(spec["per_node"])
    n_groups = max(int(n / spec["nodes_per_group"]), 1)
    slots = np.full(n, per_node, np.int64)
    if spec["name"] == hub_layer:
        slots[hubs] += extra
    draw = np.random.default_rng([shape, 1 + i])
    sizes = np.bincount(draw.integers(0, n_groups, int(slots.sum())),
                        minlength=n_groups)
    sizes = sizes[rng.permutation(n_groups)]
    nodes = np.repeat(np.arange(n, dtype=np.int32), slots)
    return TwoModeRaw(n, n_groups, nodes, _memberships(nodes, sizes, rng))


def _one_mode(n, spec, j, shape, seed):
    rng = np.random.default_rng([seed % 2**63, 100 + j])
    draw = np.random.default_rng([shape, 100 + j])
    degrees = np.bincount(draw.integers(0, n, n * int(spec["mean_degree"])),
                          minlength=n)
    degrees[0] += degrees.sum() % 2  # an even number of stubs
    degrees = degrees[rng.permutation(n)]
    return OneModeRaw(n, *_contacts(degrees, rng),
                      directed=bool(spec["directed"]))


def generate(cfg: dict, seed: int) -> PlainNetwork:
    """The raw lists; layers are drawn in threads, each from its own
    stream of the seed (numpy's sorts release the interpreter lock)."""
    from concurrent.futures import ThreadPoolExecutor

    n = int(cfg["nodes"])
    shape = int(cfg["shape_seed"])
    hub = cfg["hubs"]
    n_hubs = int(hub["count"])
    hubs = np.sort(np.random.default_rng([seed % 2**63, 0]).choice(
        n, n_hubs, replace=False))
    extra = (int(hub["min_memberships"])
             + 2 * (np.arange(n_hubs) * int(hub["extra_memberships"])
                    // (2 * n_hubs)))
    with ThreadPoolExecutor(max_workers=len(cfg["two_mode"])
                            + len(cfg["one_mode"])) as pool:
        jobs = [(spec["name"], pool.submit(
            _two_mode, n, spec, i, hubs, extra, hub["layer"], shape, seed))
            for i, spec in enumerate(cfg["two_mode"])]
        jobs += [(spec["name"], pool.submit(_one_mode, n, spec, j, shape,
                                            seed))
                 for j, spec in enumerate(cfg["one_mode"])]
        layers = {name: job.result() for name, job in jobs}
    return PlainNetwork(n, layers, hubs)
