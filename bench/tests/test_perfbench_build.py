"""The benchmark's network is the program's own build of the generator's
raw lists: every row holds what the raw lists say, and every seed gives
the same shapes."""

from __future__ import annotations

import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import numpy as np  # noqa: E402
import pytest  # noqa: E402

import graphref  # noqa: E402
import netbuild  # noqa: E402
from manifest import load_module  # noqa: E402


def _rehearsal(config: str, **size) -> dict:
    cfg = json.loads((BENCH / "configs" / f"{config}.json").read_text())
    cfg.update(cfg.pop("rehearsal"))
    cfg.update(size)
    return cfg


def _rows(indptr, indices) -> list[set]:
    indptr, indices = np.asarray(indptr), np.asarray(indices)
    return [set(indices[a:b].tolist())
            for a, b in zip(indptr[:-1], indptr[1:])]


def _raw_rows(keys, vals, n) -> list[set]:
    out: list[set] = [set() for _ in range(n)]
    for k, v in zip(np.asarray(keys).tolist(), np.asarray(vals).tolist()):
        out[k].add(v)
    return out


@pytest.mark.parametrize("config,size", [
    ("register10m", {"nodes": 20000}),
    ("register10m", {"nodes": 40000}),
])
def test_network_holds_the_raw_lists(config, size):
    plain = load_module(BENCH / "configs" / f"{config}.py").generate(
        _rehearsal(config, **size), 31)
    net = netbuild.network(plain)
    assert list(net.layer_names) == list(plain.layers)
    for layer, raw in zip(net.layers, plain.layers.values()):
        if raw.mode == 2:
            assert _rows(layer.memb.indptr, layer.memb.indices) == \
                _raw_rows(raw.nodes, raw.groups, plain.n_nodes)
            assert _rows(layer.members.indptr, layer.members.indices) == \
                _raw_rows(raw.groups, raw.nodes, raw.n_groups)
        else:
            keep = raw.src != raw.dst
            src = np.concatenate([raw.src[keep], raw.dst[keep]])
            dst = np.concatenate([raw.dst[keep], raw.src[keep]])
            assert not layer.directed
            assert _rows(layer.out.indptr, layer.out.indices) == \
                _raw_rows(src, dst, plain.n_nodes)


def test_directed_flag_reaches_the_builder():
    """A one-mode layer is built as its raw layer says; the shared
    reference refuses a directed layer rather than answer it as
    undirected."""

    class Directed(graphref.OneModeRaw):
        def __init__(self, n, src, dst):
            super().__init__(n, src, dst)
            self.directed = True

    raw = Directed(4, np.array([0, 1]), np.array([1, 2]))
    layer = netbuild.build_layer(4, raw)
    assert layer.directed
    assert _rows(layer.out.indptr, layer.out.indices) == \
        [{1}, {2}, set(), set()]
    with pytest.raises(ValueError):
        graphref.OneModeRaw(4, raw.src, raw.dst, directed=True)


def test_every_seed_gives_the_same_shapes():
    """The configuration model keeps array shapes and static row bounds
    (the program's compile keys) fixed across seeds, and the answers
    differ."""
    import jax

    cfg = _rehearsal("register10m")
    gen = load_module(BENCH / "configs" / "register10m.py").generate
    shapes, hubs = [], []
    for seed in (5, 2**31 + 5):
        plain = gen(cfg, seed)
        net = netbuild.network(plain)
        leaves, tree = jax.tree_util.tree_flatten(net.layers)
        shapes.append((tree, [x.shape for x in leaves]))
        hubs.append(plain.hubs.tolist())
        for raw in plain.layers.values():  # nothing repeated, no loops
            if raw.mode == 2:
                pairs = raw.nodes.astype(np.int64) * raw.n_groups + raw.groups
            else:
                lo = np.minimum(raw.src, raw.dst).astype(np.int64)
                hi = np.maximum(raw.src, raw.dst)
                assert (lo != hi).all()
                pairs = lo * raw.n_nodes + hi
            assert np.unique(pairs).size == pairs.size
    assert shapes[0] == shapes[1]
    assert hubs[0] != hubs[1]
