"""The program's network, built from the generator's raw lists by the
program's own builders.

Every run serves a network made from its own seed, so every run builds
one, through the functions a user's import goes through:
``two_mode_from_membership_chunks`` for a two-mode layer (memberships to
the node -> groups CSR, then its transpose) and ``one_mode_from_edges``
for a one-mode layer, with the layer's ``directed`` flag as the
configuration states it. The layout (row order, dtypes, static bounds)
is the program's to choose; the benchmark reads nothing of it, and the
plain reference answers from the raw lists.

The layers are built in threads, one each: the builders' counting sorts
are numpy work that releases the interpreter lock.
"""

from __future__ import annotations


def build_layer(n_nodes: int, raw):
    """One raw layer through the program's builder."""
    from repro.core.layers import (
        one_mode_from_edges,
        two_mode_from_membership_chunks,
    )

    if raw.mode == 2:
        return two_mode_from_membership_chunks(
            n_nodes, raw.n_groups, [(raw.nodes, raw.groups)])
    return one_mode_from_edges(n_nodes, raw.src, raw.dst,
                               directed=raw.directed)


def network(plain):
    """The program's ``Network`` holding ``plain``'s layers, in order."""
    from concurrent.futures import ThreadPoolExecutor

    from repro.core import api

    with ThreadPoolExecutor(max_workers=max(len(plain.layers), 1)) as pool:
        jobs = [(name, pool.submit(build_layer, plain.n_nodes, raw))
                for name, raw in plain.layers.items()]
        layers = [(name, job.result()) for name, job in jobs]
    net = api.createnetwork(api.createnodeset(plain.n_nodes))
    for name, layer in layers:
        net = net.with_layer(name, layer)
    return net
