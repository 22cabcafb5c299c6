"""Serve a 10M-node register network from the TPU and check every answer.

One process, data made from ``--seed``:

1. **build** — the register network of ``benchmarks/table1_scale.py``
   (Households / Workplaces / Schools two-mode layers, ~110M memberships
   at 10M nodes) through the streaming builder, plus a few dozen hub
   nodes with 256+ Workplaces memberships and an undirected one-mode
   ``Contacts`` layer of mean degree ~8; built in host memory;
2. **transfer** — the whole network is put on the accelerator and every
   layer array is checked to sit there;
3. **serve** — ``api.servenet`` starts the NDJSON/TCP frontend and
   ``GraphServeClient`` sessions over loopback send a few hundred
   ``getedge`` / ``alters`` (Workplaces, Schools), ``degree``, ``khop``
   (k=2 on Contacts, max_frontier 256) and ``walkbatch`` requests,
   sources drawn from the seed, hubs included;
   Kinds are served one after another, each from 8 concurrent sessions;
4. **check** — every answer against a host numpy reference that reads
   the CSR arrays pulled back from the device once (it never goes
   through ``core/dispatch.py`` or ``kernels/``); every walk step must be
   an edge of a layer or a stay; any error record, mismatch, pump fault
   or expired deadline fails the run. Each Pallas kernel must have been
   reached by served requests and must compile to a ``tpu_custom_call``.

``--chips 4`` runs only the sharded phase: the same requests through
``api.servenet(net, shards=4)`` with one shard per chip, compared with
the host reference and with the unsharded engine on one chip.

The last line of standard output is
``{"ok": ..., "device": {"platform", "kind", "count"}}``; the exit code
is 0 only when ``ok`` is true, which needs a TPU. Without an accelerator
the full-size run refuses to start (exit 2, no result line). ``--nodes``
rehearses at a small size on any backend, e.g.

    JAX_PLATFORMS=cpu python chip_smoke.py --nodes 50000

which runs end to end and ends ``"ok": false``.
"""

from __future__ import annotations

import argparse
import json
import sys
import threading
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT / "benchmarks")]

import numpy as np  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core import api, compile_cache, dispatch  # noqa: E402
from repro.core.layers import (  # noqa: E402
    LayerTwoMode,
    one_mode_from_edges,
    two_mode_from_membership_chunks,
)
from repro.kernels import ops as kops  # noqa: E402
from repro.serve import GraphServeClient  # noqa: E402
from table1_scale import LAYER_RECIPE, _membership_chunks  # noqa: E402

KERNELS = ("intersect", "segmented_union", "frontier")
KINDS = ("getedge", "alters", "degree", "khop", "walkbatch")
N_HUBS = 32
HUB_MEMBERSHIPS = 256  # Workplaces memberships per hub, at least
CONTACT_DEGREE = 8  # mean degree of the one-mode layer
MAX_ALTERS = 512
KHOP_FRONTIER = 256
WALK_STARTS, WALK_WALKERS, WALK_STEPS = 4, 2, 8


def log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# Build + transfer
# ---------------------------------------------------------------------------


def build_network(n: int, seed: int):
    """Register network in host memory -> (net, hub ids)."""
    rng = np.random.default_rng(seed)
    hubs = np.sort(rng.choice(n, N_HUBS, replace=False)).astype(np.int64)
    net = api.createnetwork(api.createnodeset(n))
    for i, (name, per_node, npg) in enumerate(LAYER_RECIPE):
        n_groups = max(int(n / npg), 1)
        chunks = _membership_chunks(n, per_node, n_groups, seed * 1000 + i)
        if name == "Workplaces":
            hub_groups = [
                rng.choice(n_groups, HUB_MEMBERSHIPS + int(rng.integers(64)),
                           replace=False)
                for _ in hubs
            ]
            hub_chunk = (
                np.repeat(hubs, [g.size for g in hub_groups]),
                np.concatenate(hub_groups).astype(np.int64),
            )
            chunks = _chain(chunks, [hub_chunk])
        net = net.with_layer(
            name, two_mode_from_membership_chunks(n, n_groups, chunks)
        )
    m = n * CONTACT_DEGREE // 2
    src = rng.integers(0, n, m, dtype=np.int64)
    dst = rng.integers(0, n, m, dtype=np.int64)
    net = net.with_layer("Contacts", one_mode_from_edges(n, src, dst))
    return net, hubs


def _chain(*iterables):
    for it in iterables:
        yield from it


def host_device():
    """The CPU device the network is built on (the default device when
    the CPU backend is not loaded)."""
    try:
        return jax.devices("cpu")[0]
    except RuntimeError:
        return jax.devices()[0]


def layer_arrays(net) -> list:
    return [x for x in jax.tree_util.tree_leaves(net.layers)
            if isinstance(x, jax.Array)]


# ---------------------------------------------------------------------------
# Host reference: numpy over CSR arrays pulled from the device once
# ---------------------------------------------------------------------------


class HostReference:
    def __init__(self, net):
        self.names = net.layer_names
        self.csr = {}
        for name, layer in zip(net.layer_names, net.layers):
            if isinstance(layer, LayerTwoMode):
                self.csr[name] = (
                    np.asarray(layer.memb.indptr),
                    np.asarray(layer.memb.indices),
                    np.asarray(layer.members.indptr),
                    np.asarray(layer.members.indices),
                )
            else:
                self.csr[name] = (
                    np.asarray(layer.out.indptr),
                    np.asarray(layer.out.indices),
                )

    def row(self, name: str, u: int, second: bool = False) -> np.ndarray:
        arrs = self.csr[name]
        indptr, indices = arrs[2:] if second else arrs[:2]
        return indices[int(indptr[u]): int(indptr[u + 1])].astype(np.int64)

    def two_mode(self, name: str) -> bool:
        return len(self.csr[name]) == 4

    def getedge(self, name: str, u: int, v: int) -> float:
        return float(np.intersect1d(self.row(name, u), self.row(name, v)).size)

    def alters(self, name: str, u: int, max_alters: int) -> list:
        groups = self.row(name, u)
        members = [self.row(name, int(h), second=True) for h in groups]
        out = np.unique(np.concatenate(members + [np.zeros(0, np.int64)]))
        return out[out != u][:max_alters].tolist()

    def degree(self, ids: list) -> list:
        return [
            int(sum(int(self.csr[n][0][u + 1] - self.csr[n][0][u])
                    for n in self.names))
            for u in ids
        ]

    def khop(self, name: str, source: int, k: int, max_frontier: int):
        visited = {source}
        frontier = [source]
        nodes, hops = [], []
        for h in range(1, k + 1):
            cand = np.unique(np.concatenate(
                [self.row(name, f) for f in frontier] + [np.zeros(0, np.int64)]
            ))
            new = [int(x) for x in cand if int(x) not in visited]
            frontier = new[:max_frontier]
            if not frontier:
                break
            visited.update(frontier)
            nodes += frontier
            hops += [h] * len(frontier)
        return {"source": source, "count": len(nodes), "nodes": nodes,
                "hops": hops}

    def adjacent(self, u: int, v: int) -> bool:
        for name in self.names:
            if self.two_mode(name):
                if self.getedge(name, u, v) > 0:
                    return True
            elif v in set(self.row(name, u).tolist()):
                return True
        return False


# ---------------------------------------------------------------------------
# Requests and their reference answers
# ---------------------------------------------------------------------------


def make_requests(ref: HostReference, n: int, hubs: np.ndarray, seed: int,
                  per_kind: int) -> list[dict]:
    rng = np.random.default_rng(seed + 1)

    def node() -> int:
        # one draw in eight is a hub; the rest are uniform
        if rng.random() < 0.125:
            return int(rng.choice(hubs))
        return int(rng.integers(n))

    def partner(name: str, u: int) -> int:
        groups = ref.row(name, u)
        if groups.size and rng.random() < 0.5:
            members = ref.row(name, int(rng.choice(groups)), second=True)
            return int(rng.choice(members))
        return node()

    reqs = []
    for name in ("Workplaces", "Schools"):
        for _ in range(per_kind):
            u = node()
            reqs.append({"kind": "getedge", "layer": name, "u": u,
                         "v": partner(name, u)})
        reqs.append({"kind": "getedge", "layer": name, "u": int(hubs[0]),
                     "v": int(hubs[1])})
        for _ in range(per_kind):
            reqs.append({"kind": "alters", "u": node(), "layers": [name],
                         "max_alters": MAX_ALTERS})
    for _ in range(per_kind):
        reqs.append({"kind": "degree",
                     "u": [node() for _ in range(int(rng.integers(1, 5)))]})
        reqs.append({"kind": "khop", "sources": node(), "k": 2,
                     "layers": ["Contacts"], "max_frontier": KHOP_FRONTIER})
        reqs.append({"kind": "walkbatch",
                     "starts": [node() for _ in range(WALK_STARTS)],
                     "steps": WALK_STEPS, "walkers": WALK_WALKERS,
                     "seed": int(rng.integers(2**31 - 1))})
    order = rng.permutation(len(reqs))
    return [reqs[i] for i in order]


def check_answer(ref: HostReference, req: dict, got) -> str | None:
    """None when ``got`` is right for ``req``, else what is wrong."""
    kind = req["kind"]
    if kind == "getedge":
        want = ref.getedge(req["layer"], req["u"], req["v"])
    elif kind == "alters":
        want = ref.alters(req["layers"][0], req["u"], req["max_alters"])
    elif kind == "degree":
        want = ref.degree(req["u"])
        want = want[0] if len(want) == 1 else want
    elif kind == "khop":
        want = [ref.khop(req["layers"][0], req["sources"], req["k"],
                         req["max_frontier"])]
    else:
        paths = np.asarray(got)
        shape = (WALK_STARTS * WALK_WALKERS, WALK_STEPS + 1)
        if paths.shape != shape:
            return f"walk shape {paths.shape} != {shape}"
        starts = np.repeat(req["starts"], WALK_WALKERS)
        if not np.array_equal(paths[:, 0], starts):
            return "walks do not begin at their starts"
        for path in paths:
            for a, b in zip(path[:-1], path[1:]):
                if a != b and not ref.adjacent(int(a), int(b)):
                    return f"walk step {a}->{b} is not an edge"
        return None
    if got == want:
        return None
    return f"got {str(got)[:200]} want {str(want)[:200]}"


# ---------------------------------------------------------------------------
# Serving
# ---------------------------------------------------------------------------


_sessions = iter(range(1 << 30))


def serve_all(fe, requests: list[dict], n_clients: int) -> list[dict]:
    """Send every request over loopback from ``n_clients`` sessions."""
    host, port = fe.address
    out: list = [None] * len(requests)
    # each session seeds its own idempotency keys: a seed shared by two
    # sessions would make the server replay one's answers to the other
    seeds = [next(_sessions) for _ in range(n_clients)]

    def worker(w: int) -> None:
        with GraphServeClient(host, port, io_timeout=900.0,
                              seed=seeds[w]) as c:
            for i in range(w, len(requests), n_clients):
                try:
                    out[i] = c.query(requests[i], full=True)
                except Exception as e:  # recorded, fails the run
                    out[i] = {"ok": False, "error": f"{type(e).__name__}: {e}"}

    threads = [threading.Thread(target=worker, args=(w,))
               for w in range(n_clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return out


def run_phase(label: str, net, ref, requests, *, shards=None,
              n_clients: int = 8) -> tuple[list, bool]:
    """Serve ``requests`` through a fresh frontend; returns (results, ok)."""
    fe = api.servenet(net, shards=shards, result_timeout=900.0)
    resp: list = [None] * len(requests)
    t_first = t_rest = 0.0
    try:
        # kind by kind: the first request alone (its programs compile),
        # then the rest from n_clients concurrent sessions
        for kind in KINDS:
            idx = [i for i, r in enumerate(requests) if r["kind"] == kind]
            compiled = compile_cache.stats()["requests"]
            t0 = time.perf_counter()
            resp[idx[0]] = serve_all(fe, [requests[idx[0]]], 1)[0]
            t1 = time.perf_counter()
            for i, r in zip(idx[1:], serve_all(
                    fe, [requests[i] for i in idx[1:]], n_clients)):
                resp[i] = r
            t2 = time.perf_counter()
            t_first, t_rest = t_first + t1 - t0, t_rest + t2 - t1
            log(f"[{label}] {kind}: first request {t1 - t0:.2f}s, "
                f"{len(idx) - 1} more in {t2 - t1:.2f}s, "
                f"{compile_cache.stats()['requests'] - compiled} programs "
                f"compiled")
        stats = fe.stats
        if shards:
            placement = check_shard_placement(fe.engine.sharded)
        else:
            placement = True
    finally:
        fe.close()
    log(f"[{label}] wall: first-request compile {t_first:.1f}s, "
        f"serve {t_rest:.1f}s ({len(requests) - len(KINDS)} requests)")
    per_kind: dict[str, list[int]] = {}
    ok = placement
    for req, r in zip(requests, resp):
        tally = per_kind.setdefault(req["kind"], [0, 0, 0])
        tally[0] += 1
        if not r.get("ok") or r.get("degraded"):
            tally[1] += 1
            if tally[1] <= 3:
                log(f"[{label}] ERROR {req['kind']}: {r.get('error', r)}")
            continue
        bad = check_answer(ref, req, r["result"])
        if bad is not None:
            tally[2] += 1
            if tally[2] <= 3:
                log(f"[{label}] MISMATCH {json.dumps(req)}: {bad}")
    for kind, (count, errors, mismatches) in sorted(per_kind.items()):
        log(f"[{label}] {kind}: {count} requests, {errors} errors, "
            f"{mismatches} mismatches")
        ok &= errors == 0 and mismatches == 0
    eng = stats["engine"]
    log(f"[{label}] engine: served {eng['served']}, pump_faults "
        f"{eng['pump_faults']}, deadline_expired {eng['deadline_expired']}, "
        f"cache hits {eng['cache']['hits']}, batches {eng['batches']}")
    ok &= eng["pump_faults"] == 0 and eng["deadline_expired"] == 0
    return [r.get("result") for r in resp], ok


def check_shard_placement(snet) -> bool:
    devs = []
    for s, shard in enumerate(snet.shards):
        held = {d for x in layer_arrays(shard) for d in x.devices()}
        log(f"[sharded] shard {s}: nodes [{snet.bounds[s]}, "
            f"{snet.bounds[s + 1]}) on {sorted(map(str, held))}")
        devs.append(held)
    distinct = all(len(h) == 1 for h in devs) and len(
        set().union(*devs)) == len(devs)
    log(f"[sharded] each shard on its own chip: {distinct}")
    return distinct


# ---------------------------------------------------------------------------
# Kernels: compiled for the chip, reached by served requests
# ---------------------------------------------------------------------------


def kernels_compile(net, platform: str) -> bool:
    if platform != "tpu":
        log("[kernels] tpu_custom_call check needs a TPU: not run")
        return False
    wk = net.layer("Workplaces")
    u = jnp.zeros((8,), jnp.int32)
    wn = int(dispatch.node_max_hyperedge_size(wk).max())
    cand = jnp.zeros((8, 640), jnp.int32)
    visited = jnp.zeros((8, 384), jnp.int32)
    lowered = {
        "intersect": dispatch._edge_value_bucket.lower(
            wk, u, u, width=wk.max_memberships, use_pallas=True,
            interpret=False),
        "segmented_union": dispatch._node_alters_bucket.lower(
            wk, u, None, width_m=8, width_n=min(wn, 256),
            max_alters=MAX_ALTERS, use_pallas=True, interpret=False),
        "frontier": jax.jit(lambda c, v: kops.frontier_compact(
            c, v, KHOP_FRONTIER, use_pallas=True, interpret=False,
            visited_sorted=True)).lower(cand, visited),
    }
    ok = True
    for name, low in lowered.items():
        found = "tpu_custom_call" in low.compile().as_text()
        log(f"[kernels] {name}: compiled to tpu_custom_call: {found}")
        ok &= found
    return ok


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--nodes", type=int, default=None,
                    help="rehearsal size (default: 10,000,000, TPU only)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--requests", type=int, default=200,
                    help="requests per kind (and per layer where a kind "
                         "names one)")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    platform = dev.platform
    if args.nodes is None and platform == "cpu":
        print("chip_smoke: no accelerator found; pass --nodes to rehearse "
              "at a small size on the CPU", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 2
    n = args.nodes or 10_000_000
    log(f"jax.devices(): {devices}")
    log(f"compile cache: {compile_cache.enable()}")

    t = time.perf_counter()
    with jax.default_device(host_device()):
        host_net, hubs = build_network(n, args.seed)
    t_build = time.perf_counter() - t
    t = time.perf_counter()
    net = jax.device_put(host_net, dev)
    jax.block_until_ready(net)
    t_transfer = time.perf_counter() - t
    del host_net
    log(f"wall: build {t_build:.1f}s, transfer {t_transfer:.1f}s")

    arrays = layer_arrays(net)
    on_dev = all(x.devices() == {dev} for x in arrays)
    ok = on_dev and platform == "tpu"
    log(f"every layer array ({len(arrays)}) on {dev}: {on_dev}")
    for name, layer in zip(net.layer_names, net.layers):
        what = (f"{layer.n_memberships:,} memberships (max per node "
                f"{layer.max_memberships}, largest hyperedge "
                f"{layer.max_hyperedge_size})"
                if isinstance(layer, LayerTwoMode)
                else f"{layer.n_edges:,} edges (max degree "
                     f"{layer.max_degree()})")
        log(f"layer {name}: {what}, {layer.nbytes:,} bytes")
    mem = dev.memory_stats() or {}
    log(f"network: {net.n_nodes:,} nodes, {net.nbytes:,} bytes; device "
        f"bytes_in_use {mem.get('bytes_in_use', 'n/a')}, "
        f"bytes_limit {mem.get('bytes_limit', 'n/a')}")

    ref = HostReference(net)
    requests = make_requests(ref, n, hubs, args.seed, args.requests)

    if args.chips == 4:
        one, ok1 = run_phase("one-chip", net, ref, requests)
        four, ok4 = run_phase("sharded", net, ref, requests, shards=4)
        same = one == four
        log(f"[sharded] answers equal to the one-chip engine: {same}")
        ok = ok and ok1 and ok4 and same
    else:
        _, served_ok = run_phase("serve", net, ref, requests)
        reached = {k: kops.PALLAS_ENTRIES[k] for k in KERNELS}
        log(f"[kernels] reached by served requests: {reached}")
        ok = ok and served_ok and all(reached.values())
        ok = kernels_compile(net, platform) and ok
    log(f"compile cache: {compile_cache.stats()}")
    print(json.dumps({"ok": bool(ok), "device": {
        "platform": platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
