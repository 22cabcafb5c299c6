"""Sharded graph engine (beyond-paper: removes the single-machine limit).

Runs in a subprocess with 8 CPU devices; pseudo-projection queries over
the node-range-sharded layer must equal the single-device engine.
"""

import subprocess
import sys
from pathlib import Path

SRC = str(Path(__file__).resolve().parents[1] / "src")


def _run(code: str, n_devices: int = 8) -> str:
    env = {
        "PYTHONPATH": SRC,
        "XLA_FLAGS": f"--xla_force_host_platform_device_count={n_devices}",
        "PATH": "/usr/bin:/bin",
        "HOME": "/tmp",
    }
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env=env, timeout=600,
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


def test_sharded_edge_value_matches_local():
    code = """
import numpy as np
import jax, jax.numpy as jnp
from repro.core import random_two_mode
from repro.core.sharded import make_sharded_edge_value, shard_two_mode

assert len(jax.devices()) == 8
from repro.launch.mesh import make_mesh
mesh = make_mesh((8,), ("data",))
layer = random_two_mode(1000, 40, 4.0, seed=3)
graph = shard_two_mode(layer, 8)
edge_value = make_sharded_edge_value(graph, mesh)

rng = np.random.default_rng(0)
u = jnp.asarray(rng.integers(0, 1000, 512), jnp.int32)
v = jnp.asarray(rng.integers(0, 1000, 512), jnp.int32)
got = np.asarray(edge_value(u, v))
want = np.asarray(layer.edge_value(u, v))
np.testing.assert_allclose(got, want)
print("EDGE_VALUE_OK", float(got.sum()))
"""
    assert "EDGE_VALUE_OK" in _run(code)


def test_sharded_walk_step_valid_neighbors():
    code = """
import numpy as np
import jax, jax.numpy as jnp
from repro.core import random_two_mode
from repro.core.sharded import make_sharded_walk_step, shard_two_mode

from repro.launch.mesh import make_mesh
mesh = make_mesh((8,), ("data",))
layer = random_two_mode(400, 12, 3.0, seed=5)
graph = shard_two_mode(layer, 8)
step = make_sharded_walk_step(graph, mesh)

u = jnp.arange(128, dtype=jnp.int32)
moved = 0
for t in range(4):
    nxt = step(u, t)
    nv = np.asarray(nxt)
    uv = np.asarray(u)
    m = nv != uv
    moved += int(m.sum())
    if m.any():
        # every move must be a pseudo-projected edge (or a self co-member)
        vals = np.asarray(layer.edge_value(u, nxt))
        bad = m & (vals == 0)
        assert not bad.any(), f"step {t}: walkers jumped off-graph"
    u = nxt
assert moved > 100, "walkers barely moved"
print("WALK_OK", moved)
"""
    assert "WALK_OK" in _run(code)

def test_sharded_network_bit_identity_on_8_device_mesh():
    """ShardedNetwork on the forced 8-CPU-device mesh: shards land on
    distinct devices (round-robin placement) and every query kind is
    bit-identical to the single-device Network path."""
    code = """
import numpy as np
import jax, jax.numpy as jnp
from repro.core import api
from repro.core.layers import one_mode_from_edges, two_mode_from_memberships
from repro.core.sharded import shard_network
from repro.core.traversal import components_batched

assert len(jax.devices()) == 8
n = 640
rng = np.random.default_rng(7)
bounds = [(n * s) // 8 for s in range(1, 8)]
src = [rng.integers(0, n, 2400)]
dst = [rng.integers(0, n, 2400)]
for b in bounds:  # hub pinned at each shard boundary
    src.append(np.full(50, b))
    dst.append(rng.integers(0, n, 50))
net = api.createnetwork(n)
net = net.with_layer("ties", one_mode_from_edges(
    n, np.concatenate(src), np.concatenate(dst), directed=False))
nodes, hes = [], []
for h in range(32):
    b = bounds[h % 7]
    members = rng.integers(max(0, b - 24), min(n, b + 24), 10)
    nodes.append(members); hes.append(np.full(members.size, h))
net = net.with_layer("hh", two_mode_from_memberships(
    n, 32, np.concatenate(nodes), np.concatenate(hes)))

sn = shard_network(net, 8)
# shard payloads must be spread over all 8 devices
devset = set()
for s in sn.shards:
    for leaf in jax.tree_util.tree_leaves(s):
        if hasattr(leaf, "devices"):
            devset |= leaf.devices()
assert len(devset) == 8, f"shards on {len(devset)} devices, want 8"

u = np.concatenate([np.asarray(bounds), rng.integers(0, n, 64)]).astype(np.int32)
v = np.concatenate([np.asarray(bounds) + 1, rng.integers(0, n, 64)]).astype(np.int32)
for layer in ("ties", "hh"):
    np.testing.assert_array_equal(
        np.asarray(net.edge_value(layer, u, v)),
        np.asarray(sn.edge_value(layer, u, v)))
av, am = net.node_alters(u, 64)
bv, bm = sn.node_alters(u, 64)
np.testing.assert_array_equal(np.asarray(av), np.asarray(bv))
np.testing.assert_array_equal(np.asarray(am), np.asarray(bm))
np.testing.assert_array_equal(np.asarray(net.degree(u)), np.asarray(sn.degree(u)))
srcs = np.asarray(bounds, np.int32)
a = net.khop(srcs, 2, max_frontier=128)
b = sn.khop(srcs, 2, max_frontier=128)
for x, y in zip(a, b):
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
np.testing.assert_array_equal(
    np.asarray(components_batched(net)), np.asarray(sn.components()))
print("MESH_SHARDED_OK", len(devset))
"""
    assert "MESH_SHARDED_OK 8" in _run(code)
