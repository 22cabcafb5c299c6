"""The graph kernels compile for a v5e chip (described, not attached).

Interpret mode (every other kernel test) cannot see what Mosaic refuses:
vector shape casts, slicing of loaded values, scoped-VMEM overruns. These
tests lower each kernel at the widths the dispatcher routes to it, plus
one dispatched bucket function, for a described ``v5e:2x2`` topology
and check that the compiled program holds the kernel (``tpu_custom_call``).
The topology is described inside a fixture, never at import: only the
test process that runs this file loads the TPU compiler.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import dispatch
from repro.core.layers import two_mode_from_memberships
from repro.kernels.frontier import frontier_kernel
from repro.kernels.intersect import intersect_count_kernel
from repro.kernels.segmented_union import segmented_union_kernel


@pytest.fixture(scope="module")
def no_persistent_cache():
    """Compiles for a described chip are written to the persistent cache
    but cannot be read back without one; keep them out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def topo(no_persistent_cache):
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        if "TPU_LOG_DIR" not in os.environ:
            mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def ids(one_chip):
    """int32 id-matrix shape on the described chip."""
    return lambda *shape: jax.ShapeDtypeStruct(
        shape, jnp.int32, sharding=one_chip
    )


def _holds_kernel(lowered) -> bool:
    return "tpu_custom_call" in lowered.compile().as_text()


@pytest.mark.parametrize("K", [128, 1024])
def test_intersect_compiles(ids, K):
    lowered = intersect_count_kernel.lower(
        ids(1024, K), ids(1024, K), interpret=False
    )
    assert _holds_kernel(lowered)


@pytest.mark.parametrize("K", [256, 2048])
def test_segmented_union_compiles(ids, K):
    lowered = segmented_union_kernel.lower(ids(1024, K), interpret=False)
    assert _holds_kernel(lowered)


@pytest.mark.parametrize("Kv", [1024, 4096, 8192])
def test_frontier_compiles(ids, Kv):
    # Kv 8192 (k=3 hops at the default max_frontier 4096) needs ~18 MiB
    # of scoped VMEM, over Mosaic's 16 MiB default: the kernel asks for it
    lowered = frontier_kernel.lower(
        ids(1024, 2048), ids(1024, Kv), interpret=False
    )
    assert _holds_kernel(lowered)


@pytest.fixture(scope="module")
def hub_layer_shapes(one_chip):
    """A two-mode layer with a 300-membership hub, as shapes on the chip."""
    rng = np.random.default_rng(0)
    nodes = np.concatenate([rng.integers(0, 500, 2000), np.zeros(300, int)])
    groups = np.concatenate([rng.integers(0, 400, 2000), np.arange(300)])
    layer = two_mode_from_memberships(500, 400, nodes, groups)
    assert layer.max_memberships >= dispatch.PALLAS_MIN_WIDTH
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        layer,
    )


def test_dispatch_hub_bucket_compiles(hub_layer_shapes, ids):
    """The getedge bucket the auto rule sends to the intersect kernel."""
    layer = hub_layer_shapes
    lowered = dispatch._edge_value_bucket.lower(
        layer, ids(8), ids(8), width=layer.max_memberships,
        use_pallas=True, interpret=False,
    )
    assert _holds_kernel(lowered)


def test_one_pass_hop_compiles_at_the_kron22_shapes(one_chip, ids):
    """The one-pass khop hop at ``kron22.khop``'s shapes (a scale-22 graph
    of 128M stored ids, 32 rows, k 2, max_frontier 4096, the default
    chunk): one program that holds the segment-copy kernel, whose scratch
    does not grow with the hop."""
    from repro.core import traversal
    from repro.core.csr import CSR
    from repro.core.layers import LayerOneMode

    n, nnz = 1 << 22, 128_309_766
    csr = CSR(
        indptr=ids(n + 1), indices=ids(nnz), values=None, n_rows=n, n_cols=n,
    )
    layer = LayerOneMode(
        out=csr, in_=None, directed=False, valued=False, allow_self=False,
        store_inbound=True,
    )
    rows, k, mf = traversal.HOP_ROW_FLOOR, 2, 4096
    compiled = traversal._hop_expand.lower(
        (layer,), None, ids(rows), ids(k, rows * mf), ids(), ids(rows * mf),
        ids(2, rows * mf), chunk=traversal.HOP_CHUNK, id_bits=22,
        interpret=False,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20
