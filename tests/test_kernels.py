"""Per-kernel shape/dtype sweeps vs the ref.py oracles (interpret mode)."""

import numpy as np
import jax.numpy as jnp
import pytest
pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core.csr import SENTINEL
from repro.kernels import ops, ref

RNG = np.random.default_rng(0)


# ---------------------------------------------------------------------------
# intersect
# ---------------------------------------------------------------------------


def _padded_rows(rng, B, K, universe=500):
    rows = np.full((B, K), SENTINEL, dtype=np.int32)
    sets = []
    for i in range(B):
        l = rng.integers(0, K + 1)
        s = np.sort(rng.choice(universe, size=l, replace=False))
        rows[i, :l] = s
        sets.append(set(s.tolist()))
    return rows, sets


@pytest.mark.parametrize("B", [1, 7, 8, 33])
@pytest.mark.parametrize("Ka,Kb", [(4, 4), (20, 64), (128, 128), (200, 60)])
def test_intersect_shapes(B, Ka, Kb):
    rng = np.random.default_rng(B * 1000 + Ka + Kb)
    a, sa = _padded_rows(rng, B, Ka)
    b, sb = _padded_rows(rng, B, Kb)
    want = np.array([len(x & y) for x, y in zip(sa, sb)], dtype=np.int32)
    got = np.asarray(ops.intersect_count(jnp.asarray(a), jnp.asarray(b)))
    np.testing.assert_array_equal(got, want)
    got_ref = np.asarray(
        ref.intersect_count_ref(jnp.asarray(a), jnp.asarray(b))
    )
    np.testing.assert_array_equal(got_ref, want)


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_intersect_property(seed):
    rng = np.random.default_rng(seed)
    B = int(rng.integers(1, 24))
    Ka = int(rng.integers(1, 96))
    Kb = int(rng.integers(1, 96))
    a, sa = _padded_rows(rng, B, Ka)
    b, sb = _padded_rows(rng, B, Kb)
    want = [len(x & y) for x, y in zip(sa, sb)]
    got = np.asarray(ops.intersect_count(jnp.asarray(a), jnp.asarray(b)))
    np.testing.assert_array_equal(got, want)


def test_intersect_layer_integration(small_mixed_network):
    layer = small_mixed_network.layer("wk")
    u = jnp.arange(0, 40)
    v = jnp.arange(40, 80)
    kernel_vals = np.asarray(ops.pseudo_edge_value(layer, u, v))
    jnp_vals = np.asarray(layer.edge_value(u, v))
    np.testing.assert_allclose(kernel_vals, jnp_vals)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,Hq,Hkv,S,D",
    [
        (1, 2, 2, 128, 64),   # MHA
        (2, 4, 2, 256, 64),   # GQA group 2
        (1, 8, 1, 128, 128),  # MQA
    ],
)
def test_flash_attention_sweep(B, Hq, Hkv, S, D, dtype):
    rng = np.random.default_rng(42)
    q = jnp.asarray(rng.normal(size=(B, Hq, S, D)), dtype)
    k = jnp.asarray(rng.normal(size=(B, Hkv, S, D)), dtype)
    v = jnp.asarray(rng.normal(size=(B, Hkv, S, D)), dtype)
    got = ops.flash_attention(q, k, v, causal=True)
    want = ops.flash_attention(q, k, v, causal=True, use_pallas=False)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=tol, rtol=tol,
    )


def test_flash_attention_non_causal():
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(1, 2, 128, 64)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 2, 128, 64)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 2, 128, 64)), jnp.float32)
    got = ops.flash_attention(q, k, v, causal=False)
    want = ops.flash_attention(q, k, v, causal=False, use_pallas=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_flash_attention_causality():
    """Changing future tokens must not change past outputs."""
    rng = np.random.default_rng(4)
    q = jnp.asarray(rng.normal(size=(1, 1, 256, 64)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 1, 256, 64)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 1, 256, 64)), jnp.float32)
    out1 = ops.flash_attention(q, k, v, causal=True)
    k2 = k.at[:, :, 200:].set(99.0)
    v2 = v.at[:, :, 200:].set(-99.0)
    out2 = ops.flash_attention(q, k2, v2, causal=True)
    np.testing.assert_allclose(
        np.asarray(out1[:, :, :200]), np.asarray(out2[:, :, :200]), atol=1e-6
    )


# ---------------------------------------------------------------------------
# SSD scan
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,H,S,P,N,chunk",
    [
        (1, 1, 128, 16, 32, 64),
        (2, 3, 256, 32, 64, 128),
        (1, 2, 192, 64, 128, 64),  # 3 chunks
    ],
)
def test_ssd_scan_sweep(B, H, S, P, N, chunk, dtype):
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(size=(B, H, S, P)), dtype)
    dt = jnp.asarray(rng.uniform(0.1, 1.0, size=(B, H, S)), jnp.float32)
    a_log = -dt * jnp.asarray(
        rng.uniform(0.5, 2.0, size=(B, H, S)), jnp.float32
    )
    bm = jnp.asarray(rng.normal(size=(B, S, N)) * 0.2, jnp.float32)
    cm = jnp.asarray(rng.normal(size=(B, S, N)) * 0.2, jnp.float32)
    got = ops.ssd_scan(x, dt, a_log, bm, cm, chunk=chunk)
    want = ops.ssd_scan(x, dt, a_log, bm, cm, use_pallas=False)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=tol, rtol=tol,
    )


def test_ssd_state_carries_across_chunks():
    """Output at t > chunk must depend on inputs from the first chunk."""
    rng = np.random.default_rng(8)
    B, H, S, P, N = 1, 1, 256, 16, 32
    x = jnp.asarray(rng.normal(size=(B, H, S, P)), jnp.float32)
    dt = jnp.ones((B, H, S)) * 0.5
    a_log = -0.005 * jnp.ones((B, H, S))  # slow decay -> long memory
    bm = jnp.asarray(rng.normal(size=(B, S, N)) * 0.2, jnp.float32)
    cm = jnp.asarray(rng.normal(size=(B, S, N)) * 0.2, jnp.float32)
    y1 = ops.ssd_scan(x, dt, a_log, bm, cm, chunk=128)
    x2 = x.at[:, :, 0].set(x[:, :, 0] + 5.0)
    y2 = ops.ssd_scan(x2, dt, a_log, bm, cm, chunk=128)
    assert float(jnp.max(jnp.abs(y1[:, :, 200] - y2[:, :, 200]))) > 1e-4
    # and the kernel's cross-chunk effect must match the sequential oracle
    y1r = ops.ssd_scan(x, dt, a_log, bm, cm, use_pallas=False)
    y2r = ops.ssd_scan(x2, dt, a_log, bm, cm, use_pallas=False)
    np.testing.assert_allclose(
        np.asarray(y1 - y2), np.asarray(y1r - y2r), atol=1e-4
    )


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(3, 128), (5, 7, 96), (1, 256), (16, 2048)])
@pytest.mark.parametrize("plus_one", [False, True])
def test_rmsnorm_sweep(shape, dtype, plus_one):
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.normal(size=shape), dtype)
    w = jnp.asarray(rng.normal(size=shape[-1:]), dtype)
    got = ops.rmsnorm(x, w, plus_one=plus_one)
    want = ref.rmsnorm_ref(x, w, plus_one=plus_one)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=tol, rtol=tol,
    )


@pytest.mark.parametrize("op", ["intersect", "union", "frontier"])
def test_interpret_refused_on_tpu(monkeypatch, op):
    """On a TPU a kernel runs compiled; interpret=True there is an error."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    rows = jnp.zeros((8, 128), jnp.int32)
    with pytest.raises(ValueError, match="interpret=True on a TPU"):
        if op == "intersect":
            ops.intersect_count(rows, rows, interpret=True)
        elif op == "union":
            ops.segmented_union(rows, 16, interpret=True)
        else:
            ops.frontier_compact(rows, rows, 16, interpret=True)
