"""Pure-jnp oracles for every Pallas kernel (the correctness contracts).

Each function is the simplest obviously-correct implementation; kernel
tests sweep shapes/dtypes and assert_allclose against these.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.csr import SENTINEL


def intersect_count_ref(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """|row_a ∩ row_b| for SENTINEL-padded rows with unique real entries.

    a: int32[B, Ka], b: int32[B, Kb] -> int32[B]. All-pairs equality.
    """
    valid = a != SENTINEL
    eq = (a[:, :, None] == b[:, None, :]) & valid[:, :, None]
    return jnp.sum(eq, axis=(1, 2)).astype(jnp.int32)


def segmented_union_ref(
    flat: jnp.ndarray, max_out: int
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Sort-based dedup of SENTINEL-padded rows, capped at ``max_out``.

    flat: int32[..., K] (unsorted, duplicates allowed) ->
    (int32[..., max_out] sorted unique SENTINEL-padded, mask). The oracle
    for the segmented-union kernel; identical to the engine's
    ``padded_unique`` + slice path.
    """
    from repro.core.csr import padded_unique

    uniq, mask = padded_unique(flat, flat != SENTINEL)
    if uniq.shape[-1] < max_out:
        pad = [(0, 0)] * (uniq.ndim - 1) + [(0, max_out - uniq.shape[-1])]
        uniq = jnp.pad(uniq, pad, constant_values=SENTINEL)
        mask = jnp.pad(mask, pad, constant_values=False)
    return uniq[..., :max_out], mask[..., :max_out]


def frontier_ref(
    cand: jnp.ndarray, visited: jnp.ndarray, max_out: int
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Oracle for the frontier dedup/compaction kernel (kernels/frontier.py).

    cand: int32[..., Kc] SENTINEL-padded candidate next-hop nodes
    (unsorted, duplicates allowed); visited: int32[..., Kv] SENTINEL-padded
    already-collected nodes. Drops candidates present in the visited row,
    then dedups/sorts/caps exactly like ``segmented_union_ref``. All-pairs
    membership — the simplest obviously-correct form.
    """
    valid = cand != SENTINEL
    seen = jnp.any(
        (cand[..., :, None] == visited[..., None, :]) & valid[..., :, None],
        axis=-1,
    )
    flat = jnp.where(valid & ~seen, cand, SENTINEL)
    return segmented_union_ref(flat, max_out)


def filtered_alters_ref(
    vals: jnp.ndarray,
    mask: jnp.ndarray,
    node_filter: jnp.ndarray,
    max_out: int,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Post-filter oracle for attribute-filtered GetNodeAlters.

    Takes an UNfiltered alters result (``vals``/``mask`` at full width —
    callers must query with ``max_alters`` large enough that nothing was
    truncated), drops alters failing ``node_filter`` (bool[n_nodes]), and
    re-compacts to ``max_out`` sorted-unique entries. The filtered query
    path must be bit-identical to this.
    """
    keep = mask & jnp.take(node_filter, jnp.where(mask, vals, 0), mode="clip")
    flat = jnp.where(keep, vals, SENTINEL)
    return segmented_union_ref(flat, max_out)


def filtered_degree_ref(
    vals: jnp.ndarray, mask: jnp.ndarray, node_filter: jnp.ndarray
) -> jnp.ndarray:
    """Post-filter oracle for attribute-filtered degree: count the alters
    of an UNfiltered full-width query that pass ``node_filter``."""
    keep = mask & jnp.take(node_filter, jnp.where(mask, vals, 0), mode="clip")
    return jnp.sum(keep, axis=-1).astype(jnp.int32)


def attention_ref(
    q: jnp.ndarray,  # (BH, S, D)
    k: jnp.ndarray,  # (BHkv, S, D)
    v: jnp.ndarray,  # (BHkv, S, D)
    *,
    scale: float,
    causal: bool = True,
    kv_group: int = 1,
) -> jnp.ndarray:
    """Naive softmax attention with GQA via explicit kv repeat."""
    if kv_group > 1:
        k = jnp.repeat(k, kv_group, axis=0)
        v = jnp.repeat(v, kv_group, axis=0)
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32), k.astype(jnp.float32))
    s = s * scale
    if causal:
        S = q.shape[1]
        mask = jnp.tril(jnp.ones((S, S), dtype=bool))
        s = jnp.where(mask[None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bqk,bkd->bqd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def ssd_scan_ref(
    x: jnp.ndarray,  # (BH, S, P)
    dt: jnp.ndarray,  # (BH, S)
    a_log: jnp.ndarray,  # (BH, S) log-decay per step (dt * A, negative)
    bmat: jnp.ndarray,  # (BH, S, N)
    cmat: jnp.ndarray,  # (BH, S, N)
) -> jnp.ndarray:
    """Sequential SSD recurrence: S_t = a_t S_{t-1} + (dt_t B_t) x_t^T,
    y_t = C_t S_t. The oracle for the chunked kernel."""
    BH, S, P = x.shape
    N = bmat.shape[-1]

    def step(state, inp):
        xt, dtt, at, bt, ct = inp
        state = jnp.exp(at)[..., None, None] * state + (
            (dtt[..., None] * bt)[..., :, None] * xt[..., None, :]
        )  # (BH, N, P)
        y = jnp.einsum("bn,bnp->bp", ct, state)
        return state, y

    state0 = jnp.zeros((BH, N, P), dtype=jnp.float32)
    xs = (
        jnp.moveaxis(x.astype(jnp.float32), 1, 0),
        jnp.moveaxis(dt.astype(jnp.float32), 1, 0),
        jnp.moveaxis(a_log.astype(jnp.float32), 1, 0),
        jnp.moveaxis(bmat.astype(jnp.float32), 1, 0),
        jnp.moveaxis(cmat.astype(jnp.float32), 1, 0),
    )
    _, ys = jax.lax.scan(step, state0, xs)
    return jnp.moveaxis(ys, 0, 1).astype(x.dtype)


def ssd_scan_chunked_ref(
    x: jnp.ndarray,  # (BH, S, P)
    dt: jnp.ndarray,  # (BH, S)
    a_log: jnp.ndarray,  # (BH, S)
    bmat: jnp.ndarray,  # (BH, S, N)
    cmat: jnp.ndarray,  # (BH, S, N)
    chunk: int = 128,
) -> jnp.ndarray:
    """Chunked SSD in pure jnp — the kernel's math, XLA-compiled.

    This is the DEFAULT non-Pallas path (ops.ssd_scan): a scan over S/chunk
    block steps with MXU-shaped matmuls, vs ssd_scan_ref's S sequential
    steps (kept as the bitwise oracle; it lowers to S-iteration loops that
    dominate both compile-size and wire bytes at 32k+ tokens).
    """
    BH, S, P = x.shape
    N = bmat.shape[-1]
    chunk = min(chunk, S)
    assert S % chunk == 0
    nc = S // chunk
    f32 = jnp.float32

    xc = x.reshape(BH, nc, chunk, P).astype(f32)
    dtc = dt.reshape(BH, nc, chunk, 1).astype(f32)
    ac = a_log.reshape(BH, nc, chunk, 1).astype(f32)
    bc = bmat.reshape(BH, nc, chunk, N).astype(f32)
    cc = cmat.reshape(BH, nc, chunk, N).astype(f32)

    row = jnp.arange(chunk)[:, None]
    col = jnp.arange(chunk)[None, :]

    def step(state, inp):
        xb, dtb, ab, bb, cb = inp  # (BH, chunk, ...)
        l = jnp.cumsum(ab, axis=1)  # (BH, chunk, 1)
        # mask the EXPONENT, not the exp: exp(l_i - l_j) overflows to inf
        # for i < j (l is decreasing), and where(mask, inf, 0) NaNs in bwd
        diff = jnp.where(
            row >= col, l - l.transpose(0, 2, 1), -jnp.inf
        )
        L = jnp.exp(diff)  # (BH, chunk, chunk)
        bt = bb * dtb
        cb_t = jnp.einsum("bqn,bkn->bqk", cb, bt)  # C B̃^T
        y = jnp.einsum("bqk,bkp->bqp", cb_t * L, xb)
        y += jnp.einsum("bqn,bnp->bqp", cb * jnp.exp(l), state)
        l_tot = l[:, -1:]  # (BH, 1, 1)
        decay = jnp.exp(l_tot - l)
        state = jnp.exp(l_tot[:, 0]) [..., None] * state + jnp.einsum(
            "bkn,bkp->bnp", bt * decay, xb
        )
        return state, y

    state0 = jnp.zeros((BH, N, P), f32)
    xs = tuple(
        jnp.moveaxis(t, 1, 0) for t in (xc, dtc, ac, bc, cc)
    )
    _, ys = jax.lax.scan(step, state0, xs)
    return jnp.moveaxis(ys, 0, 1).reshape(BH, S, P).astype(x.dtype)


def rmsnorm_ref(
    x: jnp.ndarray, w: jnp.ndarray, *, eps: float = 1e-6,
    plus_one: bool = False,
) -> jnp.ndarray:
    # fp32-ACCUMULATED mean-square without materializing an fp32 copy of x:
    # a full `x.astype(f32)` (or an elementwise einsum with
    # preferred_element_type, which lowers to convert→mul) as the first
    # consumer of the layer input makes XLA hoist the convert onto the
    # remat-saved carry stack — +14 GiB/dev at train_4k (EXPERIMENTS.md
    # §Perf iteration 1). A true batched dot_general accumulates bf16
    # inputs in fp32 inside the MXU without a materialized convert.
    D = x.shape[-1]
    x2 = x.reshape(-1, D)
    ms = jax.lax.dot_general(
        x2, x2,
        dimension_numbers=(((1,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    ).reshape(x.shape[:-1]) / D
    mult = jax.lax.rsqrt(ms + eps)[..., None].astype(x.dtype)
    scale = (
        (w.astype(jnp.float32) + 1.0) if plus_one else w.astype(jnp.float32)
    ).astype(x.dtype)
    return x * mult * scale


def segment_copy_ref(sources, ends, starts, tags, lo, chunk: int):
    """Oracle for the segment-copy kernel (kernels/segment_copy.py).

    Slots ``[lo, lo + chunk)`` of a hop whose live segments end at
    ``ends`` (a running sum, then the total repeated) and read
    ``sources[tag % len(sources)]`` from ``starts`` -> (ids, rows)
    int32[chunk], SENTINEL ids and row 0 past the total. Each segment's
    values (source start less its first slot, and tag), less the last
    segment's, are added at its first slot, so that a running sum gives
    every slot its segment's values; each slot's id is then one gather
    per part.
    """
    n_parts = len(sources)
    total = ends[-1]
    off = jnp.concatenate([jnp.zeros(1, ends.dtype), ends[:-1]])
    at = jnp.where(off < total, jnp.maximum(off - lo, 0), chunk)
    base, tag = (
        jnp.cumsum(jnp.zeros(chunk, jnp.int32).at[at].add(
            v - jnp.concatenate([jnp.zeros(1, jnp.int32), v[:-1]]),
            mode="drop",
        ))
        for v in (starts - off, tags)
    )
    t = lo + jnp.arange(chunk, dtype=jnp.int32)
    pos = base + t
    ids = jnp.take(sources[0], pos, mode="clip").astype(jnp.int32)
    for p in range(1, n_parts):
        got = jnp.take(sources[p], pos, mode="clip").astype(jnp.int32)
        ids = jnp.where(tag % n_parts == p, got, ids)
    inside = t < total
    return (jnp.where(inside, ids, SENTINEL),
            jnp.where(inside, tag // n_parts, 0))
