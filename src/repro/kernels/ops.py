"""Public jit'd wrappers around the Pallas kernels.

Each op pads/aligns inputs to kernel block requirements, dispatches to the
kernel (interpret=True on CPU — the validation mode; always compiled on
TPU), and slices the result back. ``use_pallas=False`` takes the jnp oracle,
which is also what the distributed dry-run lowers (kernel bodies are a TPU
runtime concern, not a sharding concern).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.csr import SENTINEL, on_tpu as _on_tpu, sorted_isin
from . import ref
from .frontier import frontier_kernel
from .intersect import LANES, intersect_count_kernel
from .segmented_union import segmented_union_kernel
from .flash_attention import flash_attention_kernel
from .rmsnorm import rmsnorm_kernel
from .ssd_scan import ssd_scan_kernel


def _interpret(interpret: bool | None) -> bool:
    """Interpret mode is the CPU validation mode; on a TPU kernels compile.

    ``None`` picks from the backend. An explicit ``True`` on a TPU is an
    error, never a silent fallback: a kernel that runs there runs compiled.
    """
    if interpret is None:
        return not _on_tpu()
    if interpret and _on_tpu():
        raise ValueError("interpret=True on a TPU: kernels compile there")
    return bool(interpret)


def _pad_to(x: jnp.ndarray, axis: int, multiple: int, fill) -> jnp.ndarray:
    size = x.shape[axis]
    rem = (-size) % multiple
    if rem == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, rem)
    return jnp.pad(x, pad, constant_values=fill)


# ---------------------------------------------------------------------------
# intersect (pseudo-projection hot path)
# ---------------------------------------------------------------------------


def intersect_count(
    a: jnp.ndarray,
    b: jnp.ndarray,
    *,
    use_pallas: bool = True,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Batched |row∩row| for SENTINEL-padded sorted rows -> int32[B]."""
    if not use_pallas:
        return ref.intersect_count_ref(a, b)
    interpret = _interpret(interpret)
    B = a.shape[0]
    a = _pad_to(_pad_to(a, 1, 128, SENTINEL), 0, LANES, SENTINEL)
    b = _pad_to(_pad_to(b, 1, 128, SENTINEL), 0, LANES, SENTINEL)
    out = intersect_count_kernel(a, b, interpret=interpret)
    return out[:B]


def pseudo_edge_value(
    layer,
    u: jnp.ndarray,
    v: jnp.ndarray,
    *,
    use_pallas: bool = True,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Kernel-accelerated LayerTwoMode.edge_value (GetEdgeValue)."""
    a, am = layer.memberships(u)
    b, bm = layer.memberships(v)
    a = jnp.where(am, a, SENTINEL)
    b = jnp.where(bm, b, SENTINEL)
    return intersect_count(
        a, b, use_pallas=use_pallas, interpret=interpret
    ).astype(jnp.float32)


# ---------------------------------------------------------------------------
# segmented union (pseudo-projection GetNodeAlters hot path)
# ---------------------------------------------------------------------------


def segmented_union(
    flat: jnp.ndarray,
    max_out: int,
    *,
    use_pallas: bool = True,
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Dedup + sort + compact SENTINEL-padded rows -> (int32[B, max_out], mask).

    Pallas path: all-pairs first-occurrence + rank kernel, then a single
    scatter places each unique value at its sorted position (no sort).
    Fallback: the padded_unique double-sort. Both cap at ``max_out``
    smallest unique values — bit-identical outputs.
    """
    if not use_pallas:
        return _union_sort(flat, max_out)
    return _union_pallas(flat, max_out, _interpret(interpret))


@functools.partial(jax.jit, static_argnames=("max_out",))
def _union_sort(flat, max_out):
    return ref.segmented_union_ref(flat, max_out)


def _place_kept(rows, kept, rank, max_out, batch_shape):
    """Scatter each kept value of ``rows`` to its rank -> (vals, mask)."""
    B = int(np.prod(batch_shape, dtype=np.int64))
    keep = (kept > 0) & (rank < max_out)
    val = jnp.where(keep, rows, SENTINEL)
    pos = jnp.clip(rank, 0, max_out - 1)
    out = jnp.full((rows.shape[0], max_out), SENTINEL, jnp.int32)
    out = out.at[jnp.arange(rows.shape[0])[:, None], pos].min(val)
    out = out[:B].reshape(batch_shape + (max_out,))
    return out, out != SENTINEL


@functools.partial(jax.jit, static_argnames=("max_out", "interpret"))
def _union_pallas(flat, max_out, interpret):
    f2 = flat.reshape((-1, flat.shape[-1]))
    fp = _pad_to(_pad_to(f2, 1, 128, SENTINEL), 0, LANES, SENTINEL)
    kept, rank = segmented_union_kernel(fp, interpret=interpret)
    return _place_kept(fp, kept, rank, max_out, flat.shape[:-1])


def frontier_compact(
    cand: jnp.ndarray,
    visited: jnp.ndarray,
    max_out: int,
    *,
    use_pallas: bool = True,
    interpret: bool | None = None,
    visited_sorted: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Next-BFS-frontier compaction -> (int32[..., max_out], mask).

    Keeps the first occurrence of every SENTINEL-padded candidate that is
    not present in the matching ``visited`` row, sorted ascending and
    capped at ``max_out`` — the k-hop traversal inner step. Pallas path:
    the all-pairs first-occurrence + rank kernel with a visited-row
    exclusion pass, then one scatter (no sort). Fallback: the
    ``frontier_ref`` sort path. Bit-identical outputs either way.

    ``visited_sorted=True`` promises each visited row is already sorted
    ascending (SENTINEL pads last) — callers compacting several candidate
    chunks against one visited buffer sort it once, not per chunk.
    """
    if cand.shape[:-1] != visited.shape[:-1]:
        raise ValueError(f"batch mismatch {cand.shape} vs {visited.shape}")
    if not use_pallas:
        return _frontier_sort(cand, visited, max_out, visited_sorted)
    return _frontier_pallas(cand, visited, max_out, _interpret(interpret))


@functools.partial(jax.jit, static_argnames=("max_out", "visited_sorted"))
def _frontier_sort(cand, visited, max_out, visited_sorted):
    # Production jnp path: sort the visited row and exclude by binary
    # search (O(Kc log Kv)), then the double-sort dedup. The all-pairs
    # ``frontier_ref`` oracle is O(Kc*Kv) — it exists for obvious
    # correctness, not speed — outputs are bit-identical.
    valid = cand != SENTINEL
    vs = visited if visited_sorted else jnp.sort(visited, axis=-1)
    seen = sorted_isin(cand, valid, vs, vs != SENTINEL)
    flat = jnp.where(valid & ~seen, cand, SENTINEL)
    return ref.segmented_union_ref(flat, max_out)


@functools.partial(jax.jit, static_argnames=("max_out", "interpret"))
def _frontier_pallas(cand, visited, max_out, interpret):
    c2 = cand.reshape((-1, cand.shape[-1]))
    v2 = visited.reshape((-1, visited.shape[-1]))
    cp = _pad_to(_pad_to(c2, 1, 128, SENTINEL), 0, LANES, SENTINEL)
    vp = _pad_to(_pad_to(v2, 1, 128, SENTINEL), 0, LANES, SENTINEL)
    kept, rank = frontier_kernel(cp, vp, interpret=interpret)
    return _place_kept(cp, kept, rank, max_out, cand.shape[:-1])


def pseudo_node_alters(
    layer,
    u: jnp.ndarray,
    max_alters: int,
    *,
    width_m: int | None = None,
    width_n: int | None = None,
    node_filter: jnp.ndarray | None = None,
    use_pallas: bool = True,
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Kernel-accelerated LayerTwoMode.node_alters (GetNodeAlters).

    ``width_m`` / ``width_n`` override the two-hop gather pad widths
    (membership count / hyperedge size); the bucketed dispatcher passes
    per-bucket widths, None means the layer-global maxima.

    ``node_filter`` (bool[n_nodes]) drops gathered co-members failing an
    attribute predicate *before* the union — the filtered query stays at
    the same gather width and the ``max_alters`` cap applies post-filter.
    """
    he, he_mask = layer.memberships(u, width_m)
    wn = layer.max_hyperedge_size if width_n is None else max(width_n, 1)
    mem, mem_mask = layer.member_rows(jnp.where(he_mask, he, 0), wn)
    mem_mask = mem_mask & he_mask[..., None]
    if node_filter is not None:
        mem_mask = mem_mask & jnp.take(node_filter, mem, mode="clip")
    flat = jnp.where(mem_mask, mem, SENTINEL).reshape(u.shape + (-1,))
    flat = jnp.where(flat == u[..., None], SENTINEL, flat)  # drop ego
    return segmented_union(
        flat, max_alters, use_pallas=use_pallas, interpret=interpret
    )


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


def flash_attention(
    q: jnp.ndarray,  # (B, Hq, S, D)
    k: jnp.ndarray,  # (B, Hkv, S, D)
    v: jnp.ndarray,  # (B, Hkv, S, D)
    *,
    causal: bool = True,
    scale: float | None = None,
    block_q: int = 128,
    block_k: int = 128,
    use_pallas: bool = True,
    interpret: bool | None = None,
) -> jnp.ndarray:
    B, Hq, S, D = q.shape
    Hkv = k.shape[1]
    if Hq % Hkv:
        raise ValueError(f"q heads {Hq} not a multiple of kv heads {Hkv}")
    group = Hq // Hkv
    if scale is None:
        scale = 1.0 / float(np.sqrt(D))
    qf = q.reshape(B * Hq, S, D)
    kf = k.reshape(B * Hkv, S, D)
    vf = v.reshape(B * Hkv, S, D)
    if not use_pallas:
        out = ref.attention_ref(qf, kf, vf, scale=scale, causal=causal,
                                kv_group=group)
        return out.reshape(B, Hq, S, D)
    interpret = _interpret(interpret)
    bq = min(block_q, S)
    bk = min(block_k, S)
    out = flash_attention_kernel(
        qf, kf, vf, scale=scale, causal=causal, kv_group=group,
        block_q=bq, block_k=bk, interpret=interpret,
    )
    return out.reshape(B, Hq, S, D)


# ---------------------------------------------------------------------------
# Mamba2 SSD scan
# ---------------------------------------------------------------------------


def ssd_scan(
    x: jnp.ndarray,  # (B, H, S, P)
    dt: jnp.ndarray,  # (B, H, S)
    a_log: jnp.ndarray,  # (B, H, S)
    bmat: jnp.ndarray,  # (B, S, N) shared single group
    cmat: jnp.ndarray,  # (B, S, N)
    *,
    chunk: int = 128,
    use_pallas: bool = True,
    interpret: bool | None = None,
) -> jnp.ndarray:
    B, H, S, P = x.shape
    N = bmat.shape[-1]
    xf = x.reshape(B * H, S, P)
    dtf = dt.reshape(B * H, S)
    af = a_log.reshape(B * H, S)
    bf = jnp.repeat(bmat[:, None], H, axis=1).reshape(B * H, S, N)
    cf = jnp.repeat(cmat[:, None], H, axis=1).reshape(B * H, S, N)
    if not use_pallas:
        if S % min(chunk, S) == 0:
            out = ref.ssd_scan_chunked_ref(
                xf, dtf, af, bf, cf, chunk=min(chunk, S)
            )
        else:
            out = ref.ssd_scan_ref(xf, dtf, af, bf, cf)
        return out.reshape(B, H, S, P)
    interpret = _interpret(interpret)
    ck = min(chunk, S)
    if S % ck:
        raise ValueError(f"seq {S} not a multiple of chunk {ck}")
    out = ssd_scan_kernel(xf, dtf, af, bf, cf, chunk=ck, interpret=interpret)
    return out.reshape(B, H, S, P)


# ---------------------------------------------------------------------------
# fused RMSNorm
# ---------------------------------------------------------------------------


def rmsnorm(
    x: jnp.ndarray,  # (..., D)
    w: jnp.ndarray,  # (D,)
    *,
    eps: float = 1e-6,
    plus_one: bool = False,
    use_pallas: bool = True,
    interpret: bool | None = None,
) -> jnp.ndarray:
    if not use_pallas:
        return ref.rmsnorm_ref(x, w, eps=eps, plus_one=plus_one)
    interpret = _interpret(interpret)
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    R = x2.shape[0]
    x2 = _pad_to(x2, 0, 8, 0)
    out = rmsnorm_kernel(
        x2, w, eps=eps, plus_one=plus_one, interpret=interpret
    )
    return out[:R].reshape(shape)
