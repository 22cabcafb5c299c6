"""Pallas kernel: frontier dedup/compaction for batched multi-source BFS.

This is the k-hop traversal inner loop (core/traversal.py): after one
frontier expansion each source row holds up to F*cap candidate next-hop
nodes — the concatenated per-bucket ``node_alters`` outputs — with
duplicates (nodes reached from several frontier nodes) and revisits
(nodes already collected in an earlier hop). The next frontier is the
first occurrence of every candidate that is NOT in the visited row.

Same machinery as the segmented-union kernel (all-pairs compares beat
sorting at bucketed widths), plus a pass over the visited row:

  pass 0  seen[i] = any v in visited row with v == cand[i]
  pass 1  kept[i] = valid[i] & ~seen[i] & no j<i with cand[j] == cand[i]
  pass 2  rank[i] = #{ j : kept[j] & cand[j] < cand[i] }

``kept``/``rank`` let the caller place each surviving candidate at its
sorted position with one scatter — sort-free, like segmented_union.
Same batch-on-lanes layout: a grid step holds the candidate block
``(Kc, LANES)`` and the visited block ``(Kv, LANES)``, and every pass
broadcasts one slot row at a time down the sublanes. Padding is SENTINEL
in both inputs; SENTINEL candidates are never kept, and a SENTINEL
visited slot never matches a real candidate.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.csr import SENTINEL
from .intersect import LANES, vmem_limit
from .segmented_union import first_occurrence, unique_rank


def _frontier_kernel(c_ref, v_ref, kept_ref, rank_ref):
    cand = c_ref[...]  # (Kc, LANES) int32, SENTINEL-padded, unsorted

    def seen_body(j, seen):
        return seen | jnp.where(cand == v_ref[pl.ds(j, 1), :], 1, 0)

    seen = jax.lax.fori_loop(
        0, v_ref.shape[0], seen_body, jnp.zeros(cand.shape, jnp.int32)
    )
    drop = first_occurrence(cand, c_ref, seen)
    kept_ref[...] = jnp.where((cand != SENTINEL) & (drop == 0), 1, 0)
    rank_ref[...] = unique_rank(cand, c_ref, kept_ref)


@functools.partial(jax.jit, static_argnames=("interpret",))
def frontier_kernel(
    cand: jnp.ndarray,
    visited: jnp.ndarray,
    *,
    interpret: bool = True,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-row first-occurrence-not-visited mask and surviving-value rank.

    cand: int32[B, Kc] SENTINEL-padded (unsorted, duplicates allowed);
    visited: int32[B, Kv] SENTINEL-padded (any order). B must be a
    multiple of LANES and Kc/Kv of 8 (ops.py wrapper pads). Returns
    (kept int32[B, Kc] 0/1, rank int32[B, Kc]); ``rank`` of a kept
    element is its position in the sorted compacted frontier.
    """
    B, Kc = cand.shape
    Bv, Kv = visited.shape
    if B != Bv:
        raise ValueError(f"batch mismatch {cand.shape} vs {visited.shape}")
    if B % LANES or Kc % 8 or Kv % 8:
        raise ValueError(f"unaligned shapes {cand.shape} / {visited.shape}")

    cspec = pl.BlockSpec((Kc, LANES), lambda i: (0, i))
    kept, rank = pl.pallas_call(
        _frontier_kernel,
        grid=(B // LANES,),
        in_specs=[cspec, pl.BlockSpec((Kv, LANES), lambda i: (0, i))],
        out_specs=[cspec, cspec],
        out_shape=[
            jax.ShapeDtypeStruct((Kc, B), jnp.int32),
            jax.ShapeDtypeStruct((Kc, B), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit((3 * Kc + Kv) * LANES * 4)
        ),
        interpret=interpret,
    )(cand.T, visited.T)
    return kept.T, rank.T
