"""Pallas kernel: segmented union (dedup + rank) of padded id rows.

This is the pseudo-projection ``GetNodeAlters`` inner loop: after the
two-hop gather (node -> hyperedges -> co-members) each query row holds up
to Km*Kn candidate alters with duplicates (nodes sharing several
hyperedges with the ego). The jnp reference dedups by sorting the row
TWICE (``padded_unique``); sorts are lane-serial on the VPU and their cost
is set by the *global* padded width.

TPU adaptation: for bucketed widths (core/dispatch.py) the row is small
enough that **all-pairs compares** beat sorting, exactly like the
intersect kernel. Two O(K^2) passes over a resident row:

  pass 1  kept[i]  = valid[i] & no j<i with row[j] == row[i]   (first occurrence)
  pass 2  rank[i]  = #{ j : kept[j] & row[j] < row[i] }        (rank among uniques)

``kept``/``rank`` let the caller place each unique value directly at its
sorted position with one scatter — no sort at all.

Layout (as in kernels/intersect.py): the batch sits on the 128 lanes and
the row slots on the sublanes, so a grid step holds a ``(K, LANES)``
block and each pass loops over the K slots, broadcasting slot j of every
row (a ``(1, LANES)`` ref-side slice) down the sublanes in one 2-D
lane-dense compare. Padding is SENTINEL on the input; SENTINEL slots are
never kept and never compare less-than a real value.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.csr import SENTINEL
from .intersect import LANES, vmem_limit


def first_occurrence(row, slot_ref, dup):
    """OR into ``dup`` every slot i whose value occurs at some slot j < i.

    ``row`` is the loaded ``(K, LANES)`` block and ``slot_ref`` its ref;
    ``dup`` an int32 0/1 array of the same shape.
    """
    pos = jax.lax.broadcasted_iota(jnp.int32, row.shape, 0)

    def body(j, dup):
        vj = slot_ref[pl.ds(j, 1), :]
        return dup | jnp.where((row == vj) & (pos > j), 1, 0)

    return jax.lax.fori_loop(0, row.shape[0], body, dup)


def unique_rank(row, slot_ref, kept_ref):
    """rank[i] = #{j : kept[j] & row[j] < row[i]} over the K slots."""

    def body(j, rank):
        vj = slot_ref[pl.ds(j, 1), :]
        kj = kept_ref[pl.ds(j, 1), :]
        return rank + jnp.where((vj < row) & (kj > 0), 1, 0)

    return jax.lax.fori_loop(
        0, row.shape[0], body, jnp.zeros(row.shape, jnp.int32)
    )


def _union_kernel(v_ref, kept_ref, rank_ref):
    row = v_ref[...]  # (K, LANES) int32, SENTINEL-padded, unsorted
    dup = first_occurrence(row, v_ref, jnp.zeros(row.shape, jnp.int32))
    kept_ref[...] = jnp.where((row != SENTINEL) & (dup == 0), 1, 0)
    rank_ref[...] = unique_rank(row, v_ref, kept_ref)


@functools.partial(jax.jit, static_argnames=("interpret",))
def segmented_union_kernel(
    flat: jnp.ndarray,
    *,
    interpret: bool = True,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-row first-occurrence mask and unique-value rank.

    flat: int32[B, K] SENTINEL-padded (unsorted); B must be a multiple of
    LANES and K of 8 (ops.py wrapper pads). Returns (kept int32[B, K] 0/1,
    rank int32[B, K]); ``rank`` of a kept element is the number of
    distinct smaller values in the row, i.e. its position in the
    sorted-unique output.
    """
    B, K = flat.shape
    if B % LANES or K % 8:
        raise ValueError(f"unaligned shape {flat.shape}")

    spec = pl.BlockSpec((K, LANES), lambda i: (0, i))
    kept, rank = pl.pallas_call(
        _union_kernel,
        grid=(B // LANES,),
        in_specs=[spec],
        out_specs=[spec, spec],
        out_shape=[
            jax.ShapeDtypeStruct((K, B), jnp.int32),
            jax.ShapeDtypeStruct((K, B), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit(3 * K * LANES * 4)
        ),
        interpret=interpret,
        name="segmented_union",
    )(flat.T)
    return kept.T, rank.T
