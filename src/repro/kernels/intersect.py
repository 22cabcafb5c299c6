"""Pallas kernel: batched hyperedge-membership intersection.

This is the pseudo-projection inner loop (paper Listing 1:
``CheckEdgeExists`` / ``GetEdgeValue``): given two batches of *sorted,
padded* membership rows, count shared hyperedges per row pair.

TPU adaptation (DESIGN.md §2): the C# engine early-exits a hash-set probe;
TPUs have no hash units and win by batching. For register-data regimes
(mean ~20 memberships/node, rows padded to 128) an **all-pairs equality
compare on the VPU** is a few thousand 1-cycle ops per query and beats any
serialized merge.

Layout: the batch sits on the 128 lanes and the membership slots on the
sublanes, so every compare is a 2-D lane-dense ``(Ka, 128)`` op. Inside
the kernel ``a`` is ``(Ka, LANES)`` — column c is batch row c's list —
and one row of ``b`` at a time (a ``(1, LANES)`` ref-side slice)
broadcasts down the sublanes against it:

  grid = (B / LANES, Kb / block_k)
  a tile: (Ka, LANES)       — kept resident across the k-sweep
  b tile: (block_k, LANES)
  out:    (1, LANES) accumulated across the k grid dimension
          (TPU 'revisiting output' reduction pattern)

Padding uses SENTINEL (int32 max) on BOTH sides; sentinel==sentinel matches
are masked out by validity of the `a` side only (a pad never matches a real
b value, and a pad vs b pad is excluded by the a-mask).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.csr import SENTINEL

# Batch rows per grid step: one per lane.
LANES = 128
DEFAULT_BLOCK_K = 128


def vmem_limit(*block_bytes: int) -> int:
    """Scoped-VMEM request for kernels whose blocks scale with K.

    Every in/out block is double-buffered by the pipeline and the loop
    bodies hold a few block-sized temporaries, so ask for four times the
    blocks, never less than Mosaic's 16 MiB default and within the
    128 MiB of VMEM a v5e core has.
    """
    return int(min(max(4 * sum(block_bytes), 16 << 20), 100 << 20))


def _intersect_kernel(a_ref, b_ref, o_ref):
    """Accumulate |a_col ∩ b_tile_col| into o_ref across the k grid dim."""
    k = pl.program_id(1)
    a = a_ref[...]  # (Ka, LANES) int32, sorted down each column

    def body(j, acc):
        bj = b_ref[pl.ds(j, 1), :]  # (1, LANES): slot j of every b row
        return acc + jnp.where(a == bj, 1, 0)

    acc = jax.lax.fori_loop(
        0, b_ref.shape[0], body, jnp.zeros(a.shape, jnp.int32)
    )
    partial = jnp.sum(
        jnp.where(a != SENTINEL, acc, 0), axis=0, keepdims=True
    )

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += partial


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def intersect_count_kernel(
    a: jnp.ndarray,
    b: jnp.ndarray,
    *,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = True,
) -> jnp.ndarray:
    """Count per-row sorted-set intersections.

    a: int32[B, Ka], b: int32[B, Kb] — sorted rows, SENTINEL padding.
    B must be a multiple of LANES, Ka of 8 and Kb of block_k (the ops.py
    wrapper pads). Returns int32[B].
    """
    B, Ka = a.shape
    _, Kb = b.shape
    if B % LANES or Ka % 8 or Kb % block_k:
        raise ValueError(f"unaligned shapes {a.shape} / {b.shape}")

    out = pl.pallas_call(
        _intersect_kernel,
        grid=(B // LANES, Kb // block_k),
        in_specs=[
            pl.BlockSpec((Ka, LANES), lambda i, k: (0, i)),
            pl.BlockSpec((block_k, LANES), lambda i, k: (k, i)),
        ],
        out_specs=pl.BlockSpec((1, LANES), lambda i, k: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, B), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit(Ka * LANES * 4)
        ),
        interpret=interpret,
        name="intersect",
    )(a.T, b.T)
    return out[0]
