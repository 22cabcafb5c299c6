"""Pallas kernel: a chunk of a one-pass hop's slots, copied run by run.

A one-pass hop (core/traversal.py) reads, for every frontier node u, the
run ``indices[indptr[u] : indptr[u] + len]`` of its sorted neighbour ids.
The host lays the runs end to end as segments of slots and plans, for
each live segment, its slot offset, its source start and its tag (row
times the number of parts, plus the part: an overlay's base and delta
CSRs are separate sources). Slot t holds the id at source position
``src + (t - off)`` of the segment (off, src) that holds it, and that
segment's row.

The jnp way (``ref.segment_copy_ref``) spreads each segment's values over
its slots with scatters and running sums and reads every slot's id with
a scalar gather. Here the runs are copied whole. A grid step writes one
``(8, 128)`` block of 1024 slots. For every segment piece in the block
it copies, by DMA, the 2048-id window of ``indices`` that starts at the
1024-id tile holding the piece's first id (a 1-D array's HBM tiling is
1024 ids, so a copy starts on a tile and is two tiles long: a piece of
at most 1024 ids always fits), rolls the window along the lanes and
reads it back at a sublane offset, so that each id lands on its slot,
and merges it into the block under the piece's mask; the piece's row is
a broadcast. ``IN_FLIGHT`` copies are kept in flight. Each step's pieces
come from the segment table in SMEM, two 1024-entry blocks of it from the
block's first segment on (a block holds at most 1024 pieces, and a
piece's end is the next segment's offset).

The last tiles of a source, which a whole window would read past its
end, are copied from a padded copy of its last 3072 ids.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.csr import SENTINEL

# slots per grid step: one (8, 128) int32 tile
BLOCK = 1024
# ids per copy: the 1024-id tile that holds a piece's first id and the next
WINDOW = 2 * BLOCK
# copies kept in flight
IN_FLIGHT = 8
# segment table fields: offset, source start, tag
FIELDS = 3


def tail_tile(n: int) -> int:
    """The first 1024-id tile of an ``n``-id source that is copied from its
    padded tail (a window from there would read past the end)."""
    return max(n // BLOCK - 1, 0)


def source_tail(indices: jnp.ndarray) -> jnp.ndarray:
    """int32[3 * BLOCK]: ``indices`` from tile ``tail_tile`` on, padded."""
    lo = tail_tile(indices.shape[0]) * BLOCK
    tail = indices[lo : lo + 3 * BLOCK].astype(jnp.int32)
    return jnp.pad(tail, (0, 3 * BLOCK - tail.shape[0]))


def _copy_kernel(first_ref, count_ref, lo_ref, *refs, tails, n_parts):
    tabs = refs[: 2 * FIELDS]
    mains = refs[2 * FIELDS : 2 * FIELDS + n_parts]
    tail_refs = refs[2 * FIELDS + n_parts : 2 * FIELDS + 2 * n_parts]
    ids_ref, rows_ref, buf, stage, sem = refs[2 * FIELDS + 2 * n_parts :]
    j = pl.program_id(0)
    s0 = first_ref[j]
    n = count_ref[j]
    base = (s0 // BLOCK) * BLOCK
    blk = lo_ref[0] + j * BLOCK

    def field(s, f):
        loc = s - base
        in_a = loc < BLOCK
        a = tabs[2 * f][jnp.where(in_a, loc, 0)]
        b = tabs[2 * f + 1][jnp.where(in_a, 0, loc - BLOCK)]
        return jnp.where(in_a, a, b)

    def piece(i):
        """Piece i of the block -> (first, end) slots in the block, its
        first source position, its tag."""
        s = s0 + i
        off = field(s, 0)
        a = jnp.maximum(off, blk)
        b = jnp.minimum(field(s + 1, 0), blk + BLOCK)
        return a - blk, b - blk, field(s, 1) + (a - off), field(s, 2)

    def copy(src, slot):
        dst = buf.at[pl.ds(pl.multiple_of(slot * WINDOW, WINDOW), WINDOW)]
        return pltpu.make_async_copy(src, dst, sem.at[slot])

    def start(i):
        _, _, q, tag = piece(i)
        t = q // BLOCK
        part = tag % n_parts
        slot = i % IN_FLIGHT
        for p in range(n_parts):
            ts = tails[p]
            if ts > 0:
                @pl.when((part == p) & (t < ts))
                def _():
                    at = pl.multiple_of(t * BLOCK, BLOCK)
                    copy(mains[p].at[pl.ds(at, WINDOW)], slot).start()

            @pl.when((part == p) & (t >= ts))
            def _():
                at = pl.multiple_of((t - ts) * BLOCK, BLOCK)
                copy(tail_refs[p].at[pl.ds(at, WINDOW)], slot).start()

    lane = jax.lax.broadcasted_iota(jnp.int32, (8, 128), 1)
    flat = jax.lax.broadcasted_iota(jnp.int32, (8, 128), 0) * 128 + lane

    def place(i, carry):
        ids, rows = carry
        slot = i % IN_FLIGHT
        copy(tail_refs[0].at[pl.ds(0, WINDOW)], slot).wait()
        a, b, q, tag = piece(i)
        # slot a + x reads window position q - (q // 1024) * 1024 + x;
        # staged 8 rows down, block slot f reads stage position f + e
        e = q % BLOCK - a + BLOCK
        es, el = e // 128, e % 128
        win = buf[pl.ds(pl.multiple_of(slot * WINDOW, WINDOW), WINDOW)]
        stage[pl.ds(8, 16), :] = pltpu.roll(
            win.reshape(16, 128), (128 - el) % 128, 1
        )
        vals = jnp.where(lane < 128 - el, stage[pl.ds(es, 8), :],
                         stage[pl.ds(es + 1, 8), :])
        inside = (flat >= a) & (flat < b)
        return (jnp.where(inside, vals, ids),
                jnp.where(inside, tag // n_parts, rows))

    def prime(i, carry):
        start(i)
        return carry

    jax.lax.fori_loop(0, jnp.minimum(n, IN_FLIGHT - 1), prime, 0)

    def body(i, carry):
        pl.when(i + IN_FLIGHT - 1 < n)(
            functools.partial(start, i + IN_FLIGHT - 1)
        )
        return place(i, carry)

    ids, rows = jax.lax.fori_loop(
        0, n, body,
        (jnp.full((8, 128), SENTINEL, jnp.int32),
         jnp.zeros((8, 128), jnp.int32)),
    )
    ids_ref[...] = ids
    rows_ref[...] = rows


def table_len(segments: int) -> int:
    """Entries of the SMEM table for ``segments`` segments: one past the
    last, and two 1024-entry blocks from the last one's block on."""
    return (segments // BLOCK + 2) * BLOCK


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def segment_copy_kernel(sources, ends, starts, tags, lo, *, chunk,
                        interpret=True):
    """Slots ``[lo, lo + chunk)`` of a hop -> (ids int32[chunk], rows
    int32[chunk]); slots past the hop's last segment hold SENTINEL ids and
    row 0.

    ``sources``: a tuple of 1-D id arrays, one per part. ``ends``
    int32[S]: the running sum of the live segments' lengths (each at least
    1), then the total repeated. ``starts`` and ``tags`` int32[S]: each
    live segment's source start and tag (row * len(sources) + part).
    ``chunk`` is a multiple of 1024.
    """
    n_parts = len(sources)
    if chunk % BLOCK:
        raise ValueError(f"chunk {chunk} is not a multiple of {BLOCK}")
    S = ends.shape[0]
    L = table_len(S)
    total = ends[-1]
    nseg = jnp.minimum(jnp.searchsorted(ends, total, side="left") + 1, S)
    # offsets, with the total from entry nseg on
    off = jnp.concatenate([jnp.zeros(1, jnp.int32), ends,
                           jnp.full(L - S - 1, total, jnp.int32)])
    table = jnp.concatenate([
        off, jnp.pad(starts, (0, L - S)), jnp.pad(tags, (0, L - S)),
    ]).astype(jnp.int32)
    nblk = chunk // BLOCK
    bounds = lo + BLOCK * jnp.arange(nblk + 1, dtype=jnp.int32)
    first = jnp.clip(jnp.searchsorted(off, bounds[:-1], side="right") - 1,
                     0, nseg)
    count = jnp.minimum(jnp.searchsorted(off, bounds[1:], side="left"),
                        nseg) - first
    srcs = [s.astype(jnp.int32) for s in sources]
    tails = tuple(tail_tile(s.shape[0]) for s in srcs)

    def tab_spec(f, shift):
        return pl.BlockSpec(
            (BLOCK,),
            lambda j, first, count, lo: (f * L // BLOCK + first[j] // BLOCK
                                         + shift,),
            memory_space=pltpu.SMEM,
        )

    out_spec = pl.BlockSpec((8, 128), lambda j, first, count, lo: (j, 0))
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    ids, rows = pl.pallas_call(
        functools.partial(_copy_kernel, tails=tails, n_parts=n_parts),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(nblk,),
            in_specs=[tab_spec(f, shift) for f in range(FIELDS)
                      for shift in (0, 1)] + [any_spec] * (2 * n_parts),
            out_specs=[out_spec, out_spec],
            scratch_shapes=[
                pltpu.VMEM((IN_FLIGHT * WINDOW,), jnp.int32),
                pltpu.VMEM((32, 128), jnp.int32),
                pltpu.SemaphoreType.DMA((IN_FLIGHT,)),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct((chunk // 128, 128), jnp.int32)] * 2,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        interpret=interpret,
        name="segment_copy",
    )(first.astype(jnp.int32), count.astype(jnp.int32),
      jnp.reshape(lo, (1,)).astype(jnp.int32), *([table] * (2 * FIELDS)),
      *srcs, *(source_tail(s) for s in srcs))
    return ids.reshape(-1), rows.reshape(-1)
