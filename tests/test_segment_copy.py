"""The segment-copy kernel (interpret mode) against ``ref``'s oracle.

A case is a list of segments ``(part, source start, length, row)`` laid
end to end in slot order, as ``traversal._one_pass_rows`` plans a hop,
and the chunks ``[lo, lo + chunk)`` to expand. Every chunk's ids and
rows must equal the oracle's, and the oracle's must equal a plain
slot-by-slot reading of the segments.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.csr import SENTINEL
from repro.kernels import ops, ref
from repro.kernels.segment_copy import BLOCK

SIZES = (20_000, 1_500)  # a base part and a smaller overlay delta
SOURCES = tuple(
    jnp.asarray(np.random.default_rng(7 + p).integers(0, 1 << 22, n),
                jnp.int32)
    for p, n in enumerate(SIZES)
)


def _lengths():
    # runs of 0, 1, 127, 128, 129 and over 4096 ids
    lens = [0, 1, 127, 128, 129, 5000, 0, 3]
    return [(0, 1000 * i + 37, n, i % 4) for i, n in enumerate(lens)]


def _across_chunks():
    # the first run starts before the second chunk's lo and ends in it,
    # the second crosses the second chunk's end
    return [(0, 11, 3000, 0), (0, 9000, 2500, 1), (0, 400, 10, 2)]


def _lane_offsets():
    # a source start at every lane offset mod 128, in every sublane
    return [(0, 1024 * (i % 17) + 128 * (i % 8) + i, 5 + i % 3, i % 32)
            for i in range(128)]


def _two_parts():
    # base and delta runs interleaved by row, part-major as planned; the
    # delta is shorter than two tiles, and runs reach both sources' ends
    base = [(0, 3 * i + 5, 40 + i, i) for i in range(20)]
    base += [(0, SIZES[0] - 700, 700, 20)]
    delta = [(1, 17 * i, 1 + 9 * i, i) for i in range(20)]
    delta += [(1, SIZES[1] - 1400, 1400, 21)]
    return base + delta


def _source_tail():
    # runs in and ending at the source's last tiles
    n = SIZES[0]
    return [(0, n - 1, 1, 0), (0, n - 2100, 2100, 1), (0, n - 1030, 900, 2)]


CASES = {
    "lengths": (_lengths(), 2048, None),
    "across_chunks": (_across_chunks(), 4096, None),
    "lane_offsets": (_lane_offsets(), 1024, None),
    "two_parts": (_two_parts(), 2048, None),
    "source_tail": (_source_tail(), 1024, None),
    # a chunk that lies wholly past the hop's last slot
    "padding_only": (_lengths()[:3], 1024, [1024, 4096]),
}


def _table(segs, n_parts):
    """-> (ends, starts, tags) as the host plans them: per segment, where
    its slots end, its source start and row * parts + part; the total
    repeated after them."""
    S = len(segs) + 5
    lens = np.array([n for _, _, n, _ in segs], np.int64)
    ends = np.full(S, lens.sum(), np.int32)
    ends[: len(segs)] = np.cumsum(lens)
    starts = np.zeros(S, np.int32)
    tags = np.zeros(S, np.int32)
    for i, (p, s, _, r) in enumerate(segs):
        starts[i], tags[i] = s, r * n_parts + p
    return ends, starts, tags


def _plain(segs, sources, lo, chunk):
    """Slot by slot: each segment's run, then padding."""
    ids = [int(x) for p, s, n, _ in segs for x in sources[p][s : s + n]]
    rows = [r for _, _, n, r in segs for _ in range(n)]
    want_ids = np.full(chunk, SENTINEL, np.int32)
    want_rows = np.zeros(chunk, np.int32)
    got = np.asarray(ids[lo : lo + chunk], np.int32)
    want_ids[: got.size] = got
    want_rows[: got.size] = rows[lo : lo + chunk]
    return want_ids, want_rows


@pytest.mark.parametrize("case", sorted(CASES))
def test_segment_copy_matches_the_oracle(case):
    segs, chunk, los = CASES[case]
    n_parts = 1 + max(p for p, _, _, _ in segs)
    sources = SOURCES[:n_parts]
    host = [np.asarray(s) for s in sources]
    ends, starts, tags = _table(segs, n_parts)
    total = int(ends[-1])
    if los is None:
        los = range(0, total, chunk)
    assert chunk % BLOCK == 0
    for lo in los:
        args = (sources, jnp.asarray(ends), jnp.asarray(starts),
                jnp.asarray(tags), jnp.int32(lo))
        ids, rows = ops.segment_copy(*args, chunk=chunk, interpret=True)
        want_ids, want_rows = ref.segment_copy_ref(*args, chunk)
        plain_ids, plain_rows = _plain(segs, host, lo, chunk)
        np.testing.assert_array_equal(np.asarray(want_ids), plain_ids)
        np.testing.assert_array_equal(np.asarray(want_rows), plain_rows)
        np.testing.assert_array_equal(np.asarray(ids), plain_ids, f"lo {lo}")
        np.testing.assert_array_equal(np.asarray(rows), plain_rows,
                                      f"lo {lo}")
