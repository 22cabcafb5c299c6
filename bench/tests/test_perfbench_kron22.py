"""The kron22 configuration, its khop cell and its four per-layer readers.

* the generator is the seed's alone: one seed gives one edge list, and
  every seed gives ``edge_factor * 2**scale`` edges over a permutation
  of the labels;
* the program's network holds the raw list, row for row, and so does the
  configuration's plain reference (``CsrRaw``);
* a short rehearsal of ``kron22.khop`` on the CPU is sound, and a fault
  that drops one candidate per hop is caught by the comparison;
* the readers of ``hop_ms``, ``cand_pad``, ``hop_launches`` and
  ``hbm_roofline`` read the window's change and are silent without it.
"""

from __future__ import annotations

import contextlib
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import numpy as np  # noqa: E402
import pytest  # noqa: E402

import netbuild  # noqa: E402
from manifest import Cell, load_module  # noqa: E402

run = load_module(BENCH / "run.py")
kron = load_module(BENCH / "configs" / "kron22.py")

CELL = "kron22.khop"


def _cfg(**size) -> dict:
    cfg = json.loads((BENCH / "configs" / "kron22.json").read_text())
    cfg.update(cfg.pop("rehearsal"))
    cfg.update(size)
    return cfg


def _rows(indptr, indices) -> list[set]:
    indptr, indices = np.asarray(indptr), np.asarray(indices)
    return [set(indices[a:b].tolist())
            for a, b in zip(indptr[:-1], indptr[1:])]


def _raw_rows(src, dst, n) -> list[set]:
    out: list[set] = [set() for _ in range(n)]
    for u, v in zip(np.asarray(src).tolist(), np.asarray(dst).tolist()):
        if u != v:
            out[u].add(v)
            out[v].add(u)
    return out


@pytest.mark.parametrize("seed", [5, 2**31 + 77])
def test_generator_is_the_seeds_alone(seed):
    cfg = _cfg()
    n, m = 1 << cfg["scale"], cfg["edge_factor"] << cfg["scale"]
    a = kron.generate(cfg, seed).layers[cfg["layer"]]
    b = kron.generate(cfg, seed).layers[cfg["layer"]]
    other = kron.generate(cfg, seed + 1).layers[cfg["layer"]]
    np.testing.assert_array_equal(a.src, b.src)
    np.testing.assert_array_equal(a.dst, b.dst)
    for layer in (a, other):
        assert layer.src.size == layer.dst.size == m
        assert 0 <= layer.src.min() and layer.src.max() < n
    assert not np.array_equal(a.src, other.src)
    # the labels are permuted from the seed: another seed puts its
    # largest hub on another label
    assert np.argmax(np.diff(a.indptr)) != np.argmax(np.diff(other.indptr))


@pytest.mark.parametrize("scale", [8, 10])
def test_network_and_reference_hold_the_raw_list(scale):
    cfg = _cfg(scale=scale)
    plain = kron.generate(cfg, 31)
    raw = plain.layers[cfg["layer"]]
    want = _raw_rows(raw.src, raw.dst, plain.n_nodes)
    net = netbuild.network(plain)
    assert list(net.layer_names) == [cfg["layer"]]
    layer = net.layer(cfg["layer"])
    assert layer.mode == 1 and not layer.directed
    assert _rows(layer.out.indptr, layer.out.indices) == want
    assert _rows(raw.indptr, raw.indices) == want
    for u in (0, int(np.argmax(np.diff(raw.indptr)))):
        assert raw.alters(u).tolist() == sorted(want[u])
        assert raw.degree(u) == len(want[u])


def _drop_one_candidate_per_hop():
    """Each hop's program is told one candidate fewer: the last neighbour
    of the last frontier node that has any."""
    from repro.core import traversal

    hop = traversal._hop_expand

    def short(layers, nf, src, groups, h, ends, **kw):
        ends = np.array(ends)
        last = np.flatnonzero(np.diff(np.concatenate([[0], ends])) > 0)[-1]
        ends[last:] -= 1
        return hop(layers, nf, src, groups, h, ends, **kw)

    return _patched(traversal, "_hop_expand", short)


@contextlib.contextmanager
def _patched(owner, attr, value):
    old = getattr(owner, attr)
    setattr(owner, attr, value)
    try:
        yield
    finally:
        setattr(owner, attr, old)


@pytest.mark.parametrize("control,sound", [
    ("sound", True),
    ("drop_one_candidate", False),
])
def test_rehearsal_separates_sound_from_broken(control, sound):
    fault = (contextlib.nullcontext() if control == "sound"
             else _drop_one_candidate_per_hop())
    with fault:
        out = run.run_cell(Cell(CELL), 20261 + len(control), 2.0, False,
                           rehearse=True)
    assert out["checked"] > 0
    assert out["sound"] is sound, out["line"]["checks"]
    assert out["line"]["correct"] is False  # a CPU rehearsal


MS = 1_000_000  # ns


def _snap(hop_calls, hop_ns, counters) -> dict:
    return {"batches": {"khop": 1}, "dispatched": {"khop": 1},
            "trace": {"spans": {"threadle.traversal.hop": [hop_calls,
                                                           hop_ns]},
                      "counters": dict(counters)}}


BEFORE = _snap(10, 100 * MS, {"traversal.hops": 10,
                              "traversal.cand": 1_000,
                              "traversal.slots": 4_000,
                              "traversal.launches": 10})
AFTER = _snap(210, 900 * MS, {"traversal.hops": 210,
                              "traversal.cand": 501_000,
                              "traversal.slots": 1_004_000,
                              "traversal.launches": 250})
EXPECTED = {
    "hop_ms.khop": 800 / 200,
    "cand_pad.khop": 1_000_000 / 500_000,
    "hop_launches.khop": 240 / 200,
}
TRACE = {"busy_s": 2.0, "window_s": 50.0}
PEAKS = {"hbm_bytes_per_s": 819e9}


def _ctx(before=BEFORE, after=AFTER, trace=TRACE, window_bytes=8.19e9):
    return run.Context(engine=(before, after), compiles=({"requests": 0},
                                                         {"requests": 0}),
                       trace=trace, window_bytes=window_bytes, peaks=PEAKS,
                       seconds=50.0)


def _reader(metric: str):
    return Cell(CELL).reader(metric)


@pytest.mark.parametrize("metric", sorted(EXPECTED) + ["hbm_roofline.khop"])
def test_cell_lists_each_new_reader(metric):
    names = [m["name"] for m in Cell(CELL).per_layer]
    assert metric in names
    assert callable(_reader(metric).read)


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_reader_reads_the_change_over_the_window(metric):
    assert _reader(metric).read(_ctx()) == pytest.approx(EXPECTED[metric])


def test_hbm_roofline_reads_reference_bytes_over_busy_time():
    # 8.19 GB at 819 GB/s is 10 ms of the 2 s busy: 0.5 %
    assert _reader("hbm_roofline.khop").read(_ctx()) == pytest.approx(0.5)


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_reader_is_silent_without_the_programs_table(metric):
    old = {k: v for k, v in BEFORE.items() if k != "trace"}
    assert _reader(metric).read(_ctx(before=old, after=old)) is None


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_reader_is_silent_without_hops(metric):
    # a program with the table but no one-pass hop (the parent's) reads
    # zero hops, spans and candidates
    bare = _snap(0, 0, {})
    assert _reader(metric).read(_ctx(before=bare, after=bare)) is None


def test_hbm_roofline_is_silent_without_a_trace():
    assert _reader("hbm_roofline.khop").read(_ctx(trace=None)) is None
