"""Profiler trace -> device busy time, top device ops, labelled idle gaps.

``events(path)`` reads an ``.xplane.pb`` with ``jax.profiler.ProfileData``
into plain tuples ``(plane, line, name, start_ns, end_ns)``. ``reduce``
works on those tuples alone, so it is checked on a small recorded trace
without JAX.

* Device planes are the planes named ``/device:...`` (one per chip). On
  each, the ops are the events of its ``XLA Ops`` line, or of every line
  where it has none.
* The window is the host span ``bench.window`` (the measured window, on
  the trace's clock), else the extent of the device events.
* ``busy_s`` is the length of the union of op intervals inside the window,
  averaged over the chips; ``window_s`` the window's length.
* ``device_ops`` sums each op's time inside the window (chip 0's plane),
  largest first, under ``<program>:<op>``: the enclosing event of the
  ``XLA Modules`` line without its hash, and the op's HLO name.
* ``idle_gaps`` sums the gaps between ops on chip 0 by what the host was
  doing at each gap's middle: the innermost of the benchmark's spans open
  then (``exec.<kind>`` or ``dispatch.plan`` inside ``engine.pump``),
  ``frontend.line`` when only connection threads were busy, or
  ``no request in flight``.
"""

from __future__ import annotations

from pathlib import Path

import numpy as np

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10
# span names in the order of precedence for a gap's label (innermost first)
LABELS = ("dispatch.plan", "exec.", "engine.pump", "frontend.line")
NO_REQUEST = "no request in flight"


def find_xplane(trace_dir: Path) -> Path | None:
    found = sorted(Path(trace_dir).glob("**/*.xplane.pb"))
    return found[-1] if found else None


def events(path: Path) -> list[tuple]:
    """The events ``reduce`` reads: device ops and programs, and the
    host's window and layer spans (the host's own runtime events, most
    of a trace, are skipped)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    out = []
    for plane in data.planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                name = ev.name
                if not device and name != WINDOW_SPAN \
                        and not name.startswith(LABELS):
                    continue
                start = float(ev.start_ns)
                out.append((plane.name, line.name, name, start,
                            start + float(ev.duration_ns)))
    return out


def _union(iv: np.ndarray) -> np.ndarray:
    """Sorted, merged [start, end) intervals."""
    if iv.size == 0:
        return iv.reshape(0, 2)
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    ends = np.maximum.accumulate(iv[:, 1])
    new = np.ones(len(iv), bool)
    new[1:] = iv[1:, 0] > ends[:-1]
    first = np.flatnonzero(new)
    last = np.append(first[1:] - 1, len(iv) - 1)
    return np.stack([iv[first, 0], ends[last]], axis=1)


def _clip(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    iv = np.clip(iv, lo, hi)
    return iv[iv[:, 1] > iv[:, 0]]


def _contains(iv: np.ndarray, t: np.ndarray) -> np.ndarray:
    """bool per t: inside one of the sorted, disjoint intervals ``iv``."""
    if iv.size == 0:
        return np.zeros(t.shape, bool)
    k = np.searchsorted(iv[:, 0], t, side="right") - 1
    ok = k >= 0
    return ok & (iv[np.maximum(k, 0), 1] > t)


def reduce(evs: list[tuple]) -> dict | None:
    """-> {"chips", "busy_s", "window_s", "device_ops", "idle_gaps"}, or
    None where the trace has no device plane with ops."""
    planes: dict = {}
    for plane, line, name, s, e in evs:
        if plane.startswith("/device:"):
            planes.setdefault(plane, []).append((line, name, s, e))
    ops = {}
    for plane, pevs in sorted(planes.items()):
        lines = {line for line, *_ in pevs}
        keep = [(n, s, e) for line, n, s, e in pevs
                if line == OPS_LINE or OPS_LINE not in lines]
        if keep:
            ops[plane] = keep
    if not ops:
        return None
    win = [(s, e) for plane, _, name, s, e in evs
           if name == WINDOW_SPAN and not plane.startswith("/device:")]
    if win:
        lo, hi = win[0]
    else:
        lo = min(s for v in ops.values() for _, s, _ in v)
        hi = max(e for v in ops.values() for _, _, e in v)
    window_ns = hi - lo
    busy = []
    first = None
    for plane, keep in ops.items():
        iv = _clip(np.asarray([(s, e) for _, s, e in keep], float), lo, hi)
        u = _union(iv)
        busy.append(float((u[:, 1] - u[:, 0]).sum()))
        if first is None:
            first = (keep, u)
    keep, u = first
    chip0 = next(iter(ops))
    modules = sorted((s, e, n) for p, line, n, s, e in evs
                     if p == chip0 and line == MODULES_LINE)
    starts = np.asarray([m[0] for m in modules], float)
    per_op: dict = {}
    for name, s, e in keep:
        d = min(e, hi) - max(s, lo)
        if d > 0:
            label = _op_label(name, modules, starts, s)
            per_op[label] = per_op.get(label, 0.0) + d
    top_ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP]
    edges = np.concatenate([[lo], u.reshape(-1), [hi]]).reshape(-1, 2)
    gaps = edges[edges[:, 1] > edges[:, 0]]
    return {
        "chips": len(ops),
        "busy_s": float(np.mean(busy)) / 1e9,
        "window_s": window_ns / 1e9,
        "device_ops": [[n, d / 1e9] for n, d in top_ops],
        "idle_gaps": _label_gaps(evs, gaps),
    }


def _op_label(name: str, modules: list, starts: np.ndarray,
              t: float) -> str:
    """``<program>:<op>`` from an HLO instruction's text and the module
    event that encloses time ``t``."""
    op = name.split(" = ")[0].strip().lstrip("%")
    k = int(np.searchsorted(starts, t, side="right")) - 1
    if k >= 0 and modules[k][1] >= t:
        return f"{modules[k][2].split('(')[0]}:{op}"
    return op


def _label_gaps(evs: list[tuple], gaps: np.ndarray) -> list:
    if gaps.size == 0:
        return []
    mid = gaps.mean(axis=1)
    length = gaps[:, 1] - gaps[:, 0]
    label = np.full(mid.shape, NO_REQUEST, dtype=object)
    spans: dict = {}
    for plane, _, name, s, e in evs:
        if plane.startswith("/device:"):
            continue
        for prefix in LABELS:
            if name.startswith(prefix):
                spans.setdefault(name, []).append((s, e))
                break
    def depth(name: str) -> int:
        return next(i for i, p in enumerate(LABELS) if name.startswith(p))

    # outermost first, so that inner spans overwrite the label
    for name in sorted(spans, key=depth, reverse=True):
        inside = _contains(_union(np.asarray(spans[name], float)), mid)
        label[inside] = name
    totals: dict = {}
    for lab, d in zip(label, length):
        totals[lab] = totals.get(lab, 0.0) + float(d)
    top = sorted(totals.items(), key=lambda kv: -kv[1])[:TOP]
    return [[n, d / 1e9] for n, d in top]
