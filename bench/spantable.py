"""The change of the program's span and counter table over the window.

The serve engine reports its process's table of spans and counters
(``core/obs.py``) as ``stats["trace"]``: ``{"spans": {name: [calls,
total_ns]}, "counters": {name: n}}``. ``window(ctx)`` is the change of
that table between the engine snapshots at the window's start and close
(``ctx.engine``), or None where the program reports no table.
"""

from __future__ import annotations


class Delta:
    def __init__(self, before: dict, after: dict):
        self._before, self._after = before, after

    def _span(self, name: str, i: int) -> int:
        a = self._after["spans"].get(name, (0, 0))[i]
        return a - self._before["spans"].get(name, (0, 0))[i]

    def calls(self, span: str) -> int:
        return self._span(span, 0)

    def ns(self, span: str) -> int:
        return self._span(span, 1)

    def counter(self, name: str) -> int:
        return (self._after["counters"].get(name, 0)
                - self._before["counters"].get(name, 0))


def window(ctx) -> Delta | None:
    before, after = (eng.get("trace") for eng in ctx.engine)
    if before is None or after is None:
        return None
    return Delta(before, after)
