"""hop_launches: device programs launched per k-hop hop.

Counters of ``core/traversal.py`` over the window: ``traversal.launches``
(the programs launched inside hops: one per pass) over
``traversal.hops`` (hops expanded). 1 unless a hop holds more candidates
than one program takes.
"""

from spantable import window


def read(ctx):
    d = window(ctx)
    if d is None or d.counter("traversal.hops") <= 0:
        return None
    return d.counter("traversal.launches") / d.counter("traversal.hops")
