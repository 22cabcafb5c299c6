"""copies_per_kcand: window copies the segment-copy kernel issues per
thousand candidates of a one-pass k-hop.

Counters of ``core/traversal.py`` over the window: ``traversal.copies``
(one per piece of a segment in a block of the kernel's slots, which the
host counts from the hop's plan) over ``traversal.cand`` (the real
candidates), times 1000. Near 1 where runs span whole blocks; 1000
where every run is one id.
"""

from spantable import window


def read(ctx):
    d = window(ctx)
    if d is None or d.counter("traversal.copies") <= 0 \
            or d.counter("traversal.cand") <= 0:
        return None
    return 1000.0 * d.counter("traversal.copies") / d.counter("traversal.cand")
