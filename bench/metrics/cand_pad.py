"""cand_pad: candidate slots the device processed per real candidate.

Counters of ``core/traversal.py`` over the window: ``traversal.slots``
(the padded candidate slots of every hop pass) over ``traversal.cand``
(the real candidates, the sum of the frontier's degrees). 1 is no
padding; the power-of-two slot classes and their floor keep it above.
"""

from spantable import window


def read(ctx):
    d = window(ctx)
    if d is None or d.counter("traversal.cand") <= 0:
        return None
    return d.counter("traversal.slots") / d.counter("traversal.cand")
