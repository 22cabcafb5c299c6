"""hop_ms: host wall time of one k-hop hop, ms.

Span ``threadle.traversal.hop`` of ``core/traversal.py`` (one hop of the
one-pass expansion: its program's launch and the fetch of its kept ids,
which waits for the device) over the window, per hop.
"""

from spantable import window


def read(ctx):
    d = window(ctx)
    if d is None or d.calls("threadle.traversal.hop") <= 0:
        return None
    return d.ns("threadle.traversal.hop") / d.calls(
        "threadle.traversal.hop") / 1e6
