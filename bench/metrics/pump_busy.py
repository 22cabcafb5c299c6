"""pump_busy: share of the window the engine's pump spent in rounds, %.

Span ``threadle.engine.round`` of ``serve/graph_engine.py`` (one pump
round, from its pop to its results being stored): its time over the
window, as a percentage of the window's length. Near 100 the single
pump thread is the bottleneck, and requests queue behind it.
"""

from spantable import window


def read(ctx):
    d = window(ctx)
    if d is None or ctx.seconds <= 0:
        return None
    return 100.0 * d.ns("threadle.engine.round") / 1e9 / ctx.seconds
