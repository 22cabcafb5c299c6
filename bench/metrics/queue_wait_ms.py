"""queue_wait_ms: mean wait of a point request in the engine's queue, ms.

Counters of ``serve/graph_engine.py`` over the window: the nanoseconds
from enqueue to a pump round's pop, summed over the point requests
popped (``engine.queue_wait_ns.point``), per request popped
(``engine.popped.point``). A request waits there while the round before
its own runs.
"""

from spantable import window


def read(ctx):
    d = window(ctx)
    if d is None or d.counter("engine.popped.point") <= 0:
        return None
    return (d.counter("engine.queue_wait_ns.point")
            / d.counter("engine.popped.point") / 1e6)
