"""buckets: degree-bucket programs launched per request dispatched.

Counter ``dispatch.buckets`` of ``core/dispatch.py`` (one per bucket
program launched) over the window, per request the engine dispatched
(the change of ``dispatched``, summed over request kinds). Each launch
is a jitted program, a scatter back and, later, a copy to the host.
"""

from spantable import window


def read(ctx):
    d = window(ctx)
    eng0, eng1 = ctx.engine
    sent = sum(eng1["dispatched"].values()) - sum(eng0["dispatched"].values())
    if d is None or sent <= 0:
        return None
    return d.counter("dispatch.buckets") / sent
