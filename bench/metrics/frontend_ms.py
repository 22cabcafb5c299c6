"""frontend_ms: the frontend's own time per wire request, in ms.

Spans of the program (``core/obs.py``) over the window: the time of
``threadle.frontend.request`` (a wire request on its connection thread,
from the line read to the reply written) less that of
``threadle.frontend.wait`` (the wait for the engine's answer), per
request. It is the parsing, admission, submission and reply, and the
connection threads' share of the interpreter lock.
"""

from spantable import window


def read(ctx):
    d = window(ctx)
    if d is None or d.calls("threadle.frontend.request") <= 0:
        return None
    own = d.ns("threadle.frontend.request") - d.ns("threadle.frontend.wait")
    return own / d.calls("threadle.frontend.request") / 1e6
