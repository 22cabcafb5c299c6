"""fetch_ms: time spent copying device results to the host, per group, ms.

Spans of the program over the window: the time of
``threadle.dispatch.fetch`` (each device-to-host copy on the serving
path, with the wait for the device's result before it) per
``threadle.engine.group`` (one coalesced group's executor call).
"""

from spantable import window


def read(ctx):
    d = window(ctx)
    if d is None or d.calls("threadle.engine.group") <= 0:
        return None
    return (d.ns("threadle.dispatch.fetch")
            / d.calls("threadle.engine.group") / 1e6)
