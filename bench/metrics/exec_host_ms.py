"""exec_host_ms: host time of one group executor call, less its fetches, ms.

Spans of the program over the window: the time of
``threadle.engine.group`` (one coalesced group's executor call) less the
time of ``threadle.dispatch.fetch`` (each copy of a device result to the
host, with the wait for the device before it), per group. It is the
executors' host work: planning, building and padding arrays, launching
bucket programs and the glue between them.
"""

from spantable import window


def read(ctx):
    d = window(ctx)
    if d is None or d.calls("threadle.engine.group") <= 0:
        return None
    host = d.ns("threadle.engine.group") - d.ns("threadle.dispatch.fetch")
    return host / d.calls("threadle.engine.group") / 1e6
