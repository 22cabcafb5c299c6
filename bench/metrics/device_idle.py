"""device_idle: share of the traced window in which no op ran on the chip.

From the profiler trace (``trace.py``): 100 * (1 - busy / window), busy
being the union of the device's op intervals inside the measured window.
"""


def read(ctx):
    t = ctx.trace
    if t is None or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
