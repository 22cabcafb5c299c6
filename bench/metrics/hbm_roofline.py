"""hbm_roofline: the device's busy time against the least the queries need.

The bytes the plain reference reads to answer every request answered in
the traced window (``graphref.py``: 8 B per row located, 4 B per id
read) over the chip's HBM bandwidth (``peaks.json``) is the least time
the chip could spend on them; this is that time as a percentage of the
device's busy time in the window. It counts the work the queries need,
not the work a kernel does, so padding, bucket widths and which kernel
runs move the time and never the bytes.
"""


def read(ctx):
    t = ctx.trace
    if t is None or ctx.peaks is None or t["busy_s"] <= 0 \
            or ctx.window_bytes <= 0:
        return None
    least_s = ctx.window_bytes / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / t["busy_s"]
