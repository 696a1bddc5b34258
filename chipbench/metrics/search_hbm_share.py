"""Least bytes of the window's search batches (the pages the index selected
and every entry's bitmap, ``costs``) over the chip's published HBM
bandwidth, as a share of the device's busy time in the window."""


def read(ctx):
    if (ctx.trace is None or ctx.trace.busy_s <= 0 or not ctx.batches
            or not ctx.least_bytes):
        return None
    least_s = ctx.least_bytes / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / ctx.trace.busy_s
