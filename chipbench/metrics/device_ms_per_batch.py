"""Device busy milliseconds in the traced window per ``run_batch`` call."""


def read(ctx):
    if ctx.trace is None or not ctx.batches or ctx.trace.busy_s <= 0:
        return None
    return ctx.trace.busy_s / ctx.batches * 1e3
