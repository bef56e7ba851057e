"""Device nanoseconds of the Mosaic kernel per flush over the matrix's
stored nonzeros."""


def read(ctx):
    t, flushes = ctx.trace, ctx.window.flushes
    if t is None or not flushes or not t.kernel_events:
        return None
    return t.kernel_s / flushes / ctx.nnz * 1e9
