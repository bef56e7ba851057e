"""Device-busy milliseconds per flush outside the Mosaic kernel and the
clients' vector generator: the batcher's stack and pad, the k-tile pad of
X, the σ-unpermute of Y and the column slices."""


def read(ctx):
    t, flushes = ctx.trace, ctx.window.flushes
    if t is None or not flushes or not t.busy_s:
        return None
    return t.glue_s / flushes * 1e3
