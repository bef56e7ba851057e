"""Mean per flush of the batcher's ``batcher/pad`` and ``batcher/scatter``
spans (host clock; with a registry installed the batcher blocks on the
padded X, so pad includes its device time)."""


def read(ctx):
    reg, flushes = ctx.registry, ctx.window.flushes
    if reg is None or not flushes:
        return None
    pad = reg.histogram("batcher/pad")
    scatter = reg.histogram("batcher/scatter")
    if not pad.count:
        return None
    return (pad.total + scatter.total) / flushes * 1e3
