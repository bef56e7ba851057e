"""The whole serving loop's share of the chip's roofline: the least time
of every flush in the window (``bench/work.py``) over the traced window's
length, in %. It bounds every kernel's share, whatever the flush runs."""
from bench import work


def read(ctx):
    t = ctx.trace
    if t is None or t.window_s <= 0 or not ctx.window.batch_k:
        return None
    least = sum(work.least_time_s(ctx.m, ctx.n, ctx.nnz, k, ctx.peaks)
                for k in ctx.window.batch_k)
    return least / t.window_s * 100
