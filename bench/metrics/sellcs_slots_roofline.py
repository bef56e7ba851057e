"""The SELL-C-σ kernel's share of its roofline: the least time of every
flush in the window (``bench/work.py``: the larger of the bytes a k-column
multiply needs over peak HBM bandwidth and its operations over peak
FLOP/s, k the columns served) over the kernel's device time, in %."""
from bench import work


def read(ctx):
    t = ctx.trace
    if t is None or not t.kernel_events or t.kernel_s <= 0:
        return None
    least = sum(work.least_time_s(ctx.m, ctx.n, ctx.nnz, k, ctx.peaks)
                for k in ctx.window.batch_k)
    return least / t.kernel_s * 100
