"""Requests answered in the window over the window's seconds, on the host
clock: the window runs from the first request sent to the last answer
ready on the device."""


def read(ctx):
    w = ctx.window
    return w.answered / w.elapsed_s if w.answered and w.elapsed_s > 0 \
        else None
