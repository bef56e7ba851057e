"""Everything before the window: start-up, the matrix made from the seed,
its registration, the warm-up pass that compiles every flush shape."""


def read(ctx):
    return ctx.setup_s
