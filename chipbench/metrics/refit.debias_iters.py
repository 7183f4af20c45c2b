"""Mean debias (M solve) iterations run per refit in the window."""


def read(ctx):
    h = ctx.hist("stream.refit.debias_iters")
    return h["mean"] if h else None
