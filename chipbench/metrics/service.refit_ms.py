"""Mean duration of the program's `stream.refit` span, which blocks on
the refreshed model inside it."""


def read(ctx):
    h = ctx.hist("stream.refit.ms")
    return h["mean"] if h else None
