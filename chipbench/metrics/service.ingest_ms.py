"""Mean duration of the program's `stream.ingest` span: on the guarded
path the cells take, the chunk's hand-off to the device, the fold, and
the wait for the fold's health probe."""


def read(ctx):
    h = ctx.hist("stream.ingest.ms")
    return h["mean"] if h else None
