"""Mean duration of the program's `stream.ingest.probe` span on a mesh:
the guard's health probe of each shard where it was fed, its one
reduction and the verdict (true latency)."""


def read(ctx):
    h = ctx.hist("stream.ingest.probe.ms")
    return h["mean"] if h else None
