"""Mean duration of the program's `stream.ingest.feed` span on a mesh:
a chunk's placement over the devices, until every shard is resident."""


def read(ctx):
    h = ctx.hist("stream.ingest.feed.ms")
    return h["mean"] if h else None
