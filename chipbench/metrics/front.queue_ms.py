"""Mean of the program's `serve.queue_ms`: how long a predict request
waited in the front's queue, from its submit until its microbatch was
dispatched."""


def read(ctx):
    h = ctx.hist("serve.queue_ms")
    return h["mean"] if h else None
