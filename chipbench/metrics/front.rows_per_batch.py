"""Mean rows per microbatch (`serve.batch_rows`)."""


def read(ctx):
    h = ctx.hist("serve.batch_rows")
    return h["mean"] if h else None
