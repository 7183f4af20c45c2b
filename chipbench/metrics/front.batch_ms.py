"""Mean duration of the program's `serve.batch` span: one microbatch's
dispatch and its wait on the device queue."""


def read(ctx):
    h = ctx.hist("serve.batch.ms")
    return h["mean"] if h else None
