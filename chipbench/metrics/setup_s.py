"""Seconds from process start until the window opened: data, service,
autotune lookup, compile or cache load, and the set-up refits."""


def read(ctx):
    return ctx.setup_s
