"""Rows of all tasks that the service folded, over all the time of the
window (refits included: they run on the same driver thread)."""


def read(ctx):
    w = ctx.window
    return w["rows_folded"] / w["window_s"] if w["rows_folded"] else None
