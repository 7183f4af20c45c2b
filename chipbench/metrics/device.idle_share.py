"""Share of the traced window in which no operation ran on the device
(averaged over the chips used)."""
from chipbench import trace


def read(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * (1.0 - trace.busy_s(ctx.trace) / trace.window_s(ctx.trace))
