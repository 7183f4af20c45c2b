"""How long the other chips wait for the slowest one in a refit on a
mesh: per refit in the window, the slowest chip's `jit_refit` device time
minus the mean over the chips, averaged over the refits."""
from chipbench import mesh_phases


def read(ctx):
    if ctx.trace is None or ctx.cfg.get("mesh") is None:
        return None
    times = mesh_phases.program_times(ctx.trace)
    refits = min(len(t) for t in times)
    if not refits or len(times) < 2:
        return None
    per_refit = [max(t[i] for t in times) - sum(t[i] for t in times)
                 / len(times) for i in range(refits)]
    return 1e3 * sum(per_refit) / refits
