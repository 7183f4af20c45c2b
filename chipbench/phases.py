"""The refit's phases on the device: which ops of `jit_refit` belong to
which phase, and how long each phase kept the device busy.

The program runs each phase of a refit under a `jax.named_scope`
(`PHASES`), so every op of the compiled program carries its phase in
its `op_name` metadata. The reduced trace (`trace.load`) keeps only op
names, so the map from op name to phase is read from the program: the
warm refit the window ran is lowered again from the cell's shapes and
compiled (JAX hands back the executable the run compiled, from its
caches), and the `op_name` of each instruction of its HLO is parsed.

`attach(ctx)` writes each device's `phases`, [[phase, start, end], ...]
with one entry per op of `jit_refit` that has a phase, into the trace
itself, so that a recorded trace (`run.py --record`) carries them and a
later read takes them from there. Phases run one after another, so an
op with no scope (XLA's own copies, say) takes the phase of the ops on
both sides of it in the same execution of the program, when those two
agree.
"""
from __future__ import annotations

import bisect
import re

from chipbench import trace

PROGRAM = "jit_refit"
PHASES = ("refit.power", "refit.lasso", "refit.msolve", "refit.debias",
          "refit.threshold")

_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?"
                          r"metadata=\{[^}]*?op_name=\"([^\"]*)\"")
_NAME = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")


def hlo_op_phases(hlo_text: str) -> dict:
    """{instruction name: phase or None} over every instruction of an
    HLO module's text; the phase is the first of `PHASES` in the
    instruction's `op_name`."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m:
            scopes = m.group(2).split("/")
            out[m.group(1)] = next((s for s in scopes if s in PHASES), None)
        else:
            m = _NAME.match(line)
            if m:
                out.setdefault(m.group(1), None)
    return out


def program_op_phases(cfg) -> dict:
    """`hlo_op_phases` of the warm refit of a one-chip cell, as the
    service calls it in the window."""
    import jax

    from chipbench.check import penalties
    from repro.stream.refit import refit
    from repro.stream.state import init_stream_state
    s = cfg["service"]
    lam, mu, Lam = penalties(cfg)
    state = jax.eval_shape(lambda: init_stream_state(cfg["m"], cfg["p"]))
    compiled = refit.lower(
        state, lam, mu, Lam, lasso_iters=s["warm_lasso_iters"],
        debias_iters=s["warm_debias_iters"], warm=True,
        tol=s["refit_tol"]).compile()
    return hlo_op_phases(compiled.as_text())


def _short(name: str) -> str:
    return trace.op_name(name).lstrip("%")


def executions(dev) -> list:
    """The ops of each execution of `PROGRAM` on one device, as lists of
    [name, start, end] sorted by start."""
    ops = sorted(dev["ops"], key=lambda e: e[1])
    starts = [o[1] for o in ops]
    out = []
    for name, a, b in dev["modules"]:
        if trace.module_base(name) == PROGRAM:
            out.append(ops[bisect.bisect_left(starts, a):
                           bisect.bisect_right(starts, b)])
    return out


def assign(ops, op_phase) -> list:
    """[[phase, start, end]] of one execution's ops: an op's own phase,
    or for an op with none, the phase shared by the nearest ops with a
    phase before and after it."""
    own = [op_phase.get(_short(o[0])) for o in ops]
    before, last = [], None
    for ph in own:
        before.append(last)
        last = ph or last
    after, last = [None] * len(own), None
    for i in range(len(own) - 1, -1, -1):
        after[i] = last
        last = own[i] or last
    out = []
    for (_, a, b), ph, pre, post in zip(ops, own, before, after):
        ph = ph or (pre if pre == post else None)
        if ph:
            out.append([ph, a, b])
    return out


def attach(ctx) -> bool:
    """Give every device of the cell's trace its `phases`, unless it has
    them already. Returns False where there is no trace, the run was not
    on the chip the trace describes, or the program compiled here is not
    the one traced (an op name of the trace is not in it)."""
    tr = ctx.trace
    if tr is None:
        return False
    if all("phases" in dev for dev in tr["devices"]):
        return True
    import jax
    if (ctx.cfg.get("mesh") is not None
            or not tr["devices"][0]["name"].startswith(trace.DEVICE_PREFIX)
            or jax.devices()[0].platform != "tpu"):
        return False
    op_phase = program_op_phases(ctx.cfg)
    runs = [executions(dev) for dev in tr["devices"]]
    if any(_short(o[0]) not in op_phase
           for dev_runs in runs for ops in dev_runs for o in ops):
        return False
    for dev, dev_runs in zip(tr["devices"], runs):
        dev["phases"] = [e for ops in dev_runs for e in assign(ops, op_phase)]
    return True


def busy_s(ctx, phase: str):
    """Seconds in which an op of `phase` ran in the window, summed over
    the devices; None when the trace holds no such op."""
    if not attach(ctx):
        return None
    tot, seen = 0.0, False
    for dev in ctx.trace["devices"]:
        mine = [e for e in dev["phases"] if e[0] == phase]
        seen = seen or bool(mine)
        tot += sum(b - a for a, b in trace.union(
            trace.clip(mine, ctx.trace["window"])))
    return tot if seen else None


def per_refit_ms(ctx, phase: str):
    """`busy_s` of `phase` per device and per refit in the window, in
    ms."""
    busy, refits = busy_s(ctx, phase), ctx.hist("stream.refit.lasso_iters")
    if busy is None or not refits:
        return None
    return 1e3 * busy / len(ctx.trace["devices"]) / refits["count"]
