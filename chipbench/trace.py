"""From the profiler's trace to the event lists the metrics read.

`load(log_dir)` reads the `.xplane.pb` that `jax.profiler` wrote and
keeps three things, on one clock:

* `window`: [start, end] of the benchmark's `bench.window` annotation;
* `devices`: per accelerator, its `ops` and its `modules` (XLA programs)
  as [name, start, end];
* `host`: the benchmark's own `bench.*` annotations as
  [name, start, end, thread].

Times are in seconds. The reduced form is plain JSON, so a recorded
trace can sit beside the tests. Everything below reads only that form.
"""
from __future__ import annotations

import bisect
import glob
import os

DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
CONTROL_FLOW = ("while", "conditional", "call.")


def load(log_dir: str) -> dict:
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(paths[-1])
    devices, host = [], []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            dev = {"name": plane.name, "ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key:
                    dev[key] = [[ev.name, ev.start_ns * 1e-9,
                                 (ev.start_ns + ev.duration_ns) * 1e-9]
                                for ev in line.events]
            devices.append(dev)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend([ev.name, ev.start_ns * 1e-9,
                             (ev.start_ns + ev.duration_ns) * 1e-9, line.name]
                            for ev in line.events
                            if ev.name.startswith("bench."))
    windows = [h for h in host if h[0] == "bench.window"]
    if not windows:
        raise ValueError("the trace holds no bench.window annotation")
    if not devices:
        raise ValueError("the trace holds no TPU device plane")
    return {"window": windows[0][1:3], "devices": devices, "host": host}


def clip(events, window):
    """Events overlapping the window, cut to it."""
    lo, hi = window
    return [[e[0], max(e[1], lo), min(e[2], hi)] + list(e[3:])
            for e in events if e[2] > lo and e[1] < hi]


def union(intervals):
    """Merged [start, end] intervals, sorted."""
    out = []
    for _, a, b, *_ in sorted(intervals, key=lambda e: e[1]):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def busy_s(tr) -> float:
    """Seconds in which an operation ran, averaged over the devices."""
    tot = 0.0
    for dev in tr["devices"]:
        events = dev["ops"] or dev["modules"]
        tot += sum(b - a for a, b in union(clip(events, tr["window"])))
    return tot / len(tr["devices"])


def window_s(tr) -> float:
    return tr["window"][1] - tr["window"][0]


def module_time(tr, names) -> float:
    """Device seconds of the XLA programs whose name (up to any
    parenthesised id) is in `names`, summed over devices; raises when
    none ran, since a program that did not run has no time to share."""
    tot, seen = 0.0, False
    for dev in tr["devices"]:
        for name, a, b in clip(dev["modules"], tr["window"]):
            if module_base(name) in names:
                tot += b - a
                seen = True
    if not seen:
        raise LookupError(f"no {sorted(names)} program in the trace window")
    return tot


def module_base(name: str) -> str:
    return name.split("(")[0].strip()


def op_name(name: str) -> str:
    """An op's instruction name without its HLO text ("%fusion.3 = f32[..]
    fusion(..)" -> "%fusion.3")."""
    return name.split(" = ")[0].strip()


def top_ops(tr, k=10):
    """The k op names (prefixed by their program) that took most device
    time in the window, as [[name, seconds], ...]. Control flow (`while`,
    `conditional`, `call`) is left out: its time is that of the ops
    inside it, which are listed themselves."""
    tot = {}
    for dev in tr["devices"]:
        mods = sorted(clip(dev["modules"], tr["window"]), key=lambda e: e[1])
        starts = [m[1] for m in mods]
        for name, a, b in clip(dev["ops"], tr["window"]):
            short = op_name(name)
            if short.lstrip("%").startswith(CONTROL_FLOW):
                continue
            i = bisect.bisect_right(starts, a) - 1
            prog = module_base(mods[i][0]) if i >= 0 and mods[i][2] >= a \
                else "?"
            key = f"{prog}:{short}"
            tot[key] = tot.get(key, 0.0) + (b - a)
    return sorted(([n, s] for n, s in tot.items()), key=lambda e: -e[1])[:k]


def idle_gaps(tr, k=10):
    """The k longest idle gaps on the first device in the window, each
    named by the benchmark annotations open at its middle (the driver
    thread's first), as [[name, seconds], ...]."""
    dev = tr["devices"][0]
    busy = union(clip(dev["ops"] or dev["modules"], tr["window"]))
    lo, hi = tr["window"]
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    host = [h for h in tr["host"] if h[0] != "bench.window"]
    out = []
    for a, b in gaps[:k]:
        mid = 0.5 * (a + b)
        open_ = sorted({h[0] for h in host if h[1] <= mid <= h[2]})
        out.append(["+".join(open_) or "no bench span", b - a])
    return out
