"""Thin collective helpers used inside shard_map bodies.

These exist so algorithm code states *what* it communicates (gather the
per-task rows, one round) rather than which jax.lax spelling this
version supports.

Every helper also feeds the telemetry byte ledger
(`collective.calls` / `collective.bytes` counters, tagged by op and
axis) so `benchmarks/communication.py` reports bytes the program
actually moved rather than a hand-maintained formula. The accounting
runs at TRACE time — these helpers execute inside shard_map tracing —
so the counts are per compilation, and the byte model is
local-shard nbytes × mesh-axis participants (what each device puts on
the wire for a ring collective of k shards). `jax.lax.psum(1, axis)`
on a Python int is concrete at trace time and emits no HLO, so the
participant lookup never perturbs the compiled program (the HLO probe
in benchmarks/communication.py pins this).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import obs


def _record(op: str, x, axis) -> None:
    if not obs.enabled():
        return
    label = axis if isinstance(axis, str) else "+".join(axis)
    try:
        k = int(jax.lax.psum(1, axis))
    except Exception:
        k = 0       # axis not bound (helper called outside shard_map)
        obs.inc("collective.axis_unbound", op=op, axis=label)
    nbytes = int(x.size) * x.dtype.itemsize
    obs.inc("collective.calls", op=op, axis=label)
    obs.inc("collective.bytes", k * nbytes, op=op, axis=label)


def all_gather_tasks(x: jnp.ndarray, axis) -> jnp.ndarray:
    """Gather shards along mesh `axis` (a name, or a tuple of names for
    every device of those axes), concatenated on dim 0 (tiled)."""
    _record("all_gather_tasks", x, axis)
    return jax.lax.all_gather(x, axis, tiled=True)


def all_to_all_experts(x: jnp.ndarray, axis: str, *, split_axis: int = 0,
                       concat_axis: int = 0) -> jnp.ndarray:
    """all_to_all over mesh `axis` (MoE dispatch/return)."""
    _record("all_to_all_experts", x, axis)
    return jax.lax.all_to_all(x, axis, split_axis, concat_axis, tiled=False)


def psum_stats(x: jnp.ndarray, axis: str) -> jnp.ndarray:
    """Sum partial sufficient statistics over mesh `axis`.

    The streaming accumulator computes per-device partial (Sigma, c)
    sums over the minibatch rows it owns and reduces them here — the
    additive-stats property is what makes engine-level SPMD a single
    psum instead of gathering raw samples.
    """
    _record("psum_stats", x, axis)
    return jax.lax.psum(x, axis)
