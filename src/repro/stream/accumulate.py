"""Sharded streaming accumulation: engine-level SPMD over a data × task mesh.

Minibatches arrive sharded over the `data` mesh axis (each device owns a
slice of the rows) with tasks sharded over `task`. Every device reduces
its rows to partial unnormalized `(Sigma, c)` sums — a local einsum —
and one `psum_stats` over `data` turns them into the full-chunk
statistics, task-sharded and replicated along `data`. That is the whole
communication story: O(m_local * p^2) per device per chunk, no raw
sample ever crosses a device boundary, and the reduction is the same
additivity that makes `StreamState.ingest` exact.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.stream.state import StreamState, ingest_stats
from repro.substrate import psum_stats, shard_map


def accumulate_stats_fn(mesh: Mesh, data_axis: str = "data",
                        task_axis: str = "task"):
    """The shard-mapped accumulator as a callable (X, y) -> (S, c).

    X (m, n, p) sharded (task, data, -); returns UNNORMALIZED sums
    S = X'X (m, p, p), c = X'y (m, p) over the whole chunk, sharded
    over `task_axis` and replicated along `data_axis` (divide by the
    chunk's n for the mean convention). Exposed separately so probes
    can lower the actual implementation and count its collectives.
    """

    def worker(X_blk, y_blk):
        # X_blk: (m_local, n_local, p) — this device's rows of its tasks.
        S_part = jnp.einsum("tni,tnj->tij", X_blk, X_blk)
        c_part = jnp.einsum("tni,tn->ti", X_blk, y_blk)
        S = psum_stats(S_part, data_axis)
        c = psum_stats(c_part, data_axis)
        return S, c

    return shard_map(
        worker,
        mesh=mesh,
        in_specs=(P(task_axis, data_axis, None), P(task_axis, data_axis)),
        out_specs=(P(task_axis, None, None), P(task_axis, None)),
    )


@partial(jax.jit, static_argnames=("mesh", "data_axis", "task_axis"))
def ingest_sharded(state: StreamState, X_batch: jnp.ndarray,
                   y_batch: jnp.ndarray, mesh: Mesh, decay=1.0,
                   data_axis: str = "data",
                   task_axis: str = "task") -> StreamState:
    """`stream.state.ingest` with the row reduction run SPMD over `mesh`.

    The state merge itself is elementwise over tasks, so it composes
    with whatever task sharding the caller keeps the state in; the
    reduction, the chunk means and the merge are one compiled program.
    """
    n = X_batch.shape[1]
    S_sum, c_sum = accumulate_stats_fn(mesh, data_axis, task_axis)(
        X_batch, y_batch)
    return ingest_stats(state, S_sum / n, c_sum / n, n, decay)
